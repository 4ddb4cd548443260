"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in repro.kernels.ref."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref, ssd_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.models import layers as L
from repro.models.ssd import ssd_chunked
from repro.sharding.rules import TRAIN_RULES, ShardingRules, use_rules
from repro.utils import trace


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-3, atol=2e-3
    )


# --------------------------------------------------------------------------- #
# Flash attention
# --------------------------------------------------------------------------- #
FLASH_CASES = [
    # (b, hq, hkv, sq, sk, d, causal, dtype)
    (1, 4, 4, 256, 256, 64, True, jnp.float32),     # MHA causal
    (2, 8, 2, 256, 256, 128, True, jnp.float32),    # GQA
    (1, 8, 1, 128, 128, 64, True, jnp.float32),     # MQA
    (1, 4, 4, 128, 384, 64, False, jnp.float32),    # cross-shaped, bidir
    (2, 4, 2, 256, 256, 64, True, jnp.bfloat16),    # bf16
    (1, 2, 2, 512, 512, 128, True, jnp.bfloat16),   # larger seq bf16
    (1, 4, 4, 128, 128, 32, False, jnp.float32),    # small head_dim
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(c[:7]) for c in FLASH_CASES])
def test_flash_attention_matches_ref(case):
    b, hq, hkv, sq, sk, d, causal, dtype = case
    ks = jax.random.split(jax.random.key(hash(case[:7]) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_flash_attention_block_shapes():
    """Block size must not change the result (pure tiling parameter)."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4, 512, 64), jnp.float32)
    base = flash_attention(q, k, v, causal=True, interpret=True)
    for bq, bk in [(64, 64), (128, 256), (256, 128), (512, 512)]:
        out = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(base), rtol=1e-5, atol=1e-5,
            err_msg=f"block ({bq},{bk})",
        )


def test_flash_attention_long_causal_row_sums():
    """Each causal row attends only to columns <= row: verify via a probe
    value pattern (v = one-hot positions)."""
    sq = 256
    q = jnp.ones((1, 1, sq, 64), jnp.float32)
    k = jnp.zeros((1, 1, sq, 64), jnp.float32)   # uniform scores
    v = jnp.broadcast_to(
        jnp.arange(sq, dtype=jnp.float32)[None, None, :, None], (1, 1, sq, 64)
    )
    out = flash_attention(q, k, v, causal=True, interpret=True)
    # Uniform attention over first (i+1) positions -> mean of 0..i = i/2.
    want = jnp.arange(sq, dtype=jnp.float32) / 2.0
    np.testing.assert_allclose(
        np.asarray(out[0, 0, :, 0]), np.asarray(want), rtol=1e-4, atol=1e-4
    )


@given(
    b=st.integers(1, 2),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    sq_blocks=st.integers(1, 3),
    d=st.sampled_from([32, 64]),
)
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(b, hkv, group, sq_blocks, d):
    sq = 128 * sq_blocks
    hq = hkv * group
    ks = jax.random.split(jax.random.key(b * 1000 + hq * 10 + sq), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, sq, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, sq, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# The train cells' attention layouts: (name, hq, hkv, d, scale).
TRAIN_LAYOUTS = [
    ("smollm", 9, 3, 64, 0.125),
    ("olmo", 16, 16, 128, 128 ** -0.5),
    ("granite", 32, 8, 64, 0.015625),
]


def _bshd_inputs(hq, hkv, d, s=256, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, hkv, d), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, s, hq, d), jnp.float32)
    return q, k, v, w


def _value_and_grads(attn, q, k, v, w):
    """The output and the gradients in q, k and v of sum(attn(q, k, v) * w)."""
    out, vjp = jax.vjp(attn, q, k, v)
    return [out, *vjp(w.astype(out.dtype))]


def _kernel_bshd(scale, block):
    def attn(q, k, v):
        o = flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                            causal=True, scale=scale, block_q=block, block_k=block,
                            interpret=True)
        return o.swapaxes(1, 2)
    return attn


@pytest.mark.parametrize("layout", TRAIN_LAYOUTS, ids=[c[0] for c in TRAIN_LAYOUTS])
def test_flash_attention_grads_match_xla_path_and_ref(layout):
    """bf16, causal, two blocks a sequence: the kernel's output and its
    gradients in q, k and v match `_attn_core`'s XLA path and the float32
    oracle."""
    _, hq, hkv, d, scale = layout
    q, k, v, w = _bshd_inputs(hq, hkv, d)
    got = _value_and_grads(_kernel_bshd(scale, 128), q, k, v, w)
    xla = _value_and_grads(
        lambda q, k, v: L._attn_xla(q, k, v, causal=True, scale=scale, q_offset=0,
                                    kv_valid_len=None, q_chunk=128, allow_head_shard=True),
        q, k, v, w)

    def ref_attn(q, k, v):
        f32 = [x.astype(jnp.float32).swapaxes(1, 2) for x in (q, k, v)]
        return attention_ref(*f32, causal=True, scale=scale).swapaxes(1, 2)

    ref = _value_and_grads(ref_attn, q, k, v, w)
    for name, g, x, r in zip(("out", "dq", "dk", "dv"), got, xla, ref):
        assert g.dtype == jnp.bfloat16, name
        g, x, r = (np.asarray(a, np.float32) for a in (g, x, r))
        for want in (x, r):
            np.testing.assert_allclose(g, want, **_tol(jnp.bfloat16), err_msg=name)
            assert np.linalg.norm(g - want) <= 1e-2 * np.linalg.norm(want), name


def test_flash_attention_grads_are_block_independent():
    """The backward kernels' tiling is a parameter, not a result: float32
    gradients agree across block shapes that split the diagonal differently."""
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    w = jax.random.normal(ks[3], (1, 4, 512, 64), jnp.float32)

    def grads(bq, bk):
        return _value_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True), q, k, v, w)

    base = grads(512, 512)
    for bq, bk in [(128, 128), (128, 256), (256, 128)]:
        for name, a, b in zip(("out", "dq", "dk", "dv"), grads(bq, bk), base):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                                       err_msg=f"{name} block ({bq},{bk})")


def _smollm_attention_args(s=512):
    cfg = get_config("smollm-135m")
    p = jax.eval_shape(lambda: {
        "wq": jnp.zeros((cfg.d_model, cfg.num_heads, cfg.head_dim), jnp.float32),
        "wk": jnp.zeros((cfg.d_model, cfg.num_kv_heads, cfg.head_dim), jnp.float32),
        "wv": jnp.zeros((cfg.d_model, cfg.num_kv_heads, cfg.head_dim), jnp.float32),
        "wo": jnp.zeros((cfg.num_heads, cfg.head_dim, cfg.d_model), jnp.float32)})
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16)
    return cfg, p, x


def test_attention_lowered_for_the_cpu_takes_the_xla_path():
    """The training call fits the kernel, but the platform the step is
    lowered for decides: on the CPU the XLA path, counted `attn.xla`."""
    cfg, p, x = _smollm_attention_args()

    def loss(p, x):
        return L.attention(p, cfg, x, positions=None)[0].astype(jnp.float32).sum()

    with trace.recording() as rec:
        text = jax.jit(jax.grad(loss)).lower(p, x).as_text()
    assert rec.counters.get("attn.xla", 0) > 0
    assert rec.counters.get("attn.kernel", 0) == 0
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("case", ["train", "given_positions", "prefill", "decode", "cross",
                                  "seq_not_whole_blocks", "head_dim_32", "mesh"])
def test_kernel_fits_only_cacheless_causal_self_attention(case):
    """What `_attn_core` hands the kernel where the lowering is for a TPU:
    the training call, and none of the calls that must keep the XLA path."""
    s = 384 if case == "seq_not_whole_blocks" else (1 if case == "decode" else 512)
    d = 32 if case == "head_dim_32" else 64
    q = jnp.zeros((1, s, 9, d), jnp.bfloat16)
    k = jnp.zeros((1, 32 if case == "cross" else s, 3, d), jnp.bfloat16)
    kwargs = dict(causal=case != "cross", q_offset=0, kv_valid_len=None)
    if case == "given_positions":
        kwargs["q_offset"] = jnp.asarray(0, jnp.int32)
    if case in ("prefill", "decode"):
        kwargs["kv_valid_len"] = jnp.asarray(s, jnp.int32)
    rules = None
    if case == "mesh":   # two mesh slots, whatever devices the process has
        devices = np.array(jax.devices()[:1] * 2).reshape(2, 1)
        rules = ShardingRules(jax.sharding.Mesh(devices, ("data", "model")), TRAIN_RULES)
    with use_rules(rules):
        assert L._kernel_fits(q, k, **kwargs) == (case == "train")


# --------------------------------------------------------------------------- #
# SSD scan
# --------------------------------------------------------------------------- #
SSD_CASES = [
    # (b, s, h, g, p, n, chunk, dtype)
    (1, 128, 4, 1, 32, 32, 32, jnp.float32),
    (2, 256, 8, 2, 64, 64, 64, jnp.float32),
    (1, 512, 4, 4, 64, 128, 128, jnp.float32),
    (1, 256, 4, 1, 64, 128, 256, jnp.float32),   # single chunk
    (2, 256, 4, 1, 32, 64, 64, jnp.bfloat16),
]


def _ssd_inputs(case):
    b, s, h, g, p, n, chunk, dtype = case
    ks = jax.random.split(jax.random.key(hash(case[:7]) % 2**31), 4)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt_a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.3
    bp = (jax.random.normal(ks[2], (b, s, g, n)) * 0.3).astype(dtype)
    cp = (jax.random.normal(ks[3], (b, s, g, n)) * 0.3).astype(dtype)
    return x, dt_a, bp, cp


@pytest.mark.parametrize("case", SSD_CASES, ids=[str(c[:7]) for c in SSD_CASES])
def test_ssd_kernel_matches_sequential_ref(case):
    chunk, dtype = case[6], case[7]
    x, dt_a, bp, cp = _ssd_inputs(case)
    y_k, h_k = ssd_scan(x, dt_a, bp, cp, chunk=chunk, interpret=True)
    y_r, h_r = ssd_ref(x, dt_a, bp, cp)
    assert y_k.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(y_k, np.float32), np.asarray(y_r, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(
        np.asarray(h_k), np.asarray(h_r), **_tol(dtype)
    )


@pytest.mark.parametrize("case", SSD_CASES[:3], ids=[str(c[:7]) for c in SSD_CASES[:3]])
def test_ssd_chunked_jnp_matches_sequential_ref(case):
    """The model's chunked jnp path (dry-run path) against the recurrence."""
    chunk = case[6]
    x, dt_a, bp, cp = _ssd_inputs(case)
    y_c, h_c = ssd_chunked(x, dt_a, bp, cp, chunk)
    y_r, h_r = ssd_ref(x, dt_a, bp, cp)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_r),
                               rtol=2e-3, atol=2e-3)


def test_ssd_kernel_initial_state_continuation():
    """Splitting a sequence and passing the carry state must equal one
    pass over the full sequence (the decode/prefill contract)."""
    case = (1, 256, 4, 1, 32, 64, 64, jnp.float32)
    x, dt_a, bp, cp = _ssd_inputs(case)
    y_full, h_full = ssd_scan(x, dt_a, bp, cp, chunk=64, interpret=True)
    half = 128
    y1, h1 = ssd_scan(x[:, :half], dt_a[:, :half], bp[:, :half], cp[:, :half],
                      chunk=64, interpret=True)
    y2, h2 = ssd_scan(x[:, half:], dt_a[:, half:], bp[:, half:], cp[:, half:],
                      chunk=64, initial_state=h1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


@given(
    s_chunks=st.integers(1, 4),
    h=st.sampled_from([2, 4]),
    g=st.sampled_from([1, 2]),
    p=st.sampled_from([16, 32]),
    n=st.sampled_from([16, 64]),
)
@settings(max_examples=10, deadline=None)
def test_ssd_property(s_chunks, h, g, p, n):
    if h % g:
        g = 1
    chunk = 32
    case = (1, chunk * s_chunks, h, g, p, n, chunk, jnp.float32)
    x, dt_a, bp, cp = _ssd_inputs(case)
    y_k, h_k = ssd_scan(x, dt_a, bp, cp, chunk=chunk, interpret=True)
    y_r, h_r = ssd_ref(x, dt_a, bp, cp)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=5e-3, atol=5e-3)
