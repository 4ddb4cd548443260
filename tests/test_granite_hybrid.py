"""granite-4.0-h-micro on the program's normal path against the plain
reference (`repro.models.ref_hybrid`), on seeded random weights at
`reduced()` size: the loss and its gradients, and the serving engine's
prefill and decode through the attention and Mamba caches side by side.

The program computes in bfloat16 and the reference in float32, so every
tolerance below is a bfloat16 tolerance, set between what the program
reads and what a program that leaves out the conv bias or the attention
multiplier reads (both are measured here, and must fail).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.models import lm as LM
from repro.models import ref_hybrid as R
from repro.models import ssd
from repro.models.spec import ParamSpec, _materialize
from repro.serve import ServeEngine
from repro.serve.engine import Request

ARCH = "granite-4.0-h-micro"
SEQ = 40          # not a multiple of the reduced chunk (32)
BATCH = 2
PROMPT, GEN = 21, 5

# bfloat16 activations through ten layers, read over seeds 0, 1, 2 and 5:
# the loss's relative gap at most 7e-6 (it averages the rounding away), the
# worst leaf's gradient-norm gap 0.056 of the median leaf's, the served
# logits 0.17 of the reference logits' spread. Left out, the conv bias reads
# a gradient gap of at least 1.0 and logits of 3.2; the attention multiplier
# 1.58 and 0.50. Its loss gap reads 5e-7 to 7e-5 and tells neither.
LOSS_TOL = 1e-4
GRAD_TOL = 0.3
LOGIT_TOL = 0.3


def reduced():
    cfg = get_config(ARCH).reduced()
    assert cfg.ssm_chunk == 32 and SEQ % cfg.ssm_chunk
    return cfg


def ref_config(cfg) -> dict:
    """The reference's keys (the published config.json's names) from the
    program's configuration."""
    return {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "attention_multiplier": get_config(ARCH).attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": 1.0 / cfg.logit_scale,
        "rms_norm_eps": cfg.norm_eps,
        "mamba_n_heads": cfg.ssm_nheads,
        "mamba_d_head": cfg.ssm_head_dim,
        "mamba_n_groups": cfg.ssm_ngroups,
        "mamba_d_state": cfg.ssm_state,
        "mamba_chunk_size": cfg.ssm_chunk,
        "layer_types": ["attention" if b.mixer == "attn" else "mamba"
                        for b in cfg.pattern] * cfg.periods,
        "vocab_size": cfg.vocab_size,
    }


def weights(cfg, seed: int) -> dict:
    """Each leaf drawn from `fold_in(key, leaf index)` by its spec's init,
    and every bias and norm scale drawn too, so that each term of the block
    moves the result."""
    specs, treedef = jax.tree_util.tree_flatten_with_path(
        make_model(cfg).spec(), is_leaf=lambda x: isinstance(x, ParamSpec))
    out = []
    for i, (path, spec) in enumerate(specs):
        name, key = jax.tree_util.keystr(path), jax.random.fold_in(jax.random.key(seed), i)
        if "bias" in name and "dt_bias" not in name:
            leaf = 0.5 * jax.random.normal(key, spec.shape)
        elif "scale" in name or "d_skip" in name:
            leaf = 1.0 + 0.2 * jax.random.normal(key, spec.shape)
        else:
            leaf = _materialize(spec, key, jnp.float32)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def tokens(seed: int, shape) -> jax.Array:
    return jax.random.randint(jax.random.key(seed), shape, 0, reduced().vocab_size)


@pytest.fixture(params=["program", "no_conv_bias", "no_attention_multiplier"])
def variant(request, monkeypatch):
    """The program, or the program with one term of the model left out."""
    cfg = reduced()
    if request.param == "no_conv_bias":
        conv = ssd.causal_conv
        monkeypatch.setattr(ssd, "causal_conv",
                            lambda u, w, state=None, bias=None: conv(u, w, state))
    elif request.param == "no_attention_multiplier":
        cfg = dataclasses.replace(cfg, attention_multiplier=0.0)
    return request.param, cfg


@functools.lru_cache(maxsize=None)
def reference_loss_and_grad(seed: int):
    cfg = reduced()
    ids, labels = tokens(seed, (BATCH, SEQ)), tokens(seed + 7, (BATCH, SEQ))
    return jax.jit(jax.value_and_grad(
        lambda p: R.loss(p, ref_config(cfg), ids, labels)))(weights(cfg, seed))


def loss_and_grad_gaps(cfg, seed: int) -> tuple[float, float]:
    """The loss's gap relative to the reference's, and the worst leaf's gap
    between gradient norms over the larger of its and the median leaf's."""
    ids, labels = tokens(seed, (BATCH, SEQ)), tokens(seed + 7, (BATCH, SEQ))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: LM.lm_loss(p, cfg, ids, labels, q_chunk=8, loss_chunk=8)))(
            weights(reduced(), seed))
    ref_loss, ref_grads = reference_loss_and_grad(seed)
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    ref_norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(ref_grads)]
    median = float(np.median(ref_norms))
    grad_gap = max(abs(a - b) / max(b, median) for a, b in zip(norms, ref_norms))
    return abs(float(loss) - float(ref_loss)) / float(ref_loss), grad_gap


def test_loss_and_grads_match_reference(variant):
    name, cfg = variant
    loss_gap, grad_gap = loss_and_grad_gaps(cfg, seed=0)
    within = loss_gap <= LOSS_TOL and grad_gap <= GRAD_TOL
    assert within == (name == "program"), (name, loss_gap, grad_gap)


def served_logit_gap(cfg, seed: int) -> float:
    """Prefill then GEN - 1 decode steps through `ServeEngine`, each step's
    logits against the reference's full forward over the same tokens."""
    w = weights(reduced(), seed)
    prompts = np.asarray(tokens(seed, (BATCH, PROMPT)))
    engine = ServeEngine(make_model(cfg), w, max_batch=BATCH)
    seen = []
    decode = engine._decode

    def recording_decode(*args):
        logits, caches = decode(*args)
        seen.append(np.asarray(logits))
        return logits, caches
    engine._decode = recording_decode
    head = LM.logits_from_hidden

    def recording_head(p, c, h):
        out = head(p, c, h)
        if not isinstance(out, jax.core.Tracer):
            seen.append(np.asarray(out)[:, 0])
        return out
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(LM, "logits_from_hidden", recording_head)
        for i, p in enumerate(prompts):
            engine.submit(Request(rid=i, prompt=p, max_new_tokens=GEN))
        results = sorted(engine.run(), key=lambda r: r.rid)
    assert len(seen) == GEN
    generated = np.stack([r.tokens for r in results])
    assert generated.shape == (BATCH, GEN)
    full = jnp.asarray(np.concatenate([prompts, generated[:, :-1]], axis=1))
    rc = ref_config(cfg)
    ref = np.asarray(jax.jit(lambda w, ids: R.logits(w, rc, R.hidden(w, rc, ids)))(w, full))
    ref = ref[:, PROMPT - 1:]                          # (B, GEN, V)
    got = np.stack(seen, axis=1)[..., : cfg.vocab_size]
    return float(np.max(np.abs(got - ref)) / np.std(ref))


def test_prefill_and_decode_match_reference(variant):
    name, cfg = variant
    gap = served_logit_gap(cfg, seed=3)
    assert (gap <= LOGIT_TOL) == (name == "program"), (name, gap)


def test_published_sizes():
    cfg = get_config(ARCH)
    assert [b.mixer for b in cfg.pattern] == ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert all(b.ffn == "dense" for b in cfg.pattern) and cfg.periods == 4
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_ff) == (
        2048, 32, 8, 64, 8192)
    assert (cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk) == (64, 64, 128, 256)
    assert not cfg.use_rope and cfg.attention_multiplier == 0.015625
    assert make_model(cfg).param_count() == 3_191_396_096
    one_stage = dataclasses.replace(cfg, num_layers=10, vocab_size=12544)
    assert make_model(one_stage).param_count() == 772_160_448
    no_bias = dataclasses.replace(one_stage, ssm_conv_bias=False)
    assert make_model(no_bias).param_count() == 772_121_280


def test_entry_points_take_the_arch(monkeypatch, tmp_path):
    """`launch/train.py` and `launch/serve.py` run the hybrid by name, on
    their normal path, at the reduced size."""
    from repro.io.stores import clear_store_cache
    from repro.launch import serve, train

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax-cache"))
    cache_dir = jax.config.jax_compilation_cache_dir
    clear_store_cache()
    try:
        run = train.main(["--arch", ARCH, "--reduced", "--steps", "2", "--batch", "1",
                          "--seq-len", "48", "--log-every", "1", "--s3-latency", "0",
                          "--ckpt-store", "mem://granite-ckpt"])
        assert sorted(run.losses) == [1, 2]
        assert all(np.isfinite(v) for v in run.losses.values())
        served = serve.main(["--arch", ARCH, "--reduced", "--batch", "1", "--prompt-len",
                             "16", "--gen", "3", "--store", "mem://granite-weights"])
        assert served.ids.shape == (1, 3) and np.all(np.isfinite(served.logits))
    finally:
        clear_store_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
