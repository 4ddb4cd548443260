"""Compile the main path's programs for one TPU v5e chip, with no chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described `v5e:2x2` topology and refuses what the chip would refuse
(tiling, VMEM, device memory). Shapes come from `jax.eval_shape`, so
nothing is allocated. This is the only test file that describes the chip;
the description happens in a fixture, never at import.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import layers as L
from repro.models import lm as LM
from repro.models import make_model
from repro.sharding.rules import TRAIN_RULES, ShardingRules, use_rules
from repro.train import AdamWConfig, StepConfig, build_train_step, init_train_state
from repro.utils import trace

# One v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB).
HBM_BYTES = 16 * 10**9

# The sizes chip_smoke.py runs: train batch 8 x 2048, serve batch 8 with a
# 512-token prompt and 32 generated tokens.
TRAIN_BATCH, TRAIN_SEQ = 8, 2048
SERVE_BATCH, PROMPT, GEN = 8, 512, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # repro: allow[RP005] — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _assert_fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB of arguments + temporaries"


def _compile_train_step(one_chip, model, batch: int, seq: int):
    """The cell's donating train step compiled for one chip, and what
    `repro.utils.trace` counted while it was lowered."""
    state = _on(one_chip, jax.eval_shape(
        lambda: init_train_state(model, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    step = jax.jit(
        build_train_step(model, AdamWConfig(total_steps=20, warmup_steps=1),
                         StepConfig(q_chunk=512, loss_chunk=512)),
        donate_argnums=(0,))
    with trace.recording() as rec:
        compiled = step.lower(state, {"inputs": tokens, "labels": tokens}).compile()
    return compiled, rec.counters


def _assert_attention_fused(compiled, counters, seq: int) -> None:
    """Every attention call took the fused kernel: its custom calls are in
    the program, and no fp32 score tile of a 512-row query chunk against
    all `seq` keys is left under the `attn` scope."""
    assert counters.get("attn.kernel", 0) > 0, counters
    assert counters.get("attn.xla", 0) == 0, counters
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    tile = re.compile(rf"f32\[(\d+,)*512,{seq}\]")
    tiles = [line for line in text.splitlines() if "/attn/" in line and tile.search(line)]
    assert not tiles, tiles[:2]


@pytest.fixture(scope="module")
def smollm():
    return make_model(get_config("smollm-135m"))


def test_train_step_compiles_for_one_chip(one_chip, smollm):
    compiled, counters = _compile_train_step(one_chip, smollm, TRAIN_BATCH, TRAIN_SEQ)
    _assert_fits(compiled)
    _assert_attention_fused(compiled, counters, TRAIN_SEQ)


def test_olmo_train_step_compiles_for_one_chip(one_chip):
    """The OLMo cell's step: 8 of 16 layers at every published width, 4 x
    2048, 16 MHA heads of 128 under a softmax scale that is no power of two."""
    cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=8)
    compiled, counters = _compile_train_step(one_chip, make_model(cfg), 4, 2048)
    _assert_fits(compiled)
    _assert_attention_fused(compiled, counters, 2048)


def test_prefill_and_decode_compile_for_one_chip(one_chip, smollm):
    """Serving keeps the XLA attention: both programs hold a cache."""
    cfg = smollm.cfg
    params = _on(one_chip, jax.eval_shape(smollm.init, jax.random.key(0)))
    prompt = jax.ShapeDtypeStruct((SERVE_BATCH, PROMPT), jnp.int32, sharding=one_chip)
    prefill = jax.jit(lambda p, x: LM.lm_prefill(
        p, cfg, x, max_len=PROMPT + GEN, q_chunk=512))
    with trace.recording() as rec:
        _assert_fits(prefill.lower(params, prompt).compile())
    assert rec.counters.get("attn.kernel", 0) == 0 < rec.counters.get("attn.xla", 0)

    caches = _on(one_chip, jax.eval_shape(
        lambda: LM.make_stack_cache(cfg, SERVE_BATCH, PROMPT + GEN)))
    ids = jax.ShapeDtypeStruct((SERVE_BATCH, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with trace.recording() as rec:
        _assert_fits(jax.jit(smollm.decode_step).lower(params, ids, caches, pos).compile())
    assert rec.counters.get("attn.kernel", 0) == 0 < rec.counters.get("attn.xla", 0)


def test_granite_train_step_fills_one_chip(one_chip):
    """The granite cell's step: one period (10 of 40 layers) and an eighth
    of the vocabulary at 1 x 8192. Its fp32 state and AdamW moments alone
    are 9.27 GB; per-block remat must keep the rest under 16 GB, and the
    cell must use at least three quarters of the chip."""
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), num_layers=10,
                              vocab_size=12544)
    compiled, counters = _compile_train_step(one_chip, make_model(cfg), 1, 8192)
    _assert_fits(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes >= 0.75 * HBM_BYTES
    _assert_attention_fused(compiled, counters, 8192)


@pytest.mark.parametrize("case", ["train", "cross", "seq_not_whole_blocks", "mesh"])
def test_attention_path_lowered_for_the_chip(topo, one_chip, case):
    """SmolLM's attention lowered for the chip: the cache-less causal call
    takes the kernel; cross-attention, a sequence that is no whole number
    of kernel blocks, and a mesh of four chips keep the XLA path."""
    cfg = get_config("smollm-135m")
    s = 384 if case == "seq_not_whole_blocks" else 512
    sharding, rules = one_chip, None
    if case == "mesh":
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
        sharding, rules = NamedSharding(mesh, PartitionSpec()), ShardingRules(mesh, TRAIN_RULES)
    p = {name: jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=sharding)
         for name, spec in L.attention_spec(cfg).items()}
    x = jax.ShapeDtypeStruct((4, s, cfg.d_model), jnp.bfloat16, sharding=sharding)
    src = x if case == "cross" else None

    def loss(p, x, src):
        y, _ = L.attention(p, cfg, x, positions=None, kv_source=src)
        return y.astype(jnp.float32).sum()

    with use_rules(rules), trace.recording() as rec:
        text = jax.jit(jax.grad(loss)).lower(p, x, src).as_text()
    took_kernel = case == "train"
    assert rec.counters.get("attn.kernel", 0) == (1 if took_kernel else 0), rec.counters
    assert rec.counters.get("attn.xla", 0) == (0 if took_kernel else 1), rec.counters
    assert ("tpu_custom_call" in text) == took_kernel


def test_flash_attention_lowers_to_a_kernel(one_chip):
    cfg = get_config("smollm-135m")
    q = jax.ShapeDtypeStruct((TRAIN_BATCH, cfg.num_heads, TRAIN_SEQ, cfg.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((TRAIN_BATCH, cfg.num_kv_heads, TRAIN_SEQ, cfg.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    compiled = ops.flash_attention.lower(q, kv, kv, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_ssd_scan_lowers_to_a_kernel(one_chip):
    cfg = get_config("mamba2-1.3b")
    b, s, h, p = 1, 2048, cfg.ssm_nheads, cfg.ssm_head_dim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    assert (h, p, n, cfg.ssm_chunk) == (64, 64, 128, 256)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ops.ssd_scan.lower(
        arg((b, s, h, p), jnp.bfloat16), arg((b, s, h), jnp.float32),
        arg((b, s, g, n), jnp.bfloat16), arg((b, s, g, n), jnp.bfloat16),
        chunk=cfg.ssm_chunk,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)
