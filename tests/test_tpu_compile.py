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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm as LM
from repro.models import make_model
from repro.train import AdamWConfig, StepConfig, build_train_step, init_train_state

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


@pytest.fixture(scope="module")
def smollm():
    return make_model(get_config("smollm-135m"))


def test_train_step_compiles_for_one_chip(one_chip, smollm):
    state = _on(one_chip, jax.eval_shape(
        lambda: init_train_state(smollm, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                                  sharding=one_chip)
    step = jax.jit(
        build_train_step(smollm, AdamWConfig(total_steps=20, warmup_steps=1),
                         StepConfig(q_chunk=512, loss_chunk=512)),
        donate_argnums=(0,))
    _assert_fits(step.lower(state, {"inputs": tokens, "labels": tokens}).compile())


def test_prefill_and_decode_compile_for_one_chip(one_chip, smollm):
    cfg = smollm.cfg
    params = _on(one_chip, jax.eval_shape(smollm.init, jax.random.key(0)))
    prompt = jax.ShapeDtypeStruct((SERVE_BATCH, PROMPT), jnp.int32, sharding=one_chip)
    prefill = jax.jit(lambda p, x: LM.lm_prefill(
        p, cfg, x, max_len=PROMPT + GEN, q_chunk=512))
    _assert_fits(prefill.lower(params, prompt).compile())

    caches = _on(one_chip, jax.eval_shape(
        lambda: LM.make_stack_cache(cfg, SERVE_BATCH, PROMPT + GEN)))
    ids = jax.ShapeDtypeStruct((SERVE_BATCH, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _assert_fits(jax.jit(smollm.decode_step).lower(params, ids, caches, pos).compile())


def test_granite_train_step_fills_one_chip(one_chip):
    """The granite cell's step: one period (10 of 40 layers) and an eighth
    of the vocabulary at 1 x 8192. Its fp32 state and AdamW moments alone
    are 9.27 GB; per-block remat must keep the rest under 16 GB, and the
    cell must use at least three quarters of the chip."""
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), num_layers=10,
                              vocab_size=12544)
    model = make_model(cfg)
    state = _on(one_chip, jax.eval_shape(
        lambda: init_train_state(model, jax.random.key(0))))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    step = jax.jit(
        build_train_step(model, AdamWConfig(total_steps=20, warmup_steps=1),
                         StepConfig(q_chunk=512, loss_chunk=512)),
        donate_argnums=(0,))
    compiled = step.lower(state, {"inputs": tokens, "labels": tokens}).compile()
    _assert_fits(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes >= 0.75 * HBM_BYTES


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
