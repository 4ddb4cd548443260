"""Flash attention (forward) as a Pallas TPU kernel.

Blocked online-softmax attention: grid (batch, q_heads, q_blocks,
kv_blocks) with the kv dimension sequential ("arbitrary"), carrying the
running max / normalizer / output accumulator in VMEM scratch. Block
shapes are MXU-aligned (multiples of 128 on the contraction dims) and
sized so the working set — q block (Bq x D), kv blocks (Bk x D), the
(Bq x Bk) score tile, and the fp32 accumulator — fits VMEM.

GQA folds into the k/v BlockSpec index maps (head h reads kv head
h // group). Causal masking prunes fully-masked kv blocks with pl.when.

The training/dry-run path uses the XLA chunked implementation in
repro.models.layers (Pallas cannot lower on the CPU placeholder backend);
this kernel is the TPU serving/prefill hot path, validated in
interpret mode against ref.py on every shape/dtype in the test sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,            # VMEM blocks
    o_ref,                           # output block
    m_ref, l_ref, acc_ref,           # VMEM scratch (fp32)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Skip kv blocks entirely above the causal diagonal.
    run = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (Bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (Bq, Bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_ref[...]                           # (Bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (Bq, Bk)
        alpha = jnp.exp(m_prev - m_new)               # (Bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,      # (B, Hq, Sq, D)
    k: jax.Array,      # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0, (sq, block_q)
    assert sk % block_k == 0, (sk, block_k)
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )

    grid = (b, hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, group=group: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, group=group: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
