"""Flash attention, forward and backward, as Pallas TPU kernels.

Blocked online softmax (FlashAttention, arXiv 2205.14135): the scores of
one (block_q x block_k) tile live in VMEM only, never in HBM. The forward
kernel carries each row's running max and normalizer and the output
accumulator in VMEM scratch, and saves each row's log-sum-exp. The
backward pass recomputes the scores from it in two kernels: dq (one q
block against its kv blocks) and dk/dv (one kv block against its q
blocks, summed over the q heads that share the kv head). Under `causal`
the kv blocks above the diagonal are skipped in every pass, and only the
blocks that straddle it are masked.

Precision is that of the XLA attention in `repro.models.layers`: matmul
operands in the inputs' dtype with fp32 accumulation, the softmax scale
applied to the fp32 scores (a power of two is folded into an operand
instead, which gives the same bits), softmax statistics in fp32, the
output in the inputs' dtype.

Layout (B, H, S, D). q head h reads kv head h // (H_q / H_kv) (GQA). A
grid step holds one head's whole K and V (forward, dq) or Q and dO
(dk/dv) in VMEM and walks its blocks in a loop, so consecutive steps on
the same head fetch them once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 512             # q and kv block: the model path's sequences divide it
HEAD_DIMS = (64, 128)   # head sizes the model path sends here
NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fold(x, scale: float):
    """`x` with `scale` folded in where that is exact (a power of two), and
    what the scores still need multiplying by: x @ y.T * scale is then
    _dot(x', y) * rest, bit for bit."""
    if math.frexp(scale)[0] == 0.5:
        return x * jnp.asarray(scale, x.dtype), 1.0
    return x, scale


def _scaled(s, rest: float):
    return s if rest == 1.0 else s * rest


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) statistic widened or cut to n lanes."""
    return jnp.tile(x, (1, pl.cdiv(n, _LANES)))[:, :n]


def _visible(s, row0, col0, rows_are_q: bool):
    """Mask a score tile whose element (r, c) pairs position row0 + r with
    position col0 + c: a query sees keys at or before it."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = cols <= rows if rows_are_q else rows <= cols
    return jnp.where(seen, s, NEG_INF)


def _kv_blocks(i, bq: int, bk: int, nk: int, causal: bool):
    """Kv blocks of q block i: [0, full) unmasked, [full, end) masked."""
    if not causal:
        return nk, nk
    return (i * bq + 1) // bk, ((i + 1) * bq - 1) // bk + 1


def _q_blocks(j, bq: int, bk: int, causal: bool):
    """Q blocks of kv block j: [lo, full) masked, [full, nq) unmasked."""
    if not causal:
        return 0, 0
    return (j * bk) // bq, ((j + 1) * bk + bq - 2) // bq


def _loop(lo, hi, body):
    jax.lax.fori_loop(lo, hi, lambda t, c: (body(t), c)[1], None)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, bq: int, bk: int, nk: int):
    i = pl.program_id(2)
    q, rest = _fold(q_ref[...], scale)                       # (bq, D)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(j, masked: bool):
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(start, bk), :]
        v = v_ref[pl.ds(start, bk), :]
        s = _scaled(_dot(q, k, _NT), rest)                   # (bq, bk) fp32
        if masked:
            s = _visible(s, i * bq, start, rows_are_q=True)
        m_prev = m_ref[...]                                  # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (_lanes(alpha, acc_ref.shape[1]) * acc_ref[...]
                        + _dot(p.astype(v.dtype), v, _NN))

    # Block 0 comes first, and every row sees its column 0: no row's
    # statistics are still at NEG_INF when a masked block reaches it.
    full, end = _kv_blocks(i, bq, bk, nk, causal)
    _loop(0, full, lambda j: block(j, False))
    _loop(full, end, lambda j: block(j, True))
    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])).astype(o_ref.dtype)
    lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]          # (1, bq)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
               scale: float, causal: bool, bq: int, bk: int, nk: int):
    i = pl.program_id(2)
    (q, rest), do = _fold(q_ref[...], scale), do_ref[...]    # (bq, D)
    lse = lse_ref[0][:, None]                                # (bq, 1)
    delta = delta_ref[0][:, None]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(j, masked: bool):
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(start, bk), :]
        v = v_ref[pl.ds(start, bk), :]
        s = _scaled(_dot(q, k, _NT), rest)                   # (bq, bk)
        if masked:
            s = _visible(s, i * bq, start, rows_are_q=True)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, _NT) - delta)
        acc_ref[...] += _dot(ds.astype(k.dtype), k, _NN)

    full, end = _kv_blocks(i, bq, bk, nk, causal)
    _loop(0, full, lambda j: block(j, False))
    _loop(full, end, lambda j: block(j, True))
    dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, causal: bool, bq: int, bk: int,
                nq: int, group: int):
    j, r = pl.program_id(2), pl.program_id(3)   # kv block; q head in its group
    (k, rest), v = _fold(k_ref[...], scale), v_ref[...]      # (bk, D)

    @pl.when(r == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def block(i, masked: bool):
        start = pl.multiple_of(i * bq, bq)
        q = q_ref[pl.ds(start, bq), :]
        do = do_ref[pl.ds(start, bq), :]
        lse = lse_ref[:, pl.ds(start, bq)]                   # (1, bq)
        delta = delta_ref[:, pl.ds(start, bq)]
        s = _scaled(_dot(k, q, _NT), rest)                   # (bk, bq): scores, transposed
        if masked:
            s = _visible(s, j * bk, start, rows_are_q=False)
        p = jnp.exp(s - lse)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta)
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    lo, full = _q_blocks(j, bq, bk, causal)
    _loop(lo, full, lambda i: block(i, True))
    _loop(full, nq, lambda i: block(i, False))

    @pl.when(r == group - 1)
    def _write():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _forward(q, k, v, causal, scale, bq, bk, interpret):
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                               bk=bk, nk=sk // bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, sq // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((None, None, sk, d), lambda b_, h, i: (b_, h // group, 0, 0)),
            pl.BlockSpec((None, None, sk, d), lambda b_, h, i: (b_, h // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((None, None, 1, bq), lambda b_, h, i: (b_, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running normalizer
            pltpu.VMEM((bq, d), jnp.float32),        # output accumulator
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _backward(q, k, v, o, lse, do, causal, scale, bq, bk, interpret):
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    nq, nk = sq // bq, sk // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, :, None]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk),
        grid=(b, hq, nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((None, None, sk, d), lambda b_, h, i: (b_, h // group, 0, 0)),
            pl.BlockSpec((None, None, sk, d), lambda b_, h, i: (b_, h // group, 0, 0)),
            pl.BlockSpec((None, None, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((None, None, 1, bq), lambda b_, h, i: (b_, h, 0, i)),
            pl.BlockSpec((None, None, 1, bq), lambda b_, h, i: (b_, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d), lambda b_, h, i: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def head(b_, g, j, r):
        return (b_, g * group + r, 0, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          nq=nq, group=group),
        grid=(b, hkv, nk, group),
        in_specs=[
            pl.BlockSpec((None, None, sq, d), head),
            pl.BlockSpec((None, None, bk, d), lambda b_, g, j, r: (b_, g, j, 0)),
            pl.BlockSpec((None, None, bk, d), lambda b_, g, j, r: (b_, g, j, 0)),
            pl.BlockSpec((None, None, sq, d), head),
            pl.BlockSpec((None, None, 1, sq), head),
            pl.BlockSpec((None, None, 1, sq), head),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, d), lambda b_, g, j, r: (b_, g, j, 0)),
            pl.BlockSpec((None, None, bk, d), lambda b_, g, j, r: (b_, g, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        name="flash_attention_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bk, interpret):
    return _forward(q, k, v, causal, scale, bq, bk, interpret)[0]


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret):
    o, lse = _forward(q, k, v, causal, scale, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, bq, bk, interpret, res, do):
    return _backward(*res, do, causal, scale, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,      # (B, Hq, Sq, D)
    k: jax.Array,      # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Softmax(q k^T * scale) v, differentiable in q, k and v. Under
    `causal`, Sq == Sk and query i sees keys 0..i."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    assert not causal or sq == sk, (sq, sk)
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = d ** -0.5 if scale is None else scale
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret)
