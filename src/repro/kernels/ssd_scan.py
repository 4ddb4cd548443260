"""Mamba-2 SSD chunked scan, forward and backward, as Pallas TPU kernels.

The chunked SSD algorithm [arXiv:2405.21060] in one launch per pass. The
intra-chunk decay matrix and C B^T live in VMEM only, never in HBM. With
acs the chunk-local cumsum of dt_a (taken outside, so autodiff carries
its cotangent back to dt_a) and h the state entering the chunk, per head:

    L      = exp(acs_i - acs_j) . tril        (1-semiseparable decay)
    y      = ((C B^T) * L) X + exp(acs) * (C h^T)
    h_next = exp(acs_Q) h + X^T (B * exp(acs_Q - acs))

Grid (batch, chunk, head block), chunks sequential: the fp32 state of
every head is carried in VMEM scratch from one chunk to the next. A grid
step holds HEAD_BLOCK heads of one B/C group, eight rows of `acs`: the
fp32 (8, 128) tile's fewest, which keeps blocks small and the DMA
pipeline fine-grained. C B^T is computed once per (chunk, group), in the
group's first head block, and kept in scratch for the group's other
heads; the decay matrix depends on each head's dt and is its own. The
forward also writes the fp32 state entering each chunk, the backward's
only residual besides the inputs. The backward walks the chunks in
reverse, carrying d(state) in VMEM, and sums the group's heads' d(C B^T)
in scratch before its two matmuls into dB and dC, written once per
(chunk, group).

Precision is no lower than the jnp path's on the TPU. x, dy, B and C go
to the MXU in their own dtype (bf16 in the model) with fp32 accumulation.
An fp32 operand (the decay-weighted C B^T, a state or its cotangent, B or
C scaled by a decay) goes as two terms of that dtype, its rounding and
the rounding of what is left, so it keeps ~16 bits of mantissa where
XLA's default precision keeps 8. Cumsums, decays, the state carry and
its update are fp32; outputs take the inputs' dtype, the states fp32.

Layout inside the call is head-major: x (B, H, S, P), acs (B, H, S),
B/C (B, G, S, N), states (B, H, P, N); head h reads group h // (H / G).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 8            # heads a grid step holds (fp32 sublane tile of acs)
CHUNK_MULTIPLE = 128      # chunks the kernel tiles: acs rows are lane-tiled
HEAD_DIMS = (64, 128)     # head sizes the model path sends here
STATE_DIMS = (64, 128)    # state sizes likewise
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _mx(a, b, dims, dt):
    """a . b where one operand is in `dt` and the other may be fp32: that
    one goes to the MXU as its rounding to `dt` plus the rest's."""
    if a.dtype != dt:
        hi = a.astype(dt)
        lo = (a - hi.astype(a.dtype)).astype(dt)
        return _dot(hi, b, dims) + _dot(lo, b, dims)
    if b.dtype != dt:
        hi = b.astype(dt)
        lo = (b - hi.astype(b.dtype)).astype(dt)
        return _dot(a, hi, dims) + _dot(a, lo, dims)
    return _dot(a, b, dims)


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _decay(row, col, causal):
    """The chunk's decay matrix exp(acs_i - acs_j) below the diagonal, 0
    above it; `row` (1, Q) and `col` (Q, 1) are one head's acs."""
    return jnp.exp(jnp.where(causal, col - row, -jnp.inf))


def _heads(a_ref):
    """The block's acs as rows (hb, Q), as columns (Q, hb) and at the
    chunk's last position (hb, 1), and the causal mask of a chunk."""
    rows = a_ref[...]
    q = rows.shape[1]
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    at_last = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1) == q - 1
    lasts = jnp.sum(jnp.where(at_last, rows, 0.0), axis=1, keepdims=True)
    return rows, rows.T, lasts, j <= i


def _fwd_kernel(x_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, final_ref, hin_ref,
                h_ref, cb_ref, *, hb: int, kpg: int, nc: int):
    c, k = pl.program_id(1), pl.program_id(2)
    dt = x_ref.dtype
    bmat, cmat = b_ref[...].astype(dt), c_ref[...].astype(dt)   # (Q, N)

    @pl.when(c == 0)
    def _init():
        h_ref[k] = h0_ref[...]

    @pl.when(k % kpg == 0)
    def _group():
        cb_ref[...] = _dot(cmat, bmat, _NT)

    rows, cols, lasts, causal = _heads(a_ref)
    cb = cb_ref[...]                                            # (Q, Q) fp32
    for j in range(hb):
        row, col = rows[j:j + 1], cols[:, j:j + 1]              # (1, Q), (Q, 1)
        last = lasts[j:j + 1]                                   # (1, 1)
        m = cb * _decay(row, col, causal)
        x, h_prev = x_ref[j], h_ref[k, j]                       # (Q, P), (P, N)
        y = (_mx(m, x, _NN, dt)
             + jnp.exp(col) * _mx(cmat, h_prev, _NT, dt))
        y_ref[j] = y.astype(y_ref.dtype)
        hin_ref[j] = h_prev
        bd = bmat * jnp.exp(last - col)
        h_ref[k, j] = jnp.exp(last) * h_prev + _mx(x, bd, _TN, dt)

    @pl.when(c == nc - 1)
    def _finish():
        final_ref[...] = h_ref[k]


def _bwd_kernel(x_ref, a_ref, b_ref, c_ref, hin_ref, dy_ref, dfinal_ref,
                dx_ref, da_ref, db_ref, dc_ref, dh0_ref,
                dh_ref, cb_ref, dcb_ref, db_acc, dc_acc, *, hb: int, kpg: int,
                nc: int):
    r, k = pl.program_id(1), pl.program_id(2)   # r-th chunk from the end
    dt = x_ref.dtype
    bmat, cmat = b_ref[...].astype(dt), c_ref[...].astype(dt)

    @pl.when(r == 0)
    def _init():
        dh_ref[k] = dfinal_ref[...]

    @pl.when(k % kpg == 0)
    def _group():
        cb_ref[...] = _dot(cmat, bmat, _NT)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    rows, cols, lasts, causal = _heads(a_ref)
    q = rows.shape[1]
    cb = cb_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, hb), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (hb, q), 0)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    da_cols = jnp.zeros((q, hb), jnp.float32)
    da_rows = jnp.zeros((hb, q), jnp.float32)
    for j in range(hb):
        row, col, last = rows[j:j + 1], cols[:, j:j + 1], lasts[j:j + 1]
        l_mat = _decay(row, col, causal)
        m = cb * l_mat
        x, dy = x_ref[j], dy_ref[j]                             # (Q, P)
        h_prev, dh = hin_ref[j], dh_ref[k, j]                   # (P, N) fp32
        eo, ds = jnp.exp(col), jnp.exp(last - col)              # (Q, 1)

        # y = (CB * L) X: through M to X, and through L and CB.
        dm = _dot(dy, x, _NT)                                   # (Q, Q)
        dcb_ref[...] += dm * l_mat
        s = dm * m                                              # d(segment) in acs_i - acs_j
        # y += exp(acs) * (C h^T): to C, h and acs.
        z = _mx(cmat, h_prev, _NT, dt)                          # (Q, P)
        dc_acc[...] += eo * _mx(dy, h_prev, _NN, dt)
        # h_next = exp(acs_Q) h + X^T (B * exp(acs_Q - acs)): to X, B, h and acs.
        dbd = _mx(x, dh, _NN, dt)                               # (Q, N)
        db_acc[...] += dbd * ds
        dx = _mx(m, dy, _TN, dt) + ds * _mx(bmat, dh, _NT, dt)
        dx_ref[j] = dx.astype(dx_ref.dtype)
        dh_ref[k, j] = jnp.exp(last) * dh + _mx(dy, cmat * eo, _TN, dt)

        dds = jnp.sum(dbd * bmat, axis=1, keepdims=True)        # (Q, 1)
        dlast = (jnp.sum(dh * h_prev, keepdims=True) * jnp.exp(last)
                 + jnp.sum(dds * ds, keepdims=True))            # (1, 1)
        col_part = (jnp.sum(s, axis=1, keepdims=True)
                    + jnp.sum(dy * z, axis=1, keepdims=True) * eo - dds * ds)
        row_part = jnp.where(at_last, dlast, 0.0) - jnp.sum(s, axis=0, keepdims=True)
        da_cols = jnp.where(lane == j, col_part, da_cols)
        da_rows = jnp.where(sub == j, row_part, da_rows)
    da_ref[...] = da_cols.T + da_rows

    @pl.when(k % kpg == kpg - 1)
    def _group_done():
        dcb = dcb_ref[...]
        dc_ref[...] = (dc_acc[...] + _mx(dcb, bmat, _NN, dt)).astype(dc_ref.dtype)
        db_ref[...] = (db_acc[...] + _mx(dcb, cmat, _TN, dt)).astype(db_ref.dtype)

    @pl.when(r == nc - 1)
    def _finish():
        dh0_ref[...] = dh_ref[k]


def _shapes(x, b_proj, chunk):
    bsz, h, s, p = x.shape
    g, n = b_proj.shape[1], b_proj.shape[3]
    hb = math.gcd(h // g, HEAD_BLOCK)
    return bsz, h, s, p, g, n, hb, (h // g) // hb, s // chunk


def _forward(x, acs, b_proj, c_proj, h0, chunk, interpret):
    bsz, h, s, p, g, n, hb, kpg, nc = _shapes(x, b_proj, chunk)
    kernel = functools.partial(_fwd_kernel, hb=hb, kpg=kpg, nc=nc)
    heads = pl.BlockSpec((None, hb, chunk, p), lambda b, c, k: (b, k, c, 0))
    state_in = pl.BlockSpec((None, hb, p, n),
                            lambda b, c, k: (b, jnp.where(c == 0, k, 0), 0, 0))
    state_out = pl.BlockSpec((None, hb, p, n),
                             lambda b, c, k: (b, jnp.where(c == nc - 1, k, 0), 0, 0))
    group = pl.BlockSpec((None, None, chunk, n), lambda b, c, k: (b, k // kpg, c, 0))
    return pl.pallas_call(
        kernel,
        grid=(bsz, nc, h // hb),
        in_specs=[heads, pl.BlockSpec((None, hb, chunk), lambda b, c, k: (b, k, c)),
                  group, group, state_in],
        out_specs=[heads, state_out,
                   pl.BlockSpec((None, hb, None, p, n), lambda b, c, k: (b, k, c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, nc, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hb, hb, p, n), jnp.float32),   # state
                        pltpu.VMEM((chunk, chunk), jnp.float32)],        # C B^T
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="ssd_scan_fwd",
        interpret=interpret,
    )(x, acs, b_proj, c_proj, h0)


def _backward(x, acs, b_proj, c_proj, h_in, dy, dfinal, chunk, interpret):
    bsz, h, s, p, g, n, hb, kpg, nc = _shapes(x, b_proj, chunk)
    kernel = functools.partial(_bwd_kernel, hb=hb, kpg=kpg, nc=nc)
    heads = pl.BlockSpec((None, hb, chunk, p), lambda b, r, k: (b, k, nc - 1 - r, 0))
    group = pl.BlockSpec((None, None, chunk, n),
                         lambda b, r, k: (b, k // kpg, nc - 1 - r, 0))
    dfinal_in = pl.BlockSpec((None, hb, p, n),
                             lambda b, r, k: (b, jnp.where(r == 0, k, 0), 0, 0))
    dh0_out = pl.BlockSpec((None, hb, p, n),
                           lambda b, r, k: (b, jnp.where(r == nc - 1, k, 0), 0, 0))
    rows = pl.BlockSpec((None, hb, chunk), lambda b, r, k: (b, k, nc - 1 - r))
    return pl.pallas_call(
        kernel,
        grid=(bsz, nc, h // hb),
        in_specs=[heads, rows, group, group,
                  pl.BlockSpec((None, hb, None, p, n),
                               lambda b, r, k: (b, k, nc - 1 - r, 0, 0)),
                  heads, dfinal_in],
        out_specs=[heads, rows, group, group, dh0_out],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(acs.shape, jnp.float32),
                   jax.ShapeDtypeStruct(b_proj.shape, b_proj.dtype),
                   jax.ShapeDtypeStruct(c_proj.shape, c_proj.dtype),
                   jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hb, hb, p, n), jnp.float32),   # d(state)
                        pltpu.VMEM((chunk, chunk), jnp.float32),        # C B^T
                        pltpu.VMEM((chunk, chunk), jnp.float32),        # d(C B^T)
                        pltpu.VMEM((chunk, n), jnp.float32),            # dB
                        pltpu.VMEM((chunk, n), jnp.float32)],           # dC
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="ssd_scan_bwd",
        interpret=interpret,
    )(x, acs, b_proj, c_proj, h_in, dy, dfinal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, acs, b_proj, c_proj, h0, chunk, interpret):
    return tuple(_forward(x, acs, b_proj, c_proj, h0, chunk, interpret)[:2])


def _scan_fwd(x, acs, b_proj, c_proj, h0, chunk, interpret):
    y, final, h_in = _forward(x, acs, b_proj, c_proj, h0, chunk, interpret)
    return (y, final), (x, acs, b_proj, c_proj, h_in)


def _scan_bwd(chunk, interpret, res, cts):
    dy, dfinal = cts
    return tuple(_backward(*res, dy.astype(res[0].dtype), dfinal, chunk, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(
    x: jax.Array,        # (B, S, H, P) — dt-scaled inputs
    dt_a: jax.Array,     # (B, S, H)
    b_proj: jax.Array,   # (B, S, G, N)
    c_proj: jax.Array,   # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: jax.Array | None = None,   # (B, H, P, N)
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(y (B, S, H, P) in x's dtype, final state (B, H, P, N) fp32) of the
    recurrence h_t = exp(dt_a_t) h_{t-1} + B_t x_t, y_t = C_t . h_t;
    differentiable in x, dt_a, b_proj, c_proj and initial_state."""
    bsz, s, h, p = x.shape
    g = b_proj.shape[2]
    assert h % g == 0, (h, g)
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, p, b_proj.shape[3]), jnp.float32)
    acs = jnp.cumsum(dt_a.astype(jnp.float32).reshape(bsz, s // chunk, chunk, h), axis=2)
    y, final = _scan(
        x.transpose(0, 2, 1, 3),
        acs.reshape(bsz, s, h).transpose(0, 2, 1),
        b_proj.transpose(0, 2, 1, 3),
        c_proj.transpose(0, 2, 1, 3),
        initial_state.astype(jnp.float32),
        chunk, interpret)
    return y.transpose(0, 2, 1, 3), final
