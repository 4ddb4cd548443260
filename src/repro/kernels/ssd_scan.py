"""Mamba-2 SSD chunked scan as a fused Pallas TPU kernel.

Grid (batch, heads, chunks) over head-major operands, with the chunk
dimension sequential ("arbitrary"): the inter-chunk SSM state
(head_dim x state) is carried in fp32 VMEM scratch across grid steps —
the whole recurrence runs in one kernel launch. Intra-chunk work is
dense MXU matmuls:

    acs    = cumsum(dt_a)                     (via lower-tri ones matmul)
    L      = exp(acs_i - acs_j) . tril        (1-semiseparable decay)
    y_diag = ((C B^T) * L) X                  (Q,Q)@(Q,P)
    y_off  = (C h_prev^T) * exp(acs)          (Q,N)@(N,P)
    h_new  = exp(acs_Q) h_prev + X^T (B * exp(acs_Q - acs))

Block working set at (Q=256, P=64, N=128): x 64KB, B/C 128KB each, L 256KB
fp32, state 32KB — comfortably inside VMEM, MXU dims all multiples of 64.
Validated in interpret mode against both the chunked jnp path
(repro.models.ssd) and the sequential-recurrence oracle (ref.ssd_ref).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,        # (1, 1, Q, P)
    a_ref,        # (1, 1, Q, 1)
    b_ref,        # (1, 1, Q, N)
    c_ref,        # (1, 1, Q, N)
    init_ref,     # (1, 1, P, N)
    y_ref,        # (1, 1, Q, P) out
    final_ref,    # (1, 1, P, N) out
    h_ref,        # VMEM scratch (P, N) fp32
    *,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)              # (Q, 1)
    bmat = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    q = x.shape[0]

    # Inclusive cumsum via lower-triangular ones matmul (MXU-friendly),
    # as a column and as a row so no in-kernel transpose is needed.
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril_inc = (cols <= rows).astype(jnp.float32)    # includes diagonal
    acs = jax.lax.dot(tril_inc, a, preferred_element_type=jnp.float32)  # (Q, 1)
    acs_row = jax.lax.dot_general(
        a, tril_inc, (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                              # (1, Q)

    # Intra-chunk decay matrix.
    seg = acs - acs_row
    l_mat = jnp.where(cols <= rows, jnp.exp(seg), 0.0)            # (Q, Q)

    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                              # (Q, Q)
    y_diag = jax.lax.dot(cb * l_mat, x, preferred_element_type=jnp.float32)

    h_prev = h_ref[...]                                            # (P, N)
    y_off = jax.lax.dot_general(
        cmat, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jnp.exp(acs)                                               # (Q, P)

    acs_last = jnp.sum(a, axis=0, keepdims=True)                   # (1, 1)
    decay_states = jnp.exp(acs_last - acs)                         # (Q, 1)
    state_update = jax.lax.dot_general(
        x, bmat * decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                              # (P, N)
    h_ref[...] = h_prev * jnp.exp(acs_last) + state_update

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _finish():
        final_ref[0, 0] = h_ref[...].astype(final_ref.dtype)


def ssd_scan(
    x: jax.Array,        # (B, S, H, P) — dt-scaled inputs
    dt_a: jax.Array,     # (B, S, H)
    b_proj: jax.Array,   # (B, S, G, N)
    c_proj: jax.Array,   # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bsz, s, h, p = x.shape
    g, n = b_proj.shape[2], b_proj.shape[3]
    assert h % g == 0, (h, g)
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if initial_state is None:
        initial_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    # Head-major inside the call: every block's last two dims are then
    # (chunk, P), (chunk, 1) or (chunk, N), which Mosaic tiles; a
    # (1, chunk, 1, P) block over (B, S, H, P) puts a 1 against H.
    x_hm = x.transpose(0, 2, 1, 3)                   # (B, H, S, P)
    a_hm = dt_a.transpose(0, 2, 1)[..., None]        # (B, H, S, 1)
    b_hm = b_proj.transpose(0, 2, 1, 3)              # (B, G, S, N)
    c_hm = c_proj.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    grid = (bsz, h, nc)
    y, final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda b, hh, c, rep=rep: (b, hh // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda b, hh, c, rep=rep: (b, hh // rep, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_hm, a_hm, b_hm, c_hm, initial_state)
    return y.transpose(0, 2, 1, 3), final
