"""Jit'd public wrappers for the Pallas kernels.

Kernels lower natively for the TPU. `interpret=True` runs them in the
Pallas interpreter instead, which is how the CPU tests check them; the
caller says so explicitly, so a run never drops off the device unseen.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = _fa.BLOCK, block_k: int = _fa.BLOCK,
                    interpret: bool = False):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D); has a VJP."""
    return _fa.flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt_a, b_proj, c_proj, *, chunk: int = 256,
             initial_state=None, interpret: bool = False):
    """Fused Mamba-2 SSD scan. x: (B,S,H,P) dt-scaled; returns (y, state)."""
    return _ssd.ssd_scan(
        x, dt_a, b_proj, c_proj, chunk=chunk,
        initial_state=initial_state, interpret=interpret,
    )
