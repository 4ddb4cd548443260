"""Mamba-2 block via SSD (state-space duality), TPU-adapted.

The chunked SSD algorithm decomposes the selective-state-space recurrence
into dense per-chunk matmuls (MXU-friendly) plus a short `lax.scan` over
chunk states — this is the published TPU/accelerator-native formulation of
the Mamba recurrence [arXiv:2405.21060]. The intra-chunk computation is
also implemented as a Pallas kernel (repro.kernels.ssd_scan); this module
is the pure-jnp path used by smoke tests and the dry-run, and doubles as
the kernel's oracle.

Sharding: d_inner (and therefore SSM heads, which tile d_inner in
head_dim-sized groups) shards over the tensor axis; B/C group projections
are small and replicate; sequence stays unsharded inside a block (chunk
scan is sequential anyway).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.spec import ParamSpec
from repro.sharding.rules import constrain


# --------------------------------------------------------------------------- #
# Chunked SSD scan (pure jnp; fp32 state math)
# --------------------------------------------------------------------------- #
def segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k] for
    j < i, -inf above the diagonal. Produces the 1-semiseparable log-decay
    matrix."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(
    x: jax.Array,        # (B, S, H, P) — inputs, already dt-scaled
    dt_a: jax.Array,     # (B, S, H)   — dt * A (negative)
    b_proj: jax.Array,   # (B, S, G, N)
    c_proj: jax.Array,   # (B, S, G, N)
    chunk: int,
    initial_state: jax.Array | None = None,  # (B, H, P, N) fp32
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b_proj.shape[2], b_proj.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g  # heads per B/C group

    xc = x.reshape(bsz, nc, chunk, h, p).astype(jnp.float32)
    ac = dt_a.reshape(bsz, nc, chunk, h).astype(jnp.float32)
    bc = b_proj.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    cc = c_proj.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    # Broadcast groups to heads: head i belongs to group i // rep.
    bh = jnp.repeat(bc, rep, axis=3)  # (B, NC, L, H, N)
    ch = jnp.repeat(cc, rep, axis=3)

    a_perm = ac.transpose(0, 3, 1, 2)             # (B, H, NC, L)
    a_cumsum = jnp.cumsum(a_perm, axis=-1)        # (B, H, NC, L)

    # 1) Intra-chunk (diagonal blocks).
    l_mat = jnp.exp(segsum(a_perm))               # (B, H, NC, L, L)
    y_diag = jnp.einsum(
        "bclhn,bcshn,bhcls,bcshp->bclhp", ch, bh, l_mat, xc
    )

    # 2) Per-chunk end states.
    decay_states = jnp.exp(a_cumsum[..., -1:] - a_cumsum)  # (B, H, NC, L)
    states = jnp.einsum(
        "bclhn,bhcl,bclhp->bchpn", bh, decay_states, xc
    )                                              # (B, NC, H, P, N)

    # 3) Inter-chunk recurrence over chunk states (lax.scan).
    chunk_decay = jnp.exp(a_cumsum[..., -1])       # (B, H, NC)
    init = (
        jnp.zeros((bsz, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(carry, inp):
        state_c, decay_c = inp                    # (B,H,P,N), (B,H)
        new = carry * decay_c[..., None, None] + state_c
        return new, carry                          # emit the *entering* state

    final_state, entering = jax.lax.scan(
        step,
        init,
        (states.swapaxes(0, 1), chunk_decay.transpose(2, 0, 1)),
    )
    entering = entering.swapaxes(0, 1)             # (B, NC, H, P, N)

    # 4) Inter-chunk output contribution.
    state_decay_out = jnp.exp(a_cumsum)            # (B, H, NC, L)
    y_off = jnp.einsum(
        "bclhn,bchpn,bhcl->bclhp", ch, entering, state_decay_out
    )

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_decode_step(
    state: jax.Array,    # (B, H, P, N) fp32
    x_t: jax.Array,      # (B, H, P) — dt-scaled input
    dt_a_t: jax.Array,   # (B, H)
    b_t: jax.Array,      # (B, G, N)
    c_t: jax.Array,      # (B, G, N)
) -> tuple[jax.Array, jax.Array]:
    """One recurrent step: h' = exp(dt·A) h + B x ; y = C h'."""
    bsz, h, p = x_t.shape
    g = b_t.shape[1]
    rep = h // g
    bh = jnp.repeat(b_t, rep, axis=1).astype(jnp.float32)   # (B, H, N)
    ch = jnp.repeat(c_t, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(dt_a_t.astype(jnp.float32))             # (B, H)
    new_state = (
        state * decay[..., None, None]
        + jnp.einsum("bhp,bhn->bhpn", x_t.astype(jnp.float32), bh)
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch)
    return y.astype(x_t.dtype), new_state


# --------------------------------------------------------------------------- #
# Causal depthwise conv (shift-and-add; K is tiny)
# --------------------------------------------------------------------------- #
def causal_conv(x: jax.Array, w: jax.Array, state: jax.Array | None = None,
                bias: jax.Array | None = None):
    """x: (B, S, C); w: (K, C); bias: (C,) or None. Returns (y (B,S,C),
    new_state (B,K-1,C)). `state` carries the last K-1 inputs for decode
    continuity, so prefill and each decode step add the bias alike."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)     # (B, S+K-1, C)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k)
    )
    if bias is not None:
        y = y + bias[None, None, :]
    new_state = xp[:, -(k - 1):, :] if k > 1 else state
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------- #
# Mamba-2 block
# --------------------------------------------------------------------------- #
class MambaCache(NamedTuple):
    conv_x: jax.Array    # (B, K-1, d_inner)
    conv_b: jax.Array    # (B, K-1, G*N)
    conv_c: jax.Array    # (B, K-1, G*N)
    ssm: jax.Array       # (B, H, P, N) fp32


def mamba_cache_logical_axes() -> MambaCache:
    from repro.models.spec import Ax

    return MambaCache(
        conv_x=Ax(("batch", None, "tp")),
        conv_b=Ax(("batch", None, None)),
        conv_c=Ax(("batch", None, None)),
        ssm=Ax(("batch", "tp", None, None)),
    )


def mamba_spec(cfg: ModelConfig) -> dict:
    d, din = cfg.d_model, cfg.d_inner
    g, n, h, k = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_conv_kernel
    spec = {
        "w_z": ParamSpec((d, din), ("fsdp", "tp"), ("fan_in", d)),
        "w_x": ParamSpec((d, din), ("fsdp", "tp"), ("fan_in", d)),
        "w_b": ParamSpec((d, g * n), ("fsdp", None), ("fan_in", d)),
        "w_c": ParamSpec((d, g * n), ("fsdp", None), ("fan_in", d)),
        "w_dt": ParamSpec((d, h), ("fsdp", "tp"), ("fan_in", d)),
        "conv_x": ParamSpec((k, din), (None, "tp"), ("fan_in", k)),
        "conv_b": ParamSpec((k, g * n), (None, None), ("fan_in", k)),
        "conv_c": ParamSpec((k, g * n), (None, None), ("fan_in", k)),
        "dt_bias": ParamSpec((h,), ("tp",), "dt_bias"),
        "a_log": ParamSpec((h,), ("tp",), "a_log"),
        "d_skip": ParamSpec((h,), ("tp",), "ones"),
        "norm_scale": ParamSpec((din,), ("tp",), "ones"),
        "w_out": ParamSpec((din, d), ("tp", "fsdp"), ("fan_in", din)),
    }
    if cfg.ssm_conv_bias:
        spec["conv_x_bias"] = ParamSpec((din,), ("tp",), "zeros")
        spec["conv_b_bias"] = ParamSpec((g * n,), (None,), "zeros")
        spec["conv_c_bias"] = ParamSpec((g * n,), (None,), "zeros")
    return spec


def make_mamba_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> MambaCache:
    g, n, h, k = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_conv_kernel
    p = cfg.ssm_head_dim
    return MambaCache(
        conv_x=jnp.zeros((batch, k - 1, cfg.d_inner), dtype),
        conv_b=jnp.zeros((batch, k - 1, g * n), dtype),
        conv_c=jnp.zeros((batch, k - 1, g * n), dtype),
        ssm=jnp.zeros((batch, h, p, n), jnp.float32),
    )


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float) -> jax.Array:
    yf = (y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)).astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def mamba_block(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, S, D)
    *,
    cache: MambaCache | None = None,
    update_cache: bool = False,
) -> tuple[jax.Array, MambaCache | None]:
    bsz, s, _ = x.shape
    h, pdim, g, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_ngroups, cfg.ssm_state
    dt = x.dtype

    z = jnp.einsum("bsd,de->bse", x, p["w_z"].astype(dt))
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"].astype(dt))
    bp = jnp.einsum("bsd,de->bse", x, p["w_b"].astype(dt))
    cp = jnp.einsum("bsd,de->bse", x, p["w_c"].astype(dt))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["w_dt"].astype(dt))
    xs = constrain(xs, "batch", None, "tp")
    z = constrain(z, "batch", None, "tp")

    conv_state = (cache.conv_x, cache.conv_b, cache.conv_c) if cache else (None,) * 3

    def conv(u, name, state):
        bias = p.get(f"{name}_bias")
        return causal_conv(u, p[name].astype(dt), state,
                           None if bias is None else bias.astype(dt))

    xs, st_x = conv(xs, "conv_x", conv_state[0])
    bp, st_b = conv(bp, "conv_b", conv_state[1])
    cp, st_c = conv(cp, "conv_c", conv_state[2])
    xs, bp, cp = jax.nn.silu(xs), jax.nn.silu(bp), jax.nn.silu(cp)

    dt_val = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )                                                      # (B, S, H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))           # (H,)
    dt_a = dt_val * a                                      # (B, S, H)

    xh = xs.reshape(bsz, s, h, pdim)
    x_scaled = xh.astype(jnp.float32) * dt_val[..., None]  # dt-discretized input
    bg = bp.reshape(bsz, s, g, n)
    cg = cp.reshape(bsz, s, g, n)

    if s == 1 and cache is not None:
        y_t, new_ssm = ssd_decode_step(
            cache.ssm,
            x_scaled[:, 0].astype(dt),
            dt_a[:, 0],
            bg[:, 0],
            cg[:, 0],
        )
        y = y_t[:, None]
    else:
        init = cache.ssm if cache is not None else None
        pad = (-s) % cfg.ssm_chunk
        if pad:
            x_scaled = jnp.pad(x_scaled, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_a = jnp.pad(dt_a, ((0, 0), (0, pad), (0, 0)))
            bg = jnp.pad(bg, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cg = jnp.pad(cg, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y_full, new_ssm = ssd_chunked(
            x_scaled.astype(dt), dt_a, bg, cg, cfg.ssm_chunk, initial_state=init
        )
        y = y_full[:, :s]

    y = y + xh * p["d_skip"].astype(dt)[None, None, :, None]
    y = y.reshape(bsz, s, cfg.d_inner)
    y = _gated_rmsnorm(y, z, p["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(dt))
    out = constrain(out, "batch", None, "residual")

    new_cache = None
    if update_cache or cache is not None:
        new_cache = MambaCache(conv_x=st_x, conv_b=st_b, conv_c=st_c, ssm=new_ssm)
    return out, new_cache
