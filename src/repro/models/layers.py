"""Shared transformer layers: norms, RoPE, GQA attention, gated MLP.

Design notes for multi-pod scale:

* Attention shards its *KV-sequence* dimension over the tensor axis
  ("kv_seq" rule) rather than heads. Head counts across the assigned
  archs (96, 32, 9, 16, 20, 64, 24, 48) mostly do not divide a 16-way
  axis, while every assigned seq_len does; seq-sharding is uniform,
  always divisible, and keeps the O(S) score tensors distributed.
  Softmax/contractions over the sharded dim lower to LSE-style partial
  reductions + all-reduce under GSPMD (flash-decoding structure).
* Queries are processed in chunks via `lax.scan` (online, bounded memory)
  so 32k prefill and 4k train never materialize full S×S scores.
* Cache-less causal self-attention on one TPU device goes to the fused
  Pallas kernel (`repro.kernels.flash_attention`): no score tile reaches
  HBM, and blocks above the diagonal are skipped. Every other call, and
  every platform but the TPU, takes the chunked XLA path.
* All matmuls run in bf16 with fp32 softmax/norm accumulations.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.kernels.flash_attention import BLOCK as KERNEL_BLOCK, HEAD_DIMS as KERNEL_HEAD_DIMS
from repro.models.spec import ParamSpec
from repro.sharding.rules import constrain, current_rules
from repro.utils import trace

COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def norm_spec(cfg: ModelConfig, dim: int | None = None) -> dict:
    d = dim if dim is not None else cfg.d_model
    if not cfg.parametric_norm:
        return {}
    spec = {"scale": ParamSpec((d,), (None,), "ones")}
    if cfg.norm_type == "layernorm" and cfg.norm_bias:
        spec["bias"] = ParamSpec((d,), (None,), "zeros")
    return spec


def apply_norm(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps)
    else:  # layernorm
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    if p.get("scale") is not None:
        y = y * p["scale"].astype(jnp.float32)
    if p.get("bias") is not None:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Rotary position embedding
# --------------------------------------------------------------------------- #
def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D_h); positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int, offset: int = 0) -> jax.Array:
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe


# --------------------------------------------------------------------------- #
# Embedding
# --------------------------------------------------------------------------- #
def embedding_spec(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab()
    spec = {"table": ParamSpec((v, cfg.d_model), ("tp", "fsdp"), ("normal", 0.02))}
    if not cfg.tie_embeddings:
        spec["out_table"] = ParamSpec(
            (v, cfg.d_model), ("tp", "fsdp"), ("normal", 0.02)
        )
    return spec


def embed_tokens(p: dict, cfg: ModelConfig, ids: jax.Array) -> jax.Array:
    x = jnp.take(p["table"], ids, axis=0).astype(COMPUTE_DTYPE)
    return x * jnp.asarray(cfg.embedding_multiplier, COMPUTE_DTYPE)


def output_table(p: dict) -> jax.Array:
    return p.get("out_table", p["table"])


# --------------------------------------------------------------------------- #
# Attention (GQA, RoPE, chunked online computation, KV cache)
# --------------------------------------------------------------------------- #
class KVCache(NamedTuple):
    k: jax.Array      # (B, S_max, H_kv, D_h)
    v: jax.Array
    length: jax.Array  # scalar int32: number of valid positions


def attention_spec(cfg: ModelConfig, cross: bool = False) -> dict:
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((d, hq, dh), ("fsdp", "tp", None), ("fan_in", d)),
        "wk": ParamSpec((d, hkv, dh), ("fsdp", "tp", None), ("fan_in", d)),
        "wv": ParamSpec((d, hkv, dh), ("fsdp", "tp", None), ("fan_in", d)),
        "wo": ParamSpec((hq, dh, d), ("tp", None, "fsdp"), ("fan_in", hq * dh)),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((hq, dh), ("tp", None), "zeros")
        spec["bk"] = ParamSpec((hkv, dh), ("tp", None), "zeros")
        spec["bv"] = ParamSpec((hkv, dh), ("tp", None), "zeros")
    if cfg.out_bias:
        spec["bo"] = ParamSpec((d,), (None,), "zeros")
    if cfg.qk_norm:
        spec["q_norm"] = norm_spec(cfg, dh)
        spec["k_norm"] = norm_spec(cfg, dh)
    return spec


def _head_shardable(hq: int) -> bool:
    """True when the q-head count divides the tensor axis under the ambient
    rules — selects the collective-free head-sharded attention path."""
    rules = current_rules()
    return (
        rules is not None
        and rules.mesh is not None
        and rules.resolve_dim("heads", hq) is not None
    )


def _kernel_fits(q: jax.Array, k: jax.Array, *, causal: bool, q_offset,
                 kv_valid_len) -> bool:
    """True for what the fused kernel computes: causal self-attention with
    no cache over the call's own positions from 0 (`q_offset` the int 0),
    in whole kernel blocks, of a head size it takes, with no mesh of more
    than one device to shard it."""
    rules = current_rules()
    s = q.shape[1]
    return (causal and kv_valid_len is None
            and isinstance(q_offset, int) and q_offset == 0
            and k.shape[1] == s and s % KERNEL_BLOCK == 0
            and q.shape[-1] in KERNEL_HEAD_DIMS
            and (rules is None or rules.mesh is None or rules.mesh.size == 1))


def _attn_kernel(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """The fused causal kernel, in the model's (B, S, H, D) layout."""
    o = kernel_ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                   v.swapaxes(1, 2), causal=True, scale=scale)
    return o.swapaxes(1, 2)


def _attn_core(
    q: jax.Array,          # (B, S_q, H_q, D_h)
    k: jax.Array,          # (B, S_k, H_kv, D_h)
    v: jax.Array,
    *,
    causal: bool,
    scale: float,          # softmax scale of the scores
    q_offset,              # scalar: global position of q[0]; the int 0 when
                           # q and k are the call's own positions from 0
    kv_valid_len=None,     # scalar: mask kv positions >= this
    q_chunk: int = 512,
    allow_head_shard: bool = True,
) -> jax.Array:
    """Attention by the fused kernel where it fits and the lowering is for
    a TPU, else by `_attn_xla`. Counts `attn.kernel` or `attn.xla` in
    `repro.utils.trace` for the path each lowered call holds."""
    def xla(q, k, v):
        out = _attn_xla(q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                        kv_valid_len=kv_valid_len, q_chunk=q_chunk,
                        allow_head_shard=allow_head_shard)
        return trace.tag(out, "attn.xla")

    if not _kernel_fits(q, k, causal=causal, q_offset=q_offset,
                        kv_valid_len=kv_valid_len):
        return xla(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=xla,
        tpu=lambda q, k, v: trace.tag(_attn_kernel(q, k, v, scale), "attn.kernel"))


def _attn_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    q_offset,
    kv_valid_len,
    q_chunk: int,
    allow_head_shard: bool,
) -> jax.Array:
    """Chunked online attention; never materializes S_q x S_k at once.

    Two internal sharding modes (§Perf, command-r train_4k):
      * head-sharded — KV expanded to q-heads by a shard-local gather, flat
        head dim over the tensor axis: zero intra-attention collectives;
      * kv_seq-sharded fallback — score/context tensors sharded along the
        KV sequence; softmax partials + output partial sums all-reduce.
    """
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    head_mode = allow_head_shard and _head_shardable(hq)
    kv_pos = jnp.arange(sk, dtype=jnp.int32)

    if head_mode:
        # Shard-local expansion: each model shard gathers the kv heads its
        # q-heads read (h // g), so k/v land head-sharded with no collective.
        idx = jnp.arange(hq, dtype=jnp.int32) // g
        k = constrain(jnp.take(k, idx, axis=2), "batch", None, "heads", None)
        v = constrain(jnp.take(v, idx, axis=2), "batch", None, "heads", None)
    else:
        k = constrain(k, "batch", "kv_seq", None, None)
        v = constrain(v, "batch", "kv_seq", None, None)

    def chunk_attn(q_c: jax.Array, offset) -> jax.Array:
        # q_c: (B, C, H_q, D_h); offset: global position of q_c[0]
        c = q_c.shape[1]
        mask = None
        if causal:
            q_pos = offset + jnp.arange(c, dtype=jnp.int32)
            mask = kv_pos[None, :] <= q_pos[:, None]          # (C, S_k)
        if kv_valid_len is not None:
            valid = (kv_pos < kv_valid_len)[None, :]
            mask = valid if mask is None else (mask & valid)

        if head_mode:
            qh = constrain(q_c, "batch", None, "heads", None)
            s = jnp.einsum(
                "bqhd,bshd->bhqs", qh, k, preferred_element_type=jnp.float32
            ) * scale
            s = constrain(s, "batch", "heads", None, None)
            if mask is not None:
                s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "bhqs,bshd->bqhd", p.astype(v.dtype), v,
                preferred_element_type=v.dtype,
            )
            return o.astype(q.dtype)

        qg = q_c.reshape(b, c, hkv, g, dh)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
        ) * scale
        s = constrain(s, "batch", None, None, None, "kv_seq")
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # Output in compute dtype: the kv_seq-sharded contraction produces
        # partial sums that GSPMD all-reduces — emitting bf16 halves the
        # dominant collective payload (softmax itself stays fp32 above).
        o = jnp.einsum(
            "bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
            preferred_element_type=v.dtype,
        )
        return o.reshape(b, c, hq, dh).astype(q.dtype)

    if sq <= q_chunk:
        return chunk_attn(q, q_offset)
    assert sq % q_chunk == 0, (sq, q_chunk)
    n_chunks = sq // q_chunk
    q_chunks = q.reshape(b, n_chunks, q_chunk, hq, dh)

    # Remat each chunk: differentiating the scan would otherwise stash fp32
    # probabilities + masks for every chunk (flash-style recompute instead).
    chunk_attn_ckpt = jax.checkpoint(chunk_attn)

    def body(_, xs):
        q_c, idx = xs
        return None, chunk_attn_ckpt(q_c, q_offset + idx * q_chunk)

    _, out = jax.lax.scan(
        body, None, (q_chunks.swapaxes(0, 1), jnp.arange(n_chunks))
    )
    return out.swapaxes(0, 1).reshape(b, sq, hq, dh)


def attention(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, S, D)
    *,
    positions: jax.Array | None,   # (S,) or (B, S) global positions of x;
                                   # None: x's own, 0..S-1
    causal: bool = True,
    kv_source: jax.Array | None = None,   # cross-attention source (B, S_kv, D)
    cache: KVCache | None = None,
    update_cache: bool = False,    # decode: write new k/v into cache
    q_chunk: int = 512,
) -> tuple[jax.Array, KVCache | None]:
    cfg_rope = cfg.use_rope and kv_source is None
    b, s, _ = x.shape
    if positions is None:
        positions, q_offset = jnp.arange(s, dtype=jnp.int32), 0
    else:
        q_offset = positions[0] if positions.ndim == 1 else positions[0, 0]

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], cfg, q)

    if cache is not None and not update_cache:
        # Read-only cache (cross-attention at decode; precomputed KV).
        k, v, kv_len = cache.k, cache.v, cache.length
        new_cache = cache
    else:
        src = kv_source if kv_source is not None else x
        k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
        if "bk" in p:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        if cfg.qk_norm:
            k = apply_norm(p["k_norm"], cfg, k)
        if cfg_rope:
            k = apply_rope(k, positions, cfg.rope_theta)
        if cache is not None:
            # Prefill/decode: append new K/V at cache.length.
            start = cache.length
            k_cache = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, start, 0, 0)
            )
            v_cache = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, start, 0, 0)
            )
            new_cache = KVCache(k_cache, v_cache, cache.length + s)
            k, v, kv_len = k_cache, v_cache, new_cache.length
        else:
            kv_len = None
            new_cache = None

    if cfg_rope:
        q = apply_rope(q, positions, cfg.rope_theta)

    out = _attn_core(
        q, k, v,
        causal=causal and kv_source is None,
        scale=cfg.attention_multiplier or cfg.head_dim ** -0.5,
        q_offset=q_offset,
        kv_valid_len=kv_len,
        q_chunk=q_chunk,
        # Cache-backed paths (prefill/decode) keep the serving KV layout
        # (kv_seq-sharded); the head-sharded mode serves training and
        # encoder/cross attention computed from source activations.
        allow_head_shard=cache is None,
    )
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(x.dtype)
    y = constrain(y, "batch", None, "residual")
    return y, new_cache


def compute_kv(p: dict, cfg: ModelConfig, src: jax.Array) -> KVCache:
    """Precompute a read-only KV cache from `src` (encoder states for
    cross-attention at decode time)."""
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(src.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(src.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(src.dtype)
        v = v + p["bv"].astype(src.dtype)
    if cfg.qk_norm:
        k = apply_norm(p["k_norm"], cfg, k)
    k = constrain(k, "batch", "kv_seq", None, None)
    v = constrain(v, "batch", "kv_seq", None, None)
    return KVCache(k=k, v=v, length=jnp.asarray(src.shape[1], jnp.int32))


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=COMPUTE_DTYPE, length: int = 0) -> KVCache:
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.asarray(length, jnp.int32),
    )


def cache_logical_axes() -> KVCache:
    from repro.models.spec import Ax

    return KVCache(
        k=Ax(("batch", "kv_seq", None, None)),
        v=Ax(("batch", "kv_seq", None, None)),
        length=None,
    )


# --------------------------------------------------------------------------- #
# MLP (gated or plain)
# --------------------------------------------------------------------------- #
def mlp_spec(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    spec = {
        "w_up": ParamSpec((d, f), ("fsdp", "tp"), ("fan_in", d)),
        "w_down": ParamSpec((f, d), ("tp", "fsdp"), ("fan_in", f)),
    }
    if cfg.glu:
        spec["w_gate"] = ParamSpec((d, f), ("fsdp", "tp"), ("fan_in", d))
    if cfg.out_bias:
        spec["b_up"] = ParamSpec((f,), ("tp",), "zeros")
        spec["b_down"] = ParamSpec((d,), (None,), "zeros")
    return spec


def _act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    if "b_up" in p:
        h = h + p["b_up"].astype(x.dtype)
    if cfg.glu:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = _act(cfg, gate) * h
    else:
        h = _act(cfg, h)
    h = constrain(h, "batch", None, "tp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    if "b_down" in p:
        y = y + p["b_down"].astype(x.dtype)
    return constrain(y, "batch", None, "residual")
