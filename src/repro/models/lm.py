"""Decoder-only LM backbone covering dense / MoE / SSM / hybrid / VLM archs.

The layer stack is expressed as `cfg.periods` repetitions of the config's
block pattern and lowered as ONE `jax.lax.scan` over stacked per-period
parameters — HLO size is O(pattern), not O(depth), keeping 40-cell x
2-mesh dry-run compiles tractable. Training remats each period (inputs
saved, internals recomputed), bounding live activations to the residual
stream; in a period of more than one block it remats each block instead,
so the backward pass holds one block's internals at a time, not the whole
period's. Named scopes (`attn`, `mamba`, `ffn`, `loss`) tag each layer's
operations in the device trace's metadata.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import BlockDef, ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssd as S
from repro.models.spec import stacked
from repro.sharding.rules import constrain

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Block spec / forward
# --------------------------------------------------------------------------- #
def block_spec(cfg: ModelConfig, bd: BlockDef) -> dict:
    spec: dict = {"norm1": L.norm_spec(cfg)}
    if bd.mixer == "attn":
        spec["attn"] = L.attention_spec(cfg)
    else:
        spec["mamba"] = S.mamba_spec(cfg)
    if bd.cross_attn:
        spec["norm_cross"] = L.norm_spec(cfg)
        spec["cross"] = L.attention_spec(cfg, cross=True)
    if bd.ffn is not None and not cfg.parallel_block:
        spec["norm2"] = L.norm_spec(cfg)
    if bd.ffn == "dense":
        spec["ffn"] = L.mlp_spec(cfg)
    elif bd.ffn == "moe":
        spec["ffn"] = M.moe_spec(cfg)
    return spec


def block_cache_spec(cfg: ModelConfig, bd: BlockDef) -> dict:
    """Logical-axis tree describing this block's decode cache."""
    spec: dict = {}
    if bd.mixer == "attn":
        spec["attn"] = L.cache_logical_axes()
    else:
        spec["mamba"] = S.mamba_cache_logical_axes()
    if bd.cross_attn:
        spec["cross"] = L.cache_logical_axes()
    return spec


def make_block_cache(cfg: ModelConfig, bd: BlockDef, batch: int, max_len: int,
                     *, cross_len: int = 0, length: int = 0) -> dict:
    cache: dict = {}
    if bd.mixer == "attn":
        cache["attn"] = L.make_cache(cfg, batch, max_len, length=length)
    else:
        cache["mamba"] = S.make_mamba_cache(cfg, batch)
    if bd.cross_attn:
        cache["cross"] = L.make_cache(cfg, batch, cross_len, length=cross_len)
    return cache


def block_fwd(
    p: dict,
    cfg: ModelConfig,
    bd: BlockDef,
    x: jax.Array,
    *,
    positions: jax.Array | None,
    cache: dict | None = None,
    update_cache: bool = False,
    enc_hidden: jax.Array | None = None,
    causal: bool = True,
    q_chunk: int = 512,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    rm = jnp.asarray(cfg.residual_multiplier, x.dtype)

    h = L.apply_norm(p["norm1"], cfg, x)
    if bd.mixer == "attn":
        with jax.named_scope("attn"):
            attn_out, kv = L.attention(
                p["attn"], cfg, h,
                positions=positions,
                causal=causal,
                cache=None if cache is None else cache.get("attn"),
                update_cache=update_cache,
                q_chunk=q_chunk,
            )
        if kv is not None:
            new_cache["attn"] = kv
    else:
        with jax.named_scope("mamba"):
            attn_out, mc = S.mamba_block(
                p["mamba"], cfg, h,
                cache=None if cache is None else cache.get("mamba"),
                update_cache=update_cache,
            )
        if mc is not None:
            new_cache["mamba"] = mc

    def ffn(h):
        with jax.named_scope("ffn"):
            if bd.ffn == "dense":
                return L.mlp(p["ffn"], cfg, h), aux
            return M.moe(p["ffn"], cfg, h)

    if cfg.parallel_block and bd.ffn is not None:
        # Cohere: attn and FFN both read the same normed input.
        ffn_out, aux = ffn(h)
        x = x + rm * (attn_out + ffn_out)
        return x, new_cache, aux

    x = x + rm * attn_out

    if bd.cross_attn:
        hc = L.apply_norm(p["norm_cross"], cfg, x)
        if enc_hidden is not None:
            cross_out, _ = L.attention(
                p["cross"], cfg, hc,
                positions=positions,
                causal=False,
                kv_source=enc_hidden,
                q_chunk=q_chunk,
            )
        else:
            cross_out, _ = L.attention(
                p["cross"], cfg, hc,
                positions=positions,
                causal=False,
                cache=cache.get("cross") if cache else None,
                update_cache=False,
                q_chunk=q_chunk,
            )
        x = x + rm * cross_out
        if cache is not None and "cross" in cache:
            new_cache["cross"] = cache["cross"]

    if bd.ffn is not None:
        ffn_out, aux = ffn(L.apply_norm(p["norm2"], cfg, x))
        x = x + rm * ffn_out
    return x, new_cache, aux


# --------------------------------------------------------------------------- #
# Stack (scan over periods)
# --------------------------------------------------------------------------- #
def stack_spec(cfg: ModelConfig, pattern: tuple[BlockDef, ...] | None = None,
               periods: int | None = None) -> dict:
    pattern = pattern if pattern is not None else cfg.pattern
    periods = periods if periods is not None else cfg.periods
    period = {f"block{i}": block_spec(cfg, bd) for i, bd in enumerate(pattern)}
    return stacked(periods, period)


def make_stack_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     pattern=None, periods=None, cross_len: int = 0,
                     length: int = 0) -> dict:
    pattern = pattern if pattern is not None else cfg.pattern
    periods = periods if periods is not None else cfg.periods
    per = {
        f"block{i}": make_block_cache(
            cfg, bd, batch, max_len, cross_len=cross_len, length=length
        )
        for i, bd in enumerate(pattern)
    }
    return jax.tree.map(lambda leaf: jnp.stack([leaf] * periods), per)


def stack_cache_axes(cfg: ModelConfig, pattern=None, periods_axis: bool = True):
    """Logical-axes tree (Ax leaves) structurally matching make_stack_cache;
    used to attach shardings to abstract decode-state inputs."""
    from repro.models.spec import Ax

    pattern = pattern if pattern is not None else cfg.pattern
    per = {f"block{i}": block_cache_spec(cfg, bd) for i, bd in enumerate(pattern)}
    if not periods_axis:
        return per
    return jax.tree.map(
        lambda leaf: Ax((None, *leaf.axes)) if isinstance(leaf, Ax) else leaf,
        per,
        is_leaf=lambda x: isinstance(x, Ax) or x is None,
    )


def stack_fwd(
    p_stack: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array | None,
    caches: dict | None = None,
    update_cache: bool = False,
    enc_hidden: jax.Array | None = None,
    causal: bool = True,
    q_chunk: int = 512,
    remat: bool = False,
    pattern: tuple[BlockDef, ...] | None = None,
):
    """Scan the stacked period params over the residual stream. Caches ride
    in the scan CARRY with per-period indexed in-place updates — carrying
    them as xs/ys forces XLA to materialize input AND output stacked-cache
    buffers with a full copy per iteration (measured 4.3 GB/chip/layer of
    phantom traffic on command-r decode_32k).
    Returns (x, new_caches, total_aux)."""
    pattern = pattern if pattern is not None else cfg.pattern
    periods = jax.tree.leaves(p_stack)[0].shape[0]
    # One block: remat the period. Several: remat each block, so that the
    # backward pass recomputes one block's internals at a time.
    per_block = remat and len(pattern) > 1

    def block(bd, pb, x, cache):
        def fwd(pb, x, cache):
            return block_fwd(
                pb, cfg, bd, x,
                positions=positions,
                cache=cache,
                update_cache=update_cache,
                enc_hidden=enc_hidden,
                causal=causal,
                q_chunk=q_chunk,
            )
        return (jax.checkpoint(fwd) if per_block else fwd)(pb, x, cache)

    def period_fn(carry, xs):
        x, caches_all, aux_sum = carry
        pp, idx = xs
        pc = None
        if caches_all is not None:
            pc = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(buf, idx, 0,
                                                         keepdims=False),
                caches_all,
            )
        new_pc: dict = {}
        for i, bd in enumerate(pattern):
            x, nc, aux = block(
                bd, pp[f"block{i}"], x, None if pc is None else pc[f"block{i}"]
            )
            new_pc[f"block{i}"] = nc
            aux_sum = aux_sum + aux
        if caches_all is not None:
            caches_all = jax.tree.map(
                lambda buf, leaf: jax.lax.dynamic_update_index_in_dim(
                    buf, leaf.astype(buf.dtype), idx, 0
                ),
                caches_all, new_pc,
            )
        return (x, caches_all, aux_sum), None

    fn = jax.checkpoint(period_fn) if remat and not per_block else period_fn
    (x, new_caches, aux_sum), _ = jax.lax.scan(
        fn,
        (x, caches, jnp.zeros((), jnp.float32)),
        (p_stack, jnp.arange(periods, dtype=jnp.int32)),
    )
    return x, new_caches, aux_sum


# --------------------------------------------------------------------------- #
# LM spec + forward + loss
# --------------------------------------------------------------------------- #
def lm_spec(cfg: ModelConfig) -> dict:
    return {
        "embed": L.embedding_spec(cfg),
        "layers": stack_spec(cfg),
        "final_norm": L.norm_spec(cfg),
    }


def lm_inputs_to_hidden(p: dict, cfg: ModelConfig, inputs: jax.Array) -> jax.Array:
    """Token ids (B,S) -> embeddings, or pass through (B,S,D) embeddings
    (VLM/audio stub frontends)."""
    if inputs.ndim == 3:
        return inputs.astype(L.COMPUTE_DTYPE)
    return L.embed_tokens(p["embed"], cfg, inputs)


def lm_hidden(
    p: dict, cfg: ModelConfig, inputs: jax.Array, *,
    positions: jax.Array | None = None,
    caches=None, update_cache=False, q_chunk: int = 512, remat=False,
):
    x = lm_inputs_to_hidden(p, cfg, inputs)
    x = constrain(x, "batch", None, "residual")
    x, new_caches, aux = stack_fwd(
        p["layers"], cfg, x,
        positions=positions,
        caches=caches,
        update_cache=update_cache,
        q_chunk=q_chunk,
        remat=remat,
    )
    x = L.apply_norm(p["final_norm"], cfg, x)
    return x, new_caches, aux


def logits_from_hidden(p: dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    table = L.output_table(p["embed"])
    logits = jnp.einsum(
        "bsd,vd->bsv", h, table.astype(h.dtype),
        preferred_element_type=jnp.float32,
    ) * cfg.logit_scale
    v_pad = cfg.padded_vocab()
    if v_pad != cfg.vocab_size:
        invalid = jnp.arange(v_pad) >= cfg.vocab_size
        logits = jnp.where(invalid[None, None, :], NEG_INF, logits)
    return logits


def chunked_xent(
    p: dict, cfg: ModelConfig, h: jax.Array, labels: jax.Array,
    *, chunk: int = 512,
) -> jax.Array:
    """Mean next-token cross-entropy without materializing (B,S,V) at once.
    labels < 0 are masked out."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    hc = h.reshape(b, n, chunk, d).swapaxes(0, 1)        # (n, B, C, D)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)      # (n, B, C)

    def body(carry, xs):
        loss_sum, count = carry
        h_c, l_c = xs
        logits = logits_from_hidden(p, cfg, h_c)          # (B, C, V) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(l_c, 0)[..., None], axis=-1
        )[..., 0]
        valid = (l_c >= 0).astype(jnp.float32)
        loss_sum = loss_sum + jnp.sum((lse - picked) * valid)
        count = count + jnp.sum(valid)
        return (loss_sum, count), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc)
    )
    return loss_sum / jnp.maximum(count, 1.0)


def lm_loss(
    p: dict, cfg: ModelConfig, inputs: jax.Array, labels: jax.Array,
    *, q_chunk: int = 512, loss_chunk: int = 512, remat: bool = True,
) -> jax.Array:
    h, _, aux = lm_hidden(p, cfg, inputs, q_chunk=q_chunk, remat=remat)
    with jax.named_scope("loss"):
        loss = chunked_xent(p, cfg, h, labels, chunk=loss_chunk)
    if cfg.is_moe:
        loss = loss + cfg.moe_aux_loss_weight * aux / max(cfg.num_layers, 1)
    return loss


# --------------------------------------------------------------------------- #
# Serving steps
# --------------------------------------------------------------------------- #
def lm_prefill(
    p: dict, cfg: ModelConfig, inputs: jax.Array, *, max_len: int | None = None,
    q_chunk: int = 512,
):
    """Process the prompt; returns (last-position logits (B,V), caches)."""
    b, s = inputs.shape[0], inputs.shape[1]
    max_len = max_len if max_len is not None else s
    caches = make_stack_cache(cfg, b, max_len)
    h, caches, _ = lm_hidden(
        p, cfg, inputs, caches=caches, update_cache=True, q_chunk=q_chunk
    )
    logits = logits_from_hidden(p, cfg, h[:, -1:, :])[:, 0]
    return logits, caches


def lm_decode_step(
    p: dict, cfg: ModelConfig, inputs: jax.Array, caches, position,
):
    """One token step. inputs: (B, 1) ids or (B, 1, D) embeds; `position` is
    the scalar global position of the new token. Returns (logits, caches)."""
    positions = jnp.asarray(position, jnp.int32)[None]
    h, new_caches, _ = lm_hidden(
        p, cfg, inputs,
        positions=positions,
        caches=caches,
        update_cache=True,
        q_chunk=1,
    )
    logits = logits_from_hidden(p, cfg, h)[:, 0]
    return logits, new_caches
