"""The plain reference: a dense decoder-only LM and AdamW in straightforward
`jax.numpy`, importing nothing of the program.

It follows the published descriptions (Llama for SmolLM, OLMo for OLMo):
pre-norm blocks, RMSNorm with a scale or a LayerNorm without one, rotary
embeddings on the two halves of each head (theta from the configuration),
grouped-query attention where query head h reads key head h // group, a
SwiGLU feed-forward, a final norm and an output head tied to the embedding.
Logits cover the vocabulary only, not the rows the program pads it with.

`precision` selects how every matrix product is computed:

  "float32"  float32 operands at `Precision.HIGHEST` (the reference);
  "fp8"      operands rounded to float8 e4m3 with one scale per tensor, then
             multiplied in float32, in the forward and the backward products
             alike: the control, one step below the bfloat16 the
             configurations compute in.

Attention and the loss run one row at a time, and each layer is recomputed
in the backward pass, so the float32 reference fits beside the optimizer
state on one chip.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

# The configuration keys the reference reads.
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff", "vocab_size",
        "norm_type", "norm_eps", "parametric_norm", "rope_theta", "tie_embeddings")
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 under one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return _einsum(spec, _fp8(a), _fp8(b))


def _fp8_dot_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _fp8_dot_bwd(spec, res, g):
    # The backward products take float8 operands too: the incoming
    # gradient is rounded like the forward operands were.
    _, vjp = jax.vjp(lambda a, b: _einsum(spec, a, b), *res)
    return vjp(_fp8(g))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def dot(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "float32":
        return _einsum(spec, a, b)
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    return _fp8_dot(spec, a, b)


def norm(x: jax.Array, p: dict, cfg: dict) -> jax.Array:
    if cfg["norm_type"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg["norm_eps"])
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])
    return y * p["scale"] if "scale" in p else y


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_row(h: jax.Array, p: dict, cfg: dict, precision: str) -> jax.Array:
    """Causal self-attention over one row h: (S, D)."""
    hq, hkv = cfg["num_heads"], cfg["num_kv_heads"]
    q = rope(dot("sd,dhk->shk", h, p["wq"], precision), cfg["rope_theta"])
    k = rope(dot("sd,dhk->shk", h, p["wk"], precision), cfg["rope_theta"])
    v = dot("sd,dhk->shk", h, p["wv"], precision)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    s = q.shape[0]
    scores = dot("qhk,shk->hqs", q, k, precision) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = dot("hqs,shk->qhk", probs, v, precision)
    return dot("qhk,hkd->qd", out, p["wo"], precision)


def layer(x: jax.Array, p: dict, cfg: dict, precision: str) -> jax.Array:
    """One pre-norm block over x: (B, S, D); attention row by row."""
    h = norm(x, p["norm1"], cfg)
    row = jax.checkpoint(lambda r: attention_row(r, p["attn"], cfg, precision))
    x = x + jax.lax.map(row, h)
    h = norm(x, p["norm2"], cfg)
    f = p["ffn"]
    gated = jax.nn.silu(dot("bsd,df->bsf", h, f["w_gate"], precision))
    up = dot("bsd,df->bsf", h, f["w_up"], precision)
    return x + dot("bsf,fd->bsd", gated * up, f["w_down"], precision)


def hidden(w: dict, cfg: dict, ids: jax.Array, precision: str) -> jax.Array:
    """Final-norm hidden states (B, S, D) for token ids (B, S)."""
    x = jnp.take(w["embed"]["table"], ids, axis=0)
    step = jax.checkpoint(lambda x, p: (layer(x, p, cfg, precision), None))
    x, _ = jax.lax.scan(step, x, w["layers"]["block0"])
    return norm(x, w["final_norm"], cfg)


def head(w: dict, cfg: dict) -> jax.Array:
    return w["embed"].get("out_table", w["embed"]["table"])[: cfg["vocab_size"]]


def logits(w: dict, cfg: dict, h: jax.Array, precision: str) -> jax.Array:
    return dot("...d,vd->...v", h, head(w, cfg), precision)


def last_logits(w: dict, cfg: dict, ids: jax.Array, precision: str) -> jax.Array:
    """Logits (B, V) of the position after each prompt row of `ids`."""
    return logits(w, cfg, hidden(w, cfg, ids, precision)[:, -1], precision)


def loss(w: dict, cfg: dict, ids: jax.Array, labels: jax.Array, precision: str) -> jax.Array:
    """Mean next-token cross-entropy over every position of every row."""
    h = hidden(w, cfg, ids, precision)

    @jax.checkpoint
    def row(args):
        h_r, l_r = args
        z = logits(w, cfg, h_r, precision)
        return jnp.sum(jax.nn.logsumexp(z, axis=-1)
                       - jnp.take_along_axis(z, l_r[:, None], axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size


def static(cfg: dict) -> tuple:
    """The configuration as a hashable static argument."""
    return tuple((k, cfg[k]) for k in KEYS)


@partial(jax.jit, static_argnums=(1, 3))
def prompt_logits(w, cfg_items, ids, precision):
    return last_logits(w, dict(cfg_items), ids, precision)


@partial(jax.jit, static_argnums=(1, 4))
def loss_and_grad(w, cfg_items, ids, labels, precision):
    cfg = dict(cfg_items)
    return jax.value_and_grad(loss)(w, cfg, ids, labels, precision)


def lr_at(opt: dict, step: jax.Array) -> jax.Array:
    """Linear warm-up to `lr`, then a cosine down to `min_lr_ratio * lr`."""
    t = step.astype(jnp.float32)
    warm, total = opt["warmup_steps"], opt["total_steps"]
    frac = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(math.pi * frac))
    return opt["lr"] * jnp.minimum(t / max(warm, 1), 1.0) * jnp.where(t < warm, 1.0, cos)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def adamw(opt_items, w, m, v, grads, step):
    """AdamW with the global gradient norm clipped; returns the new
    (w, m, v) and the clipped gradient's per-leaf norms."""
    opt = dict(opt_items)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, opt["grad_clip_norm"] / (gnorm + 1e-9)),
                         grads)
    lr = lr_at(opt, step)
    m = jax.tree.map(lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g, m, grads)
    v = jax.tree.map(lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g, v, grads)
    b1c, b2c = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step
    w = jax.tree.map(lambda p, m, v: p - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
                                               + opt["weight_decay"] * p), w, m, v)
    return w, m, v, [jnp.sqrt(jnp.sum(g * g)) for g in jax.tree.leaves(grads)]


@jax.jit
def _change_norms(w, w0):
    return [jnp.sqrt(jnp.sum((a - b) ** 2)) for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w0))]


def train(make_w, cfg: dict, opt: dict, batches, precision: str = "float32") -> dict:
    """Follow the program's first steps from the weights `make_w()` gives, on
    `batches` [(ids, labels)]: each step's loss, the clipped first
    gradient's per-leaf norms and, after the last step, each leaf's change.
    The starting weights are made again for the change, not kept."""
    cfg_items, opt_items = static(cfg), tuple(sorted(opt.items()))
    w = make_w()
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    for i, (ids, labels) in enumerate(batches):
        value, grads = loss_and_grad(w, cfg_items, ids, labels, precision)
        w, m, v, norms = adamw(opt_items, w, m, v, grads, jnp.asarray(i + 1, jnp.float32))
        del grads
        losses.append(value)
        first = norms if first is None else first
    del m, v
    change = _change_norms(w, make_w())
    return {"losses": [float(x) for x in losses],
            "grad_norms": [float(x) for x in first],
            "change_norms": [float(x) for x in change]}
