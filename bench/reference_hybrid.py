"""The plain reference for the hybrid configurations: a copy of the
program's `repro.models.ref_hybrid`, importing nothing of the program, plus
the training it follows. AdamW and its schedule are `bench/reference.py`'s.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, with no kernel, cache, chunked
dual form or batching trick, written from the published description
(`granitemoehybrid`):

  x = embedding(ids) * embedding_multiplier
  per layer:  x += residual_multiplier * mixer(rmsnorm(x))
              x += residual_multiplier * swiglu(rmsnorm(x))
  logits = rmsnorm(x) @ E^T / logits_scaling

The mixer is causal softmax attention with scores `Q K^T *
attention_multiplier` and no positional encoding, or Mamba-2 run as its
recurrence, one time step after another:

  x, B, C = silu(causal depthwise conv(., bias)),  dt = softplus(dt + dt_bias)
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  A = -exp(A_log)
  y_t = h_t C_t + D x_t,   out = W_out gated_rmsnorm(y * silu(z))

Attention runs in blocks of query rows, the recurrence is checkpointed
every `mamba_chunk_size` steps and every layer is recomputed in the
backward pass, so that the loss's gradient fits beside the optimizer state
on one chip.

`cfg` is the configuration file: the published `config.json` keys, cut as
run; weights come in the program's layout (`bench/hybrid.py`). Departures
from the published model, none of which changes the function computed:

- z, x, B, C and dt have a projection each and x, B and C a conv each, where
  the published block fuses them into one `in_proj` and one `conv1d` over
  `xBC`: the same products, split along the output channels.
- A vocabulary cut to a slice is a smaller vocabulary: logits and loss are
  over the slice (`vocab_size` rows of the table).
- dt is not clamped: the published `time_step_limit` is (0, inf).

`dot` computes every matrix product of the model. `train` takes it from
`bench/reference.py`'s `precision`: "float32" (the reference) or "fp8" (the
control, every product's operands rounded to float8 e4m3, forward and
backward). The recurrence's own products stay float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import reference

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256   # query rows a block of attention computes at once


def matmul(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(h: jax.Array, p: dict, cfg: dict, dot=matmul) -> jax.Array:
    """Causal GQA self-attention, no positional encoding; h: (B, S, D)."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = dot("bsd,dhk->bshk", h, p["wq"])
    k = jnp.repeat(dot("bsd,dhk->bshk", h, p["wk"]), hq // hkv, axis=2)
    v = jnp.repeat(dot("bsd,dhk->bshk", h, p["wv"]), hq // hkv, axis=2)
    b, s = h.shape[:2]
    blocks = -(-s // Q_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, blocks * Q_BLOCK - s), (0, 0), (0, 0)))
    q = q.reshape(b, blocks, Q_BLOCK, hq, -1).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        q_b, first = args
        scores = dot("bqhk,bshk->bhqs", q_b, k) * cfg["attention_multiplier"]
        rows = first + jnp.arange(Q_BLOCK)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return dot("bhqs,bshk->bqhk", probs, v)

    out = jax.lax.map(block, (q, jnp.arange(blocks) * Q_BLOCK))
    out = out.swapaxes(0, 1).reshape(b, blocks * Q_BLOCK, hq, -1)[:, :s]
    return dot("bshk,hkd->bsd", out, p["wo"])


def causal_conv(u: jax.Array, w: jax.Array, bias: jax.Array | None) -> jax.Array:
    """Depthwise causal conv over time: y_t = sum_i w[i] u_{t-K+1+i} + bias."""
    k, s = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(up[:, i:i + s] * w[i] for i in range(k))
    return y if bias is None else y + bias


def recurrence(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
               cm: jax.Array, chunk: int) -> jax.Array:
    """y_t = h_t C_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, one
    step at a time; x: (B, S, H, P), dt: (B, S, H), B/C: (B, S, H, N).
    Checkpointed every `chunk` steps."""
    b, s, nh, pd = x.shape
    n = bm.shape[-1]
    steps = -(-s // chunk) * chunk
    pad = ((0, 0), (0, steps - s))

    def per_chunk(t):   # (B, S, ...) -> (chunks, chunk, B, ...)
        t = jnp.pad(t, pad + ((0, 0),) * (t.ndim - 2))
        return t.swapaxes(0, 1).reshape(steps // chunk, chunk, *t.shape[:1], *t.shape[2:])

    def step(hs, inp):
        x_t, dt_t, b_t, c_t = inp
        hs = (jnp.exp(dt_t * a)[..., None, None] * hs
              + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return hs, jnp.einsum("bhpn,bhn->bhp", hs, c_t)

    @jax.checkpoint
    def span(hs, inp):
        return jax.lax.scan(step, hs, inp)

    _, y = jax.lax.scan(span, jnp.zeros((b, nh, pd, n), x.dtype),
                        tuple(per_chunk(t) for t in (x, dt, bm, cm)))
    return y.reshape(steps, b, nh, pd).swapaxes(0, 1)[:, :s]


def mamba(h: jax.Array, p: dict, cfg: dict, dot=matmul) -> jax.Array:
    """Mamba-2 mixer as its recurrence; h: (B, S, D)."""
    nh, pd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    b, s, _ = h.shape
    z = dot("bsd,de->bse", h, p["w_z"])
    xs, bm, cm = (
        jax.nn.silu(causal_conv(dot("bsd,de->bse", h, p[f"w_{c}"]), p[f"conv_{c}"],
                                p.get(f"conv_{c}_bias")))
        for c in "xbc")
    dt = jax.nn.softplus(dot("bsd,dh->bsh", h, p["w_dt"]) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    x = xs.reshape(b, s, nh, pd)
    # Head i reads the B and C of group i // (heads per group).
    bm = jnp.repeat(bm.reshape(b, s, g, n), nh // g, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, n), nh // g, axis=2)
    y = recurrence(x, dt, a, bm, cm, cfg["mamba_chunk_size"])
    y = (y + p["d_skip"][:, None] * x).reshape(b, s, nh * pd)
    gated = (y * jax.nn.silu(z)).reshape(b, s, g, -1)
    gated = rmsnorm(gated, 1.0, cfg["rms_norm_eps"]).reshape(b, s, -1) * p["norm_scale"]
    return dot("bse,ed->bsd", gated, p["w_out"])


def swiglu(h: jax.Array, p: dict, dot=matmul) -> jax.Array:
    gate = jax.nn.silu(dot("bsd,df->bsf", h, p["w_gate"]))
    return dot("bsf,fd->bsd", gate * dot("bsd,df->bsf", h, p["w_up"]), p["w_down"])


def layer_params(w: dict, layer: int) -> dict:
    """Layer `layer`'s weights: the program stacks each block of the period
    over the periods, so layer l is block l % period of period l // period."""
    blocks = w["layers"]
    period = len(blocks)
    return jax.tree.map(lambda leaf: leaf[layer // period], blocks[f"block{layer % period}"])


def layer(x: jax.Array, p: dict, kind: str, cfg: dict, dot=matmul) -> jax.Array:
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rmsnorm(x, p["norm1"]["scale"], eps)
    if kind == "attention":
        x = x + rm * attention(h, p["attn"], cfg, dot)
    else:
        x = x + rm * mamba(h, p["mamba"], cfg, dot)
    return x + rm * swiglu(rmsnorm(x, p["norm2"]["scale"], eps), p["ffn"], dot)


def hidden(w: dict, cfg: dict, ids: jax.Array, dot=matmul) -> jax.Array:
    """Final-norm hidden states (B, S, D) for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"]["table"], ids, axis=0) * cfg["embedding_multiplier"]
        for i, kind in enumerate(cfg["layer_types"]):
            run = jax.checkpoint(lambda x, p, kind=kind: layer(x, p, kind, cfg, dot))
            x = run(x, layer_params(w, i))
        return rmsnorm(x, w["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits(w: dict, cfg: dict, h: jax.Array, dot=matmul) -> jax.Array:
    table = w["embed"]["table"][: cfg["vocab_size"]]
    with jax.default_matmul_precision("highest"):
        return dot("...d,vd->...v", h, table) / cfg["logits_scaling"]


def loss(w: dict, cfg: dict, ids: jax.Array, labels: jax.Array, dot=matmul) -> jax.Array:
    """Mean next-token cross-entropy over every position of every row."""
    h = hidden(w, cfg, ids, dot)

    @jax.checkpoint
    def row(args):
        h_r, l_r = args
        z = logits(w, cfg, h_r, dot)
        return jnp.sum(jax.nn.logsumexp(z, axis=-1)
                       - jnp.take_along_axis(z, l_r[:, None], axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "attention_multiplier",
        "embedding_multiplier", "residual_multiplier", "logits_scaling", "rms_norm_eps",
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "mamba_chunk_size", "layer_types", "vocab_size")


def static(cfg: dict) -> tuple:
    """The configuration as a hashable static argument."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in KEYS)


@partial(jax.jit, static_argnums=(1, 4))
def loss_and_grad(w, cfg_items, ids, labels, precision):
    cfg = dict(cfg_items)
    dot = partial(reference.dot, precision=precision)
    return jax.value_and_grad(loss)(w, cfg, ids, labels, dot)


@jax.jit
def _change_norms(w, w0):
    return [jnp.sqrt(jnp.sum((a - b) ** 2)) for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w0))]


def train(make_w, cfg: dict, opt: dict, batches, precision: str = "float32") -> dict:
    """Follow the program's first steps from the weights `make_w()` gives, on
    `batches` [(ids, labels)]: each step's loss, the clipped first
    gradient's per-leaf norms and, after the last step, each leaf's change.
    The starting weights are made again for the change, not kept."""
    cfg_items, opt_items = static(cfg), tuple(sorted(opt.items()))
    # The gradient is computed on the chip, AdamW's update on the host: the
    # float32 weights, moments, gradient and activations of a one-chip cut
    # do not fit the chip's memory at once. Both are float32 arithmetic.
    host = jax.local_devices(backend="cpu")[0]
    w = make_w()
    chip = next(iter(jax.tree.leaves(w)[0].devices()))
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype, device=host), w)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype, device=host), w)
    losses, first = [], None
    for i, (ids, labels) in enumerate(batches):
        value, grads = loss_and_grad(w, cfg_items, ids, labels, precision)
        w, grads = jax.device_put((w, grads), host)
        w, m, v, norms = reference.adamw(opt_items, w, m, v, grads,
                                         jnp.asarray(i + 1, jnp.float32, device=host))
        del grads
        w = jax.device_put(w, chip)
        losses.append(value)
        first = norms if first is None else first
    del m, v
    change = _change_norms(w, make_w())
    return {"losses": [float(x) for x in losses],
            "grad_norms": [float(x) for x in first],
            "change_norms": [float(x) for x in change]}
