"""Weights and token streams made from the seed, by the benchmark itself.

The weight tree has the layout of a dense decoder-only LM with its layers
stacked (`layers/block0/...` with a leading layer axis), which is the layout
the program restores and trains, and the one the plain reference reads. Each
leaf is drawn from its own key, `fold_in(seed key, leaf index)`, in one jitted
call on the device, in float32. Embedding rows beyond the vocabulary (the
program pads it to `flops.VOCAB_MULTIPLE`) are drawn like the others.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import head_dim, padded_vocab

EMBED_STD = 0.02


def _norm(cfg: dict, *lead: int) -> dict:
    return {"scale": ((*lead, cfg["d_model"]), "ones")} if cfg["parametric_norm"] else {}


def layout(cfg: dict) -> dict:
    """Tree of (shape, init) leaves; init is "ones" or the normal's std."""
    n, d, f, dh = cfg["num_layers"], cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    hq, hkv = cfg["num_heads"], cfg["num_kv_heads"]
    block = {
        "norm1": _norm(cfg, n),
        "attn": {
            "wq": ((n, d, hq, dh), 1 / math.sqrt(d)),
            "wk": ((n, d, hkv, dh), 1 / math.sqrt(d)),
            "wv": ((n, d, hkv, dh), 1 / math.sqrt(d)),
            "wo": ((n, hq, dh, d), 1 / math.sqrt(hq * dh)),
        },
        "norm2": _norm(cfg, n),
        "ffn": {
            "w_up": ((n, d, f), 1 / math.sqrt(d)),
            "w_gate": ((n, d, f), 1 / math.sqrt(d)),
            "w_down": ((n, f, d), 1 / math.sqrt(f)),
        },
    }
    embed = {"table": ((padded_vocab(cfg), d), EMBED_STD)}
    if not cfg["tie_embeddings"]:
        embed["out_table"] = ((padded_vocab(cfg), d), EMBED_STD)
    return {"embed": embed, "layers": {"block0": block}, "final_norm": _norm(cfg)}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, wider than 32 bits included."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make(cfg: dict, key: jax.Array) -> dict:
    """The weight tree (traceable: call it under `jax.jit`)."""
    leaves, treedef = jax.tree.flatten(layout(cfg), is_leaf=_is_leaf)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init)
    return jax.tree.unflatten(treedef, out)


LAYOUT_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
               "vocab_size", "parametric_norm", "tie_embeddings")


@partial(jax.jit, static_argnums=0)
def _make(items: tuple, key: jax.Array) -> dict:
    return make(dict(items), key)


def make_jitted(cfg: dict, seed: int) -> dict:
    """The weights of `seed`, made on the device in one compiled call."""
    return _make(tuple((k, cfg.get(k)) for k in LAYOUT_KEYS), seed_key(seed))


def token_stream(seed: int, n_tokens: int, vocab: int) -> np.ndarray:
    """The training cell's token ids, uint32 in [0, vocab), from the seed."""
    rng = np.random.default_rng([seed % 2**64, 1])
    return rng.integers(0, vocab, size=n_tokens, dtype=np.uint32)


def prompts(seed: int, n: int, batch: int, length: int, vocab: int) -> np.ndarray:
    """`n` waves of `batch` prompts of `length` ids, int32, from the seed."""
    rng = np.random.default_rng([seed % 2**64, 2])
    return rng.integers(0, vocab, size=(n, batch, length), dtype=np.int32)
