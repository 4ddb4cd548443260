"""The granite-4.0-h-micro hybrid as the benchmark builds it, from a
configuration file that holds the published `config.json` keys as run.

`model` is the program's architecture (`program_arch`) with the file's sizes;
`make` draws the weights from the seed in the program's layout, one
`fold_in(key, leaf index)` a leaf as `weights.make` does; `model` refuses a
program whose layout differs from the one made here, shape for shape.

Model FLOPs per token of one forward and backward pass
(`train_flops_per_token`), recomputation not counted:

  6 N                  every parameter, the tied table once (the PaLM count);
  + 12 L_attn H Dh S   attention's scores and weighted values over the whole
                       sequence S, as PaLM counts them;
  + 3 L_mamba F_ssd    the SSD scan forward and twice that backward, where a
                       token's forward share of the chunked scan (chunk Lc,
                       H heads of P, G groups of state N) is
                       F_ssd = 2 Lc G N     C B^T within its chunk, a group's
                                            heads sharing B and C
                             + 2 Lc H P     the decay-weighted product with x
                             + 4 H P N      its chunk's state and the state's
                                            part of its output.

Like attention's, the intra-chunk terms count the whole Lc x Lc block, the
masked half included.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.flops import padded_vocab
from bench.weights import seed_key

EMBED_STD = 0.02


def _check(cfg: dict) -> None:
    """Refuse what the program's architecture has no setting for."""
    fixed = {"model_type": "granitemoehybrid", "hidden_act": "silu",
             "normalization_function": "rmsnorm", "attention_bias": False,
             "mamba_proj_bias": False, "num_local_experts": 0, "tie_word_embeddings": True}
    wrong = {k: cfg[k] for k, v in fixed.items() if cfg[k] != v}
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_expand"] * cfg["hidden_size"]:
        wrong["mamba_n_heads"] = cfg["mamba_n_heads"]
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        wrong["layer_types"] = len(cfg["layer_types"])
    if wrong:
        raise ValueError(f"{cfg['name']}: the program cannot run {wrong}")


def program_config(cfg: dict):
    """The program's `ModelConfig` at the file's sizes."""
    from repro.configs import get_config

    _check(cfg)
    arch = dataclasses.replace(
        get_config(cfg["program_arch"]),
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["shared_intermediate_size"],
        vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logit_scale=1.0 / cfg["logits_scaling"],
        use_rope=cfg["position_embedding_type"] != "nope",
        ssm_state=cfg["mamba_d_state"],
        ssm_expand=cfg["mamba_expand"],
        ssm_head_dim=cfg["mamba_d_head"],
        ssm_ngroups=cfg["mamba_n_groups"],
        ssm_chunk=cfg["mamba_chunk_size"],
        ssm_conv_kernel=cfg["mamba_d_conv"],
        ssm_conv_bias=cfg["mamba_conv_bias"],
    )
    kinds = ["attention" if b.mixer == "attn" else "mamba" for b in arch.pattern]
    if cfg["layer_types"] != kinds * arch.periods:
        raise ValueError(f"{cfg['name']}: layer_types is not the program's period "
                         f"{kinds} repeated")
    return arch


def model(cfg: dict):
    from repro.models import make_model

    m = make_model(program_config(cfg))
    program = jax.eval_shape(m.init, jax.random.key(0))
    ours = jax.eval_shape(lambda k: make(cfg, k), jax.random.key(0))
    if jax.tree.structure(program) != jax.tree.structure(ours) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(program), jax.tree.leaves(ours))):
        raise ValueError(f"{cfg['name']}: the program's parameter layout differs from "
                         f"the benchmark's")
    return m


def _period(cfg: dict) -> list[str]:
    """The kinds of one period: the layer types up to where they repeat."""
    types = cfg["layer_types"]
    return next(types[:n] for n in range(1, len(types) + 1)
                if types == types[:n] * (len(types) // n))


def layout(cfg: dict) -> dict:
    """Tree of (shape, init) leaves; init is "ones", "a_log", "dt_bias" or
    the normal's std. Each block of the period is stacked over the periods."""
    kinds = _period(cfg)
    n = len(cfg["layer_types"]) // len(kinds)
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    din, h = cfg["mamba_expand"] * d, cfg["mamba_n_heads"]
    gn, k = cfg["mamba_n_groups"] * cfg["mamba_d_state"], cfg["mamba_d_conv"]
    norm = {"scale": ((n, d), "ones")}
    attn = {"wq": ((n, d, hq, dh), 1 / math.sqrt(d)), "wk": ((n, d, hkv, dh), 1 / math.sqrt(d)),
            "wv": ((n, d, hkv, dh), 1 / math.sqrt(d)),
            "wo": ((n, hq, dh, d), 1 / math.sqrt(hq * dh))}
    mamba = {"w_z": ((n, d, din), 1 / math.sqrt(d)), "w_x": ((n, d, din), 1 / math.sqrt(d)),
             "w_b": ((n, d, gn), 1 / math.sqrt(d)), "w_c": ((n, d, gn), 1 / math.sqrt(d)),
             "w_dt": ((n, d, h), 1 / math.sqrt(d)),
             "conv_x": ((n, k, din), 1 / math.sqrt(k)), "conv_b": ((n, k, gn), 1 / math.sqrt(k)),
             "conv_c": ((n, k, gn), 1 / math.sqrt(k)),
             "dt_bias": ((n, h), "dt_bias"), "a_log": ((n, h), "a_log"),
             "d_skip": ((n, h), "ones"), "norm_scale": ((n, din), "ones"),
             "w_out": ((n, din, d), 1 / math.sqrt(din))}
    if cfg["mamba_conv_bias"]:
        mamba.update(conv_x_bias=((n, din), 1 / math.sqrt(k)),
                     conv_b_bias=((n, gn), 1 / math.sqrt(k)),
                     conv_c_bias=((n, gn), 1 / math.sqrt(k)))
    ffn = {"w_up": ((n, d, f), 1 / math.sqrt(d)), "w_gate": ((n, d, f), 1 / math.sqrt(d)),
           "w_down": ((n, f, d), 1 / math.sqrt(f))}
    blocks = {}
    for i, kind in enumerate(kinds):
        mixer = {"attn": attn} if kind == "attention" else {"mamba": mamba}
        blocks[f"block{i}"] = {"norm1": norm, **mixer, "norm2": norm, "ffn": ffn}
    return {"embed": {"table": ((padded_vocab(cfg), d), EMBED_STD)},
            "layers": blocks, "final_norm": {"scale": ((d,), "ones")}}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(shape: tuple, init, key: jax.Array) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":     # A = -exp(a_log), a_log = log(U[1, 16])
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "dt_bias":   # softplus(dt_bias) = dt, log-uniform in [1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return jnp.log(jnp.expm1(dt))
    return jax.random.normal(key, shape, jnp.float32) * init


def make(cfg: dict, key: jax.Array) -> dict:
    """The weight tree (traceable: call it under `jax.jit`)."""
    leaves, treedef = jax.tree.flatten(layout(cfg), is_leaf=_is_leaf)
    return jax.tree.unflatten(treedef, [_draw(shape, init, jax.random.fold_in(key, i))
                                        for i, (shape, init) in enumerate(leaves)])


LAYOUT_KEYS = ("layer_types", "hidden_size", "shared_intermediate_size", "num_attention_heads",
               "num_key_value_heads", "mamba_expand", "mamba_n_heads", "mamba_n_groups",
               "mamba_d_state", "mamba_d_conv", "mamba_conv_bias", "vocab_size")


def _static(cfg: dict) -> tuple:
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in ((k, cfg[k]) for k in LAYOUT_KEYS))


@partial(jax.jit, static_argnums=0)
def _make(items: tuple, key: jax.Array) -> dict:
    cfg = dict(items)
    return make({**cfg, "layer_types": list(cfg["layer_types"])}, key)


def make_jitted(cfg: dict, seed: int) -> dict:
    """The weights of `seed`, made on the device in one compiled call."""
    return _make(_static(cfg), seed_key(seed))


def param_count(cfg: dict) -> int:
    leaves = jax.tree.leaves(layout(cfg), is_leaf=_is_leaf)
    return sum(math.prod(shape) for shape, _ in leaves)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    types = cfg["layer_types"]
    hq = cfg["num_attention_heads"]
    attn = 12 * types.count("attention") * hq * (cfg["hidden_size"] // hq) * seq_len
    lc, h, p = cfg["mamba_chunk_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    ssd = 2 * lc * g * n + 2 * lc * h * p + 4 * h * p * n
    return 6.0 * param_count(cfg) + attn + 3.0 * types.count("mamba") * ssd
