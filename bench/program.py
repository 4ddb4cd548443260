"""The system under test, built from a configuration file.

A configuration file names the program's architecture (`program_arch`) and
gives every size it runs at, under the program's own keys. The model is that
architecture with those keys set; its parameter layout must match the one the
benchmark makes weights in, shape for shape.
"""

from __future__ import annotations

import dataclasses

import jax

from bench import weights

MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size", "norm_type", "norm_eps", "parametric_norm", "rope_theta",
              "tie_embeddings", "act", "glu")


def model(cfg: dict):
    from repro.configs import get_config
    from repro.models import make_model

    arch = dataclasses.replace(get_config(cfg["program_arch"]),
                               **{k: cfg[k] for k in MODEL_KEYS})
    m = make_model(arch)
    program = jax.eval_shape(m.init, jax.random.key(0))
    ours = jax.eval_shape(lambda k: weights.make(cfg, k), jax.random.key(0))
    if jax.tree.structure(program) != jax.tree.structure(ours) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(program), jax.tree.leaves(ours))):
        raise ValueError(f"{cfg['name']}: the program's parameter layout differs from "
                         f"the benchmark's")
    return m
