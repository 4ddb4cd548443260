"""Elastic scaling: save a sharded train state on one mesh, restore it onto
a different topology (grow/shrink) purely through the checkpoint template.

Run with multiple CPU placeholder devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/elastic_restore.py
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np
from jax.sharding import AxisType

from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.ft import snapshot_resharded
from repro.io import IOPolicy, open_store
from repro.models import make_model
from repro.models.spec import param_shardings
from repro.sharding.rules import ShardingRules, TRAIN_RULES


def mesh_of(data: int, model: int) -> jax.sharding.Mesh:
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def main() -> None:
    cfg = get_config("olmo-1b").reduced()
    model = make_model(cfg)
    spec = model.spec()

    # --- train-time topology: 4 x 2 ------------------------------------------
    mesh_a = mesh_of(4, 2)
    rules_a = ShardingRules(mesh_a, dict(TRAIN_RULES))
    with mesh_a:
        params = model.init(jax.random.key(0))
        shardings_a = param_shardings(spec, rules_a)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s else x, params, shardings_a
        )

    store = open_store("sims3://elastic?latency_ms=2&bw_mbps=200")
    save_checkpoint(store, "elastic", 0, params,
                    policy=IOPolicy(write_depth=4))
    print(f"saved on mesh {dict(zip(mesh_a.axis_names, mesh_a.devices.shape))}")

    # --- restore onto a DIFFERENT topology: 2 x 4 ------------------------------
    mesh_b = mesh_of(2, 4)
    rules_b = ShardingRules(mesh_b, dict(TRAIN_RULES))
    shardings_b = param_shardings(spec, rules_b)
    template = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        if s is not None else jax.ShapeDtypeStruct(x.shape, x.dtype),
        params, shardings_b,
    )
    with mesh_b:
        restored, _ = restore_checkpoint(store, "elastic", template)

    # --- verify bit-identical logical arrays, new physical layout --------------
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_resharded = sum(
        s is not None for s in jax.tree.leaves(
            shardings_b, is_leaf=lambda x: x is None or hasattr(x, "spec"))
    )
    print(f"restored onto mesh {dict(zip(mesh_b.axis_names, mesh_b.devices.shape))}: "
          f"values identical, {n_resharded} sharded leaves re-laid-out")

    # --- snapshot the resized job so the reshard is immediately crash-safe -----
    snapshot_resharded(store, "elastic", 1, restored, shardings_b,
                       policy=IOPolicy(write_depth=4))
    assert latest_step(store, "elastic") == 1
    print("OK: elastic restore verified; post-reshard snapshot committed")


if __name__ == "__main__":
    main()
