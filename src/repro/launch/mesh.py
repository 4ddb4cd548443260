"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_host_shard() -> tuple[int, int]:
    """``(host_id, num_hosts)`` of this process in the launch mesh — the
    pair `BlockPlan.shard` and ``restore_checkpoint(shard=...)``
    partition prefetch work by, and the host id a `repro.peer.PeerGroup`
    must be constructed with so rendezvous block ownership agrees with
    plan sharding across the fleet. Single-process runs get ``(0, 1)``."""
    return jax.process_index(), jax.process_count()
