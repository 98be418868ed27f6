"""Production meshes.

Single pod  : (data=16, model=16)            = 256 chips (v5e pod)
Multi-pod   : (pod=2, data=16, model=16)     = 512 chips

A FUNCTION (not a module constant) so importing never touches jax device
state — only launch/dryrun.py sets the 512-device host platform flag.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """`jax.make_mesh` with Auto axes, which shard_map and the sharding
    rules here expect."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(model_parallel: int = 2):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    return make_mesh_compat((n // mp, mp), ("data", "model"))
