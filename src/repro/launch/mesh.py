"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Production shapes:

  - single pod:  (16, 16)        axes ("data", "model")  = 256 chips
  - multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") = 512 chips

The dry-run spawns these over 512 XLA host-platform placeholder devices;
on real hardware the same function builds the mesh over TPU devices with
ICI-contiguous model axes.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """Degenerate 1-device mesh for laptop runs (same code path)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def make_cache_mesh(n_shards: int):
    """1-D ``("cache",)`` mesh over the first ``n_shards`` devices for the
    sharded semantic-cache resident store (row-partitioned slab, one shard
    per device).

    Returns ``None`` when fewer devices exist (or ``n_shards <= 1``) —
    callers fall back to a single-device per-shard loop that computes the
    identical per-shard/merge math, so shard-count semantics never depend
    on the machine the code happens to run on.
    """
    import numpy as np
    devices = jax.devices()
    if n_shards <= 1 or len(devices) < n_shards:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:n_shards]), ("cache",))


def abstract_mesh(shape, axis_names):
    """A device-free ``jax.sharding.AbstractMesh`` of ``shape`` with
    ``axis_names`` — what every analysis path (sharding-plan rules, HLO
    cost tests) builds its meshes from."""
    from jax.sharding import AbstractMesh
    shape = tuple(int(s) for s in shape)
    axis_names = tuple(axis_names)
    assert len(shape) == len(axis_names)
    return AbstractMesh(shape, axis_names)
