"""Mesh construction.

Functions, not module-level constants: importing this module never
touches jax device state, so tests and benches see whatever devices the
process has (one CPU device under ``JAX_PLATFORMS=cpu``, every chip on a
TPU host) while dryrun.py (which sets XLA_FLAGS first) sees 512
placeholder devices.

Every mesh is built with ``AxisType.Auto`` axes.  JAX's default is now
``Explicit``, under which a ``shard_map`` output carries its mesh sharding
in its type and every later op must agree with it exactly (the teacher
bank's ring write into a replicated buffer is then a type error).  Auto
axes leave placement to the compiler, which is what the engine, the KD
pipeline and the bank are written for.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

CHIPS_PER_POD = 256            # 16 × 16 TPU v5e pod
PODS = 2


def _mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """``('data', 'model')`` mesh over whatever devices exist."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return _mesh((data, model), ("data", "model"))


def make_client_mesh(num_devices: int | None = None):
    """1-D ``('clients',)`` mesh for the vectorized client engine AND the
    KD pipeline's sharded teacher precompute.

    The engine stacks sampled clients along a leading axis and shard_maps
    local training over this mesh; the KD pipeline shard_maps the FedDF
    ``(C, ...)`` teacher stack's member axis over the same mesh.  The mesh
    spans every device of the process (all chips of a TPU host) unless
    ``num_devices`` says otherwise; with one device both degenerate to
    plain vmap unless REPRO_FORCE_SHARD_MAP=1 (see ``use_shard_map``).
    """
    n = num_devices or len(jax.devices())
    return _mesh((n,), ("clients",))


def mesh_size(mesh) -> int:
    """Total device count of a mesh (the shard count the engine and the
    KD pipeline pad their leading axes to)."""
    import numpy as np
    return int(np.prod(list(mesh.shape.values())))


def use_shard_map(mesh, policy: str) -> bool:
    """THE auto|vmap|shard_map decision, shared by the client engine and
    the KD pipeline's teacher precompute so the two sharded paths can
    never drift: ``vmap`` never shards, ``shard_map`` (or the
    ``REPRO_FORCE_SHARD_MAP=1`` escape hatch) always does when a mesh
    exists, ``auto`` shards exactly when the mesh spans >1 device."""
    import os
    if policy == "vmap" or mesh is None:
        return False
    if policy == "shard_map" or os.environ.get("REPRO_FORCE_SHARD_MAP") == "1":
        return True
    return mesh_size(mesh) > 1
