"""Production meshes.

Functions (not module constants) so importing never touches jax device
state.  The dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import; everything else sees the real device count.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis explicitly ``Auto``: the compiler
    propagates shardings, as the specs in this repo assume."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """A mesh over whatever devices exist (CPU tests: usually 1)."""
    n = jax.device_count()
    model = max(1, min(model, n))
    while n % model != 0:
        model -= 1
    return make_mesh((n // model, model), ("data", "model"))


def make_sweep_mesh(max_devices: int | None = None):
    """1-D mesh over all (or the first ``max_devices``) devices for
    sharding a DSE sweep's config axis — :func:`repro.core.dse_batch
    .sweep_workload` / :func:`~repro.core.dse_batch.sweep_mixed_many`
    with ``backend="jax"`` and ``mesh=...``."""
    n = jax.device_count()
    if max_devices is not None:
        n = max(1, min(n, int(max_devices)))
    return make_mesh((n,), ("configs",))


def mesh_shards(mesh) -> int:
    """Number of config-axis shards a ``mesh=`` argument implies:
    ``None`` -> 1, a plain int (the numpy backend's simulated shard
    count) -> itself, a ``jax.sharding.Mesh`` -> its device count.
    Delegates to the sweep engine's helper so padding/splitting semantics
    have a single source of truth."""
    from repro.core.dse_batch import _mesh_shards
    return _mesh_shards(mesh)

