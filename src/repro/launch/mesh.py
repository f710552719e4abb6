"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS before first jax init, while tests/benches must see
the single real CPU device.

Every mesh is built with ``Auto`` axis types: under JAX >= 0.5
``jax.make_mesh`` defaults to ``Explicit`` axes, which put mesh axes into
array types — the engine's outputs then come back typed as sharded on
``data`` and fail outside the mesh (gathers, scatters, closures over
``shard_map`` inputs). The engine and models place data with explicit
``shard_map`` specs and sharding constraints, so ``Auto`` is what they
expect.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh over the real local device — used by smoke tests/examples
    so the same pjit code path runs on this CPU container."""
    return _mesh((1, 1), ("data", "model"))


def make_sim_mesh(data: int = 1, model: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default: the visible ones) —
    the chain runtime's mesh for the train driver, for subprocess SPMD
    tests (XLA_FLAGS-forced host devices) and for right-sized slices of a
    real cluster. data = chain groups, model = shard-parallel
    surrogate/gradient work (core/engine.py)."""
    return _mesh((data, model), ("data", "model"), devices)
