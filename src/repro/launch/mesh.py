"""Production mesh construction (dry-run target: TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py forces
512 host devices via XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    hints of this repo leave layout to the partitioner, which the default
    ``Explicit`` axes of the installed JAX refuse (e.g. a gather from a
    sharded embedding table raises ``ShardingTypeError``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model single pod; (2,16,16) pod x data x model for the
    2-pod = 512-chip configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has, as a 1-D data mesh (real training on
    this container: 1 CPU device)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small fake mesh for subprocess-based distribution tests."""
    return _auto_mesh(shape, axes)


def make_serving_mesh(tp: int = 1):
    """Mesh for one tensor-parallel serving replica: ("data", "model") =
    (n_devices // tp, tp). The serving path shards attention heads and the
    paged KV pool over "model" only (parallel.sharding.paged_pool_shardings);
    "data" stays size n//tp so the same plan_for_mesh rules apply. CI runs
    this on virtual host devices via XLA_FLAGS=--xla_force_host_platform_
    device_count — on the real pod, tp divides the chips of one replica."""
    n = len(jax.devices())
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if n % tp != 0:
        raise ValueError(f"tp={tp} does not divide {n} visible devices")
    return _auto_mesh((n // tp, tp), ("data", "model"))
