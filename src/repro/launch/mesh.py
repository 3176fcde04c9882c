"""Production mesh construction.

Importing this module never touches jax device state; meshes are built by
FUNCTIONS so the dry-run controls XLA_FLAGS before first jax init.  Every
axis is ``AxisType.Auto``: GSPMD propagates shardings, and the serving
path goes manual only inside its own ``jax.shard_map``.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e pod slice); 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale integration tests (needs forced host devices
    >= prod(shape))."""
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh (plain CPU runs)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_tp_mesh(n_shards: int):
    """1-D ("model",) mesh for tensor-parallel serving (DESIGN.md §10).

    Uses the first ``n_shards`` visible devices.  On CPU, force host
    devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (must be set before jax initializes its backend).
    """
    n_dev = jax.device_count()
    if n_dev < n_shards:
        raise ValueError(
            f"make_tp_mesh({n_shards}) needs {n_shards} devices, have "
            f"{n_dev}; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before the "
            "first jax call")
    return _make_mesh((n_shards,), ("model",))


def make_serving_mesh(dp: int, tp: int):
    """("data", "model") mesh for sharded serving: batch rows over ``dp``
    data shards, packed weight planes over ``tp`` model shards
    (DESIGN.md §10).  Needs ``dp * tp`` visible devices (on CPU force
    host devices first — see ``make_tp_mesh``)."""
    need = dp * tp
    n_dev = jax.device_count()
    if n_dev < need:
        raise ValueError(
            f"make_serving_mesh(dp={dp}, tp={tp}) needs {need} devices, "
            f"have {n_dev}; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before the "
            "first jax call")
    return _make_mesh((dp, tp), ("data", "model"))
