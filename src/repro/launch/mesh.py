"""Production meshes.

Functions, not module constants — importing this module never touches jax
device state (jax locks the device count on first backend init, and only
launch/dryrun.py is allowed to force 512 host devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _serving_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The partition plan places arrays
    with ``device_put`` and pins intermediates with
    ``with_sharding_constraint``, which is the Auto-axis contract; JAX's
    default axis type is Explicit, under which those constraints are
    refused."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _serving_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (reduced configs)."""
    return _serving_mesh((1, 1), ("data", "model"))


def make_test_mesh(data: int = 1, model: int = 1):
    """Small explicit (data, model) mesh for tests and benchmarks.

    Runs on whatever devices exist; on a CPU-only box force a multi-device
    host platform FIRST (before any jax import touches the backend):

        XLA_FLAGS=--xla_force_host_platform_device_count=4

    — the recipe the shard-invariance suite and ``benchmarks/
    sharded_bench.py`` use (README §serving).
    """
    need = data * model
    have = jax.device_count()
    if have < need:
        raise RuntimeError(
            f"mesh {data}x{model} needs {need} devices but only {have} "
            f"exist; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} before starting the process")
    return _serving_mesh((data, model), ("data", "model"))
