"""Production mesh construction (function, not module-level constant — so
importing this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pods: int | None = None):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pods:
        return jax.make_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def single_device_mesh():
    """1x1 mesh — lets every PartitionSpec validate without extra devices."""
    return jax.make_mesh((1, 1), ("data", "model"))
