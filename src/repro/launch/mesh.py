"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — the ``pod`` axis is the paper's second-layer
interconnect (DESIGN.md §6).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.
"""

from __future__ import annotations

import jax

from repro.parallel.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2,
                    pod: int | None = None) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    ≥ data·model·pod)."""
    if pod:
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))
