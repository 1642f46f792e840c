"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — the ``pod`` axis is an outer data-parallel
axis whose collectives cross DCN, so the sharding rules place only the
gradient all-reduce (and nothing latency-sensitive) on it.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``xla_force_host_platform_device_count`` before any jax initialisation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Default (16,16) / (2,16,16); ``shape`` overrides the (data, model)
    factorisation (e.g. (32, 8)) keeping the chip counts — a perf-iteration
    knob (TP degree trades activation-collective traffic for FSDP traffic)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    else:
        shape = tuple(shape)
        if multi_pod and len(shape) == 2:
            shape = (2, *shape)
    n = 1
    for d in shape:
        n *= d
    assert n in (256, 512), f"production pod sizes are 256/512 chips, got {n}"
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host offers (tests / examples): (data, model)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh(
        (n // model, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
