"""Production meshes.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked at first backend init).
Axes are Auto-typed: the sharding rules in :mod:`repro.distributed`
place arrays through ``NamedSharding``/``shard_map``, not explicit-axis
types.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, dp: int = 16,
                         tp: int = 16):
    """Single pod: (data=dp, model=tp), dp·tp = 256 chips (default 16×16).
    Multi-pod:  (pod=2, data=dp, model=tp) = 512 chips (the 'pod' axis
    crosses the DCN boundary; DP spans pod×data).

    dp/tp re-balance is a per-arch §Perf knob: small-d models pay
    activation-reduction bytes ∝ per-device batch, so TP=4/DP=64 quarters
    the dense <8B models' collective term."""
    assert dp * tp == 256, (dp, tp)
    shape = (2, dp, tp) if multi_pod else (dp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many devices the host exposes."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
