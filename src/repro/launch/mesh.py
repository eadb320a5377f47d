"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 CPU device; only
dryrun.py forces 512 host devices via XLA_FLAGS before any jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which refuses Explicit axes (the
    default axis type of ``jax.make_mesh``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over whatever devices exist (CPU tests, smoke runs)."""
    n = len(jax.devices())
    model = model or 1
    return make_mesh((n // model, model), ("data", "model"))
