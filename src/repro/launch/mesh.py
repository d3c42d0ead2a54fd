"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization, while smoke tests must see the
default single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the sharding rules in repro.distributed.sharding are written
    # for GSPMD propagation. jax.make_mesh defaults to Explicit axes, under
    # which a contraction over a sharded dim (attention's wo) is a type error.
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate mesh over whatever devices exist (smoke / examples)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
