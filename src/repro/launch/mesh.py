"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: (16, 16) = 256 chips, axes
("data", "model").  Multi-pod: (2, 16, 16) = 512 chips with a leading "pod"
axis whose collectives cross the inter-pod links (DCN/ICI-optical); the
gradient all-reduce and the index result fusion are the only ops that
traverse it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
