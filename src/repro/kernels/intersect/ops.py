"""Jitted public entry point for the intersection kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import registry
from .kernel import DEFAULT_TILE, intersect_kernel


@partial(jax.jit, static_argnames=("tile_a", "tile_b", "interpret"))
def intersect_sorted(a, b, tile_a: int = DEFAULT_TILE,
                     tile_b: int = DEFAULT_TILE,
                     interpret: bool | None = None):
    """Membership flags of sorted int32 list ``a`` in sorted list ``b``.
    ``interpret=None`` interprets the kernel everywhere but on a TPU."""
    if interpret is None:
        interpret = registry.default_interpret()
    return intersect_kernel(a, b, tile_a=tile_a, tile_b=tile_b,
                            interpret=interpret)


registry.register(registry.KernelSpec(
    name="intersect", fn=intersect_sorted, modes=("conjunctive",),
    description="tiled sorted-list membership with range-disjoint tile skip "
                "(the seek_GEQ block bypass on TPU)",
    extras={"pad": int(jax.numpy.iinfo(jax.numpy.int32).max)}))
