"""Jitted public entry point for the retrieval-dot kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import registry
from .kernel import TILE_D, TILE_N, TILE_Q, retrieval_dot_kernel


@partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_d", "interpret"))
def candidate_scores(q, cand, tile_q: int = TILE_Q, tile_n: int = TILE_N,
                     tile_d: int = TILE_D, interpret: bool | None = None):
    """Two-tower scores (q, n) = q @ cand^T (f32 accumulation).
    ``interpret=None`` interprets the kernel everywhere but on a TPU."""
    if interpret is None:
        interpret = registry.default_interpret()
    return retrieval_dot_kernel(q, cand, tile_q=tile_q, tile_n=tile_n,
                                tile_d=tile_d, interpret=interpret)


registry.register(registry.KernelSpec(
    name="retrieval_dot", fn=candidate_scores, modes=(),
    description="dense two-tower candidate scoring; outside the term-query "
                "path (hybrid reranking hook)"))
