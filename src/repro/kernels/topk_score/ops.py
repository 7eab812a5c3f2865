"""Jitted public entry point for the score-accumulation kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import registry
from .kernel import DEFAULT_TILE_M, DEFAULT_TILE_N, score_kernel


@partial(jax.jit, static_argnames=("n_docs", "tile_m", "tile_n", "interpret"))
def score_accumulate(docids, weights, n_docs: int,
                     tile_m: int = DEFAULT_TILE_M,
                     tile_n: int = DEFAULT_TILE_N,
                     interpret: bool | None = None):
    """Dense TF×IDF score vector from decoded postings (docid 0 = padding).
    ``interpret=None`` interprets the kernel everywhere but on a TPU."""
    if interpret is None:
        interpret = registry.default_interpret()
    return score_kernel(docids, weights, n_docs, tile_m=tile_m,
                        tile_n=tile_n, interpret=interpret)


registry.register(registry.KernelSpec(
    name="topk_score", fn=score_accumulate,
    modes=("ranked_tfidf", "bm25"),
    description="masked-matmul scatter-add of posting weights into the dense "
                "docid score vector (MXU-shaped accumulation)"))
