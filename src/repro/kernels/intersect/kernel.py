"""Pallas TPU kernel: sorted-list membership (conjunctive AND core).

TPU adaptation of the paper's ``seek_GEQ`` conjunctive evaluation (§3.6):
instead of a pointer-chasing cursor, both docid lists are tiled, and the
(a-tile × b-tile) grid skips any pair whose docid ranges are disjoint — the
direct analogue of "touching only the b-gap and n_ptr during the scan":
a skipped tile is a block whose postings are never decoded or compared.

For overlapping tile pairs the membership test is a dense broadcast compare
(VPU), i.e. the same work a SIMD galloping intersection does per segment.

Inputs are int32 docid vectors sorted ascending, padded with INT_MAX.
Output: for every element of ``a``, whether it occurs in ``b``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PAD = jnp.iinfo(jnp.int32).max
DEFAULT_TILE = 512


def _intersect_tile(a_ref, b_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]  # (TA,)
    b = b_ref[...]  # (TB,)
    # range-disjointness skip (the seek_GEQ block bypass): tiles are sorted,
    # so if max(a) < min(b) or min(a) > max(b) nothing can match.
    overlap = (a[-1] >= b[0]) & (a[0] <= b[-1]) & (a[0] != PAD)

    @pl.when(overlap)
    def _work():
        hit = (a[:, None] == b[None, :]).any(axis=1)
        o_ref[...] = o_ref[...] | hit


def intersect_kernel(a: jnp.ndarray, b: jnp.ndarray,
                     tile_a: int = DEFAULT_TILE, tile_b: int = DEFAULT_TILE,
                     *, interpret: bool) -> jnp.ndarray:
    """flags[i] = a[i] ∈ b, for sorted, PAD-padded int32 vectors."""
    na, nb = a.shape[0], b.shape[0]
    pa = (-na) % tile_a
    pb = (-nb) % tile_b
    a = jnp.pad(a, (0, pa), constant_values=PAD)
    b = jnp.pad(b, (0, pb), constant_values=PAD)
    grid = (a.shape[0] // tile_a, b.shape[0] // tile_b)
    out = pl.pallas_call(
        _intersect_tile,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_a,), lambda i, j: (i,)),
            pl.BlockSpec((tile_b,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((tile_a,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0],), jnp.bool_),
        interpret=interpret,
    )(a, b)
    return out[:na]
