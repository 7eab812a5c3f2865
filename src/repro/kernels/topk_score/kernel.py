"""Pallas TPU kernel: postings score accumulation (TF×IDF scatter).

The disjunctive top-k path (paper §4.6) reduces to: given M decoded postings
(docid, weight), build the dense score vector over the docid space, then
top-k.  A CPU implementation scatter-adds through the heap; scatter is the
wrong shape for a systolic TPU, so we reformulate accumulation as a masked
matmul — for each docid-space tile T: scores[T] = w · (docids == iota(T)),
an (1×M_tile)·(M_tile×N_tile) MXU contraction per grid cell.  Postings whose
docid range misses the tile are skipped (same block-skip idea as intersect).

This trades FLOPs for perfect memory coalescing — the classic TPU bargain —
and is exactly how one-hot embedding updates are lowered on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_M = 1024
DEFAULT_TILE_N = 1024


def _score_tile(d_ref, w_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    i = pl.program_id(0)
    d = d_ref[...]          # (TM,) int32 docids (0 = padding)
    w = w_ref[...]          # (TM,) f32 weights
    n0 = i * o_ref.shape[0]
    # skip when this posting tile cannot touch this docid tile
    lo = n0
    hi = n0 + o_ref.shape[0]
    overlap = (jnp.max(d) >= lo) & (jnp.min(jnp.where(d > 0, d, 2**30)) < hi)

    @pl.when(overlap)
    def _work():
        n_iota = n0 + jax.lax.broadcasted_iota(jnp.int32, (o_ref.shape[0],), 0)
        onehot = (d[:, None] == n_iota[None, :]).astype(jnp.float32)
        o_ref[...] += w @ onehot  # (TM,) @ (TM, TN) -> (TN,)


def score_kernel(docids: jnp.ndarray, weights: jnp.ndarray, n_docs: int,
                 tile_m: int = DEFAULT_TILE_M, tile_n: int = DEFAULT_TILE_N,
                 *, interpret: bool) -> jnp.ndarray:
    """Dense scores over docid space [0, n_docs): scatter-add of weights."""
    M = docids.shape[0]
    pm = (-M) % tile_m
    docids = jnp.pad(docids, (0, pm))           # pad docid 0 = ignored
    weights = jnp.pad(weights, (0, pm))
    Np = n_docs + ((-n_docs) % tile_n)
    grid = (Np // tile_n, docids.shape[0] // tile_m)
    out = pl.pallas_call(
        _score_tile,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m,), lambda i, j: (j,)),
            pl.BlockSpec((tile_m,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((tile_n,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.float32),
        interpret=interpret,
    )(docids, weights)
    # docid 0 is the padding bucket: zero it before use
    out = out.at[0].set(0.0)
    return out[:n_docs]
