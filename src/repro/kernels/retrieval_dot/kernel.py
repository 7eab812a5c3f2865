"""Pallas TPU kernel: two-tower candidate scoring (retrieval_cand shape).

Scores a batch of query embeddings against a large candidate table:
``scores = Q @ C^T`` with Q (q, d) and C (n, d), n up to 10^6.  This is the
MXU-native realization of the recsys ``retrieval_cand`` cell — a straight
tiled matmul with f32 accumulation over the contraction dimension, VMEM
blocks sized to the 128-lane MXU.

Grid: (q_tiles, n_tiles, d_tiles); the d dimension accumulates in-place in
the output block (revisited across the innermost grid axis).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_Q = 128
TILE_N = 512
TILE_D = 128


def _dot_tile(q_ref, c_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)   # (TQ, TD)
    c = c_ref[...].astype(jnp.float32)   # (TN, TD)
    o_ref[...] += jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def retrieval_dot_kernel(q: jnp.ndarray, cand: jnp.ndarray,
                         tile_q: int = TILE_Q, tile_n: int = TILE_N,
                         tile_d: int = TILE_D,
                         *, interpret: bool) -> jnp.ndarray:
    """scores (q, n) = q @ cand^T, tiled for VMEM/MXU."""
    Q, D = q.shape
    N, D2 = cand.shape
    assert D == D2
    pq, pn, pd = (-Q) % tile_q, (-N) % tile_n, (-D) % tile_d
    q = jnp.pad(q, ((0, pq), (0, pd)))
    cand = jnp.pad(cand, ((0, pn), (0, pd)))
    grid = (q.shape[0] // tile_q, cand.shape[0] // tile_n,
            q.shape[1] // tile_d)
    out = pl.pallas_call(
        _dot_tile,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, tile_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((tile_n, tile_d), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], cand.shape[0]),
                                       jnp.float32),
        interpret=interpret,
    )(q, cand)
    return out[:Q, :N]
