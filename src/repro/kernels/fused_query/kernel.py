"""Pallas kernel: fused decode→score→top-k over a query-major grid.

One ``pallas_call`` serves a whole query batch: the grid walks the batch
``tq`` queries per step, and each step runs the complete pipeline of
:func:`..fused_query.ref.fused_tile` — chain-block decode, docid
reconstruction, dense weight accumulation over the docid capacity, and
top-k selection (or conjunctive bitmap matching) — without materializing
any intermediate back to HBM.  This replaces the previous four-op chain
(``dvbyte_decode`` → ``intersect``/``retrieval_dot`` → ``topk_score``),
whose per-op round trips dominated the device path's latency.

The kernel body *is* the reference implementation: it loads the tile's
refs and calls ``ref.fused_tile`` verbatim, so the Pallas flavour is
byte-identical to the reference flavour by construction (asserted by the
differential tests).  Everything inside is log-step vector ops plus one
per-query scatter-add — no scans, no dynamic shapes — which maps onto the
VPU and, in interpret mode, onto XLA:CPU's vector units.

Each resident image arrives as its own *part* (seven arrays, flattened
into the positional ref list) so the frozen and delta tiles keep their own
packed block capacities — the grid still tiles all of them by the same
``tq`` query rows per step.  ``doclens`` (a full docid-capacity lookup
table) and the two BM25 normalization scalars are broadcast to every step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import fused_tile

DEFAULT_TQ = 8  # queries per grid step


def _tile_kernel(*refs, n_parts: int, mode: str, k: int, F: int, cap: int,
                 has_alive: bool = False):
    n_in = 7 * n_parts + 3 + (1 if has_alive else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    parts = tuple(tuple(r[...] for r in ins[7 * i:7 * i + 7])
                  for i in range(n_parts))
    tail = [r[...] for r in ins[7 * n_parts:]]
    nterms, doclens, norm = tail[0], tail[1], tail[2]
    alive = tail[3] if has_alive else None
    out = fused_tile(parts, nterms, doclens, norm,
                     mode=mode, k=k, F=F, cap=cap, alive=alive)
    if mode == "conjunctive":
        outs[0][...] = out
    else:
        outs[0][...], outs[1][...] = out


def _pad_q(a: jnp.ndarray, pad: int) -> jnp.ndarray:
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def fused_query_kernel(parts, nterms, doclens, bm25_norm, *, mode: str,
                       k: int, F: int, cap: int, tq: int = DEFAULT_TQ,
                       interpret: bool, alive=None):
    """Launch the fused kernel over per-image packed part tuples.

    ``parts`` is a tuple of (gat, start, end, seg, lastd0, dnum0, widf)
    per image, each gat shaped (Q, PB_i, B) with its own packed block
    capacity.  Q is padded up to a multiple of ``tq`` (padded rows have
    ``end == 0`` everywhere, so they decode to nothing).  ``alive`` is the
    optional (cap+1,) liveness mask, broadcast to every grid step like the
    doclens table.  Returns what :func:`ref.fused_tile` returns, sliced
    back to Q rows.
    """
    Q = parts[0][0].shape[0]
    tq = min(tq, Q)
    pad = (tq - Q % tq) % tq
    if pad:
        parts = tuple(tuple(_pad_q(a, pad) for a in part) for part in parts)
        nterms = _pad_q(nterms, pad)
    Qp = Q + pad
    grid = (Qp // tq,)
    in_specs = []
    for part in parts:
        _, PB, B = part[0].shape
        in_specs += [pl.BlockSpec((tq, PB, B), lambda i: (i, 0, 0))]
        in_specs += [pl.BlockSpec((tq, PB), lambda i: (i, 0))] * 6
    DL = doclens.shape[0]
    in_specs += [
        pl.BlockSpec((tq,), lambda i: (i,)),
        pl.BlockSpec((DL,), lambda i: (0,)),      # broadcast lookup table
        pl.BlockSpec((2,), lambda i: (0,)),       # broadcast bm25 norms
    ]
    args = tuple(a for part in parts for a in part) + (nterms, doclens,
                                                       bm25_norm)
    if alive is not None:
        in_specs += [pl.BlockSpec((alive.shape[0],), lambda i: (0,))]
        args = args + (alive,)                    # broadcast liveness mask
    kern = functools.partial(_tile_kernel, n_parts=len(parts), mode=mode,
                             k=k, F=F, cap=cap, has_alive=alive is not None)
    if mode == "conjunctive":
        matches = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((tq, cap + 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Qp, cap + 1), jnp.bool_),
            interpret=interpret,
        )(*args)
        return matches[:Q]
    kk = min(k, cap + 1)
    top_d, top_s = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((tq, kk), lambda i: (i, 0)),
                   pl.BlockSpec((tq, kk), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Qp, kk), jnp.int32),
                   jax.ShapeDtypeStruct((Qp, kk), jnp.float32)],
        interpret=interpret,
    )(*args)
    return top_d[:Q], top_s[:Q]
