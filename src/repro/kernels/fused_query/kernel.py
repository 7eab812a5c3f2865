"""Pallas kernel: fused decode→score→top-k over a launch's flat pools.

One ``pallas_call`` serves a whole query batch in one invocation over the
whole of each image's flat block pool: it runs the complete pipeline of
:func:`..fused_query.ref.fused_tile` — chain-block decode, docid
reconstruction, dense weight accumulation over the docid capacity, and
top-k selection (or conjunctive bitmap matching) — without materializing
any intermediate back to HBM.  A flat pool packs the queries' blocks
back to back, so there is no per-query tile to grid over.

The kernel body *is* the reference implementation: it loads the refs and
calls ``ref.fused_tile`` verbatim, so the Pallas flavour is byte-identical
to the reference flavour by construction (asserted by the differential
tests).  Everything inside is log-step vector ops plus one scatter-add —
no scans, no dynamic shapes.

Each resident image arrives as its own *part* (eight arrays, flattened
into the positional ref list) so the frozen and delta pools keep their own
sizes.  ``doclens`` (a full docid-capacity lookup table), the two BM25
normalization scalars and the optional liveness mask follow them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import fused_tile

PART_ARRAYS = 8  # arrays per image part (see ref.fused_tile)


def _tile_kernel(*refs, n_parts: int, mode: str, k: int, F: int, cap: int,
                 has_alive: bool = False):
    n_in = PART_ARRAYS * n_parts + 3 + (1 if has_alive else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    parts = tuple(tuple(r[...] for r in
                        ins[PART_ARRAYS * i:PART_ARRAYS * (i + 1)])
                  for i in range(n_parts))
    tail = [r[...] for r in ins[PART_ARRAYS * n_parts:]]
    nterms, doclens, norm = tail[0], tail[1], tail[2]
    alive = tail[3] if has_alive else None
    out = fused_tile(parts, nterms, doclens, norm,
                     mode=mode, k=k, F=F, cap=cap, alive=alive)
    if mode == "conjunctive":
        outs[0][...] = out
    else:
        outs[0][...], outs[1][...] = out


def fused_query_kernel(parts, nterms, doclens, bm25_norm, *, mode: str,
                       k: int, F: int, cap: int, interpret: bool,
                       alive=None):
    """Launch the fused kernel over per-image flat pools.

    ``parts`` is a tuple of (gat, start, end, seg, lastd0, dnum0, widf,
    qix) per image, each gat shaped (NS_i, B) with its own pool size;
    ``nterms`` is (Qn,).  ``alive`` is the optional packed liveness mask.
    Returns what :func:`ref.fused_tile` returns.
    """
    Qn = nterms.shape[0]
    args = tuple(a for part in parts for a in part) + (nterms, doclens,
                                                       bm25_norm)
    if alive is not None:
        args = args + (alive,)
    kern = functools.partial(_tile_kernel, n_parts=len(parts), mode=mode,
                             k=k, F=F, cap=cap, has_alive=alive is not None)
    if mode == "conjunctive":
        out_shape = jax.ShapeDtypeStruct((Qn, cap + 1), jnp.bool_)
    else:
        kk = min(k, cap + 1)
        out_shape = (jax.ShapeDtypeStruct((Qn, kk), jnp.int32),
                     jax.ShapeDtypeStruct((Qn, kk), jnp.float32))
    return pl.pallas_call(kern, out_shape=out_shape,
                          interpret=interpret)(*args)
