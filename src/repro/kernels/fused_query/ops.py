"""Jitted public entry point for the fused decode→score→top-k kernel.

:func:`fused_query` answers a whole query batch against one or more
device-resident images (typically the frozen :class:`DeviceIndex` plus the
post-freeze :class:`DeltaIndex`) in a single launch per (mode, k) group:

  1. *prep/gather* (XLA): per image, the chain blocks of every live
     query's terms are packed query-major, then term-major, into one flat
     (NS_i, B) *pool* whose slots carry their (query, term) segment id,
     query row, docid-chaining bases and idf weight — the uniform slot
     layout lets frozen and delta chains run through identical segmented
     arithmetic (see ``ref.fused_tile``).  Each image keeps its OWN pool
     size NS_i (``max_blocks`` is a per-image tuple, sized by the caller to
     pow2 of the blocks the launch's queries own there): the delta suffix
     is typically a handful of blocks, and no query pays for another's
     longer chains;
  2. *fused compute*: decode → docids → score → top-k in one kernel
     (``flavor="pallas"``) or as the same math inline (``flavor="ref"``,
     the oracle the kernel is byte-compared against).

Both flavours are jitted end-to-end; shapes are bucketed by the caller
(vocab/doc/block capacities round to powers of two), so steady-state
serving reuses compiled programs across refreshes.

Merging images inside the launch is exact: frozen and delta docid spaces
are disjoint (docids are ordinal; docs ≤ freeze-N live wholly in the
frozen image) and both sides weight postings with the same global f_t.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...core.blockstore import H
from ...core.device_index import DeltaIndex, precise_log1p
from .. import registry
from .kernel import fused_query_kernel
from .ref import BM25_B, BM25_K1, fused_tile

#: Modes the fused kernel serves (positional modes need word positions,
#: which device images do not model).
FUSED_MODES = ("conjunctive", "ranked_tfidf", "bm25")


def _prep_image(image, qterms, qmask, Ns, max_blocks: int, mode: str):
    """Pack one image's chain blocks into a flat (NS, B) pool + per-slot
    metadata.

    The chain blocks of every (query, term) pair — query-major, then
    term-major — fill the first Σ nblk of NS = ``max_blocks`` slots (the
    caller sizes NS to pow2 of that sum).  Every slot carries its pair's
    segment id (unique per pair, so the tile's segmented scans chain
    docids along the one slot axis), query row, chaining bases and idf
    weight; the slots past the sum are pad slots that decode to nothing.
    """
    Q, T = qterms.shape
    NS = max_blocks
    B = image.blocks.shape[1]
    flat = qterms.reshape(-1)
    nblk = jnp.where(qmask.reshape(-1), image.term_nblk[flat], 0)  # (Q*T,)
    ends = jnp.cumsum(nblk)                            # inclusive offsets
    s = jnp.arange(NS, dtype=jnp.int32)
    # slot s belongs to the first pair whose chain ends past s (empty
    # pairs own no slot); pad slots clip to the last pair
    pair = jnp.minimum(jnp.searchsorted(ends, s, side="right"),
                       Q * T - 1).astype(jnp.int32)
    valid = s < ends[-1]
    within = s - (ends - nblk)[pair]
    bidx = jnp.where(valid, image.term_slot[flat][pair] + within, 0)
    gat = image.blocks[bidx]
    is_head = within == 0
    is_tail = within == nblk[pair] - 1
    start = jnp.where(is_head, image.term_skip[flat][pair], H)
    end = jnp.where(is_tail, image.term_nx[flat][pair], B)
    end = jnp.where(valid, end, 0)
    seg = jnp.where(valid, pair, Q * T)                # pad slots: own seg
    qix = jnp.where(valid, pair // T, 0)
    if isinstance(image, DeltaIndex):
        lastd0_s = image.term_lastd0[flat][pair]
        dnum0_s = image.term_dnum0[flat][pair]
    else:
        # frozen segments: absolute chains — the -1 sentinel makes the tile
        # use the head block's own first gap as the b-gap base (pure cumsum)
        lastd0_s = jnp.zeros(NS, jnp.int32)
        dnum0_s = jnp.full(NS, -1, jnp.int32)
    if mode == "conjunctive":
        widf_s = jnp.zeros(NS, jnp.float32)
    else:
        ft = jnp.maximum(image.term_ft[flat], 1).astype(jnp.float32)
        if mode == "bm25":
            widf = precise_log1p((Ns - ft + 0.5) / (ft + 0.5))
        else:
            widf = precise_log1p(Ns / ft)
        widf_s = jnp.where(valid, widf[pair], 0.0)
    return (gat, start, end, seg, lastd0_s, dnum0_s, widf_s, qix)


@partial(jax.jit, static_argnames=("mode", "k", "max_blocks", "flavor",
                                   "interpret"))
def fused_query(images, qterms, qmask, *, mode: str = "ranked_tfidf",
                k: int = 10, max_blocks: int | tuple = 64,
                doclens: jnp.ndarray | None = None,
                n_stat: jnp.ndarray | None = None,
                avg_stat: jnp.ndarray | None = None,
                alive: jnp.ndarray | None = None,
                flavor: str = "ref", interpret: bool | None = None):
    """One fused launch answering ``qterms``/``qmask`` against ``images``.

    Args:
      images: tuple of :class:`DeviceIndex`/:class:`DeltaIndex` sharing one
        docid capacity (``num_docs``) and vocab padding — the engine's
        resident (frozen, delta) pair.
      qterms: (Q, T) i32 padded term ids; qmask: (Q, T) bool.
      mode: one of :data:`FUSED_MODES`.
      max_blocks: per-image pool size (slots for the whole launch) — a
        tuple aligned with ``images`` (an int is broadcast to every
        image); must cover the chain blocks that all of the batch's
        queries own in that image together.
      doclens: (cap+1,) f32 document lengths (bm25 only).
      n_stat / avg_stat: dynamic collection statistics (fleet-exact idf /
        avgdl); default to the image capacity / local doclens mean.
      alive: optional (ceil((cap+1)/32),) uint32 packed little-endian
        liveness bitmask (bit ``d`` clear at tombstoned docids and index
        0) — None skips masking entirely, keeping the no-delete path
        byte-identical to its pre-deletion compilation.
      flavor: "pallas" (the kernel) or "ref" (same math inline).
      interpret: Pallas interpret mode for ``flavor="pallas"``; None
        interprets everywhere but on a TPU.  The "ref" flavour ignores it.

    Returns ``matches (Q, cap+1) bool`` for conjunctive, else
    ``(top_d (Q, kk) i32, top_s (Q, kk) f32)`` in canonical order
    (descending score, ties by ascending docid).
    """
    if mode not in FUSED_MODES:
        raise ValueError(f"unsupported fused mode {mode!r}")
    head = images[0]
    cap = head.num_docs
    F = head.F
    if isinstance(max_blocks, int):
        max_blocks = (max_blocks,) * len(images)
    Ns = (jnp.float32(cap) if n_stat is None
          else n_stat.astype(jnp.float32))
    with jax.named_scope("gather"):
        parts = tuple(_prep_image(img, qterms, qmask, Ns, mb, mode)
                      for img, mb in zip(images, max_blocks))
    nterms = qmask.sum(axis=1).astype(jnp.int32)
    if mode == "bm25":
        avgdl = (jnp.maximum(doclens[1:].sum() / Ns, 1e-9)
                 if avg_stat is None
                 else jnp.maximum(avg_stat.astype(jnp.float32), 1e-9))
        norm = jnp.stack([jnp.float32(BM25_K1 * (1.0 - BM25_B)),
                          BM25_K1 * BM25_B / avgdl])
        dl = doclens.astype(jnp.float32)
    else:
        norm = jnp.zeros(2, jnp.float32)
        dl = jnp.zeros(1, jnp.float32)
    alive_f = None if alive is None else alive.astype(jnp.uint32)
    if flavor == "pallas":
        if interpret is None:
            interpret = registry.default_interpret()
        return fused_query_kernel(parts, nterms, dl, norm, mode=mode, k=k,
                                  F=F, cap=cap, interpret=interpret,
                                  alive=alive_f)
    return fused_tile(parts, nterms, dl, norm, mode=mode, k=k, F=F, cap=cap,
                      alive=alive_f)


registry.register(registry.KernelSpec(
    name="fused_query", fn=fused_query, modes=FUSED_MODES,
    description="single-launch decode→score→top-k over resident "
                "frozen+delta images, one flat block pool per image",
    extras={"fused_modes": FUSED_MODES}))
