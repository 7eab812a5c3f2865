"""Jitted public entry point for the fused decode→score→top-k kernel.

:func:`fused_query` answers a whole query batch against one or more
device-resident images (typically the frozen :class:`DeviceIndex` plus the
post-freeze :class:`DeltaIndex`) in a single launch per (mode, k) group:

  1. *prep/gather* (XLA): per image, every query's live terms' chain
     blocks are packed term-major into a (Q, PB_i, B) *part* whose slots
     carry the owning term's segment id, docid-chaining bases and idf
     weight — the uniform slot layout lets frozen and delta chains run
     through identical segmented arithmetic (see ``ref.fused_tile``).
     Each image keeps its OWN packed capacity PB_i (``max_blocks`` is a
     per-image tuple, sized by the caller to the batch's longest per-query
     block total): the delta suffix is typically a handful of blocks, and
     packing means nobody pays for the vocabulary's longest chain;
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
from .kernel import DEFAULT_TQ, fused_query_kernel
from .ref import BM25_B, BM25_K1, fused_tile

#: Modes the fused kernel serves (positional modes need word positions,
#: which device images do not model).
FUSED_MODES = ("conjunctive", "ranked_tfidf", "bm25")


def _prep_image(image, qterms, qmask, Ns, max_blocks: int, mode: str):
    """Pack one image's chain blocks: (Q, PB, B) slots + per-slot metadata.

    Each query's live terms' actual chain blocks are packed term-major into
    PB = ``max_blocks`` slots (the caller sizes PB to the batch's longest
    per-query block total, NOT to T × the longest chain in the vocabulary —
    a ~4–8× decode saving at bench scale).  Every slot carries its owning
    term's segment id, chaining bases and idf weight, so the tile can run
    segmented scans along the slot axis.
    """
    Q, T = qterms.shape
    PB = max_blocks
    B = image.blocks.shape[1]
    flat = qterms.reshape(-1)
    slot = image.term_slot[flat].reshape(Q, T)
    nblk = jnp.where(qmask, image.term_nblk[flat].reshape(Q, T), 0)
    skip = image.term_skip[flat].reshape(Q, T)
    nx = image.term_nx[flat].reshape(Q, T)
    # term-major packing: slot s of query q belongs to the last term whose
    # exclusive block-offset is <= s (empty terms yield no slots)
    off = jnp.cumsum(nblk, axis=1) - nblk              # exclusive prefix
    total = off[:, -1] + nblk[:, -1]
    s = jnp.arange(PB, dtype=jnp.int32)[None, :]
    t_of = (s[:, :, None] >= off[:, None, :]).sum(axis=2) - 1  # (Q, PB)
    within = s - jnp.take_along_axis(off, t_of, axis=1)
    valid = s < total[:, None]
    slot_s = jnp.take_along_axis(slot, t_of, axis=1)
    nblk_s = jnp.take_along_axis(nblk, t_of, axis=1)
    bidx = jnp.where(valid, slot_s + within, 0)
    gat = image.blocks[bidx.reshape(-1)].reshape(Q, PB, B)
    is_head = within == 0
    is_tail = within == nblk_s - 1
    start = jnp.where(is_head, jnp.take_along_axis(skip, t_of, axis=1), H)
    end = jnp.where(is_tail, jnp.take_along_axis(nx, t_of, axis=1), B)
    end = jnp.where(valid, end, 0)
    seg = jnp.where(valid, t_of, T)                    # pad slots: own seg
    if isinstance(image, DeltaIndex):
        lastd0 = image.term_lastd0[flat].reshape(Q, T)
        dnum0 = image.term_dnum0[flat].reshape(Q, T)
        lastd0_s = jnp.take_along_axis(lastd0, t_of, axis=1)
        dnum0_s = jnp.take_along_axis(dnum0, t_of, axis=1)
    else:
        # frozen segments: absolute chains — the -1 sentinel makes the tile
        # use the head block's own first gap as the b-gap base (pure cumsum)
        lastd0_s = jnp.zeros((Q, PB), jnp.int32)
        dnum0_s = jnp.full((Q, PB), -1, jnp.int32)
    if mode == "conjunctive":
        widf_s = jnp.zeros((Q, PB), jnp.float32)
    else:
        ft = jnp.maximum(image.term_ft[flat], 1).astype(jnp.float32)
        if mode == "bm25":
            widf = precise_log1p((Ns - ft + 0.5) / (ft + 0.5))
        else:
            widf = precise_log1p(Ns / ft)
        widf = (widf * qmask.reshape(-1)).reshape(Q, T)
        widf_s = jnp.where(valid, jnp.take_along_axis(widf, t_of, axis=1),
                           0.0)
    return (gat, start, end, seg, lastd0_s, dnum0_s, widf_s)


@partial(jax.jit, static_argnames=("mode", "k", "max_blocks", "flavor",
                                   "interpret", "tq"))
def fused_query(images, qterms, qmask, *, mode: str = "ranked_tfidf",
                k: int = 10, max_blocks: int | tuple = 64,
                doclens: jnp.ndarray | None = None,
                n_stat: jnp.ndarray | None = None,
                avg_stat: jnp.ndarray | None = None,
                alive: jnp.ndarray | None = None,
                flavor: str = "ref", interpret: bool | None = None,
                tq: int = DEFAULT_TQ):
    """One fused launch answering ``qterms``/``qmask`` against ``images``.

    Args:
      images: tuple of :class:`DeviceIndex`/:class:`DeltaIndex` sharing one
        docid capacity (``num_docs``) and vocab padding — the engine's
        resident (frozen, delta) pair.
      qterms: (Q, T) i32 padded term ids; qmask: (Q, T) bool.
      mode: one of :data:`FUSED_MODES`.
      max_blocks: per-image PACKED block capacity (slots per query, not
        per term) — a tuple aligned with ``images`` (an int is broadcast
        to every image); must cover the batch's largest per-query total
        block count in that image.
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
                                  F=F, cap=cap, tq=tq, interpret=interpret,
                                  alive=alive_f)
    return fused_tile(parts, nterms, dl, norm, mode=mode, k=k, F=F, cap=cap,
                      alive=alive_f)


registry.register(registry.KernelSpec(
    name="fused_query", fn=fused_query, modes=FUSED_MODES,
    description="single-launch decode→score→top-k over resident "
                "frozen+delta images, query-major grid",
    extras={"fused_modes": FUSED_MODES}))
