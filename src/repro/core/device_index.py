"""Device-resident immediate-access index: the TPU query path.

This is the hardware adaptation described in DESIGN.md §2.  The collated
index image (§5.5 makes every chain contiguous, which is precisely what lets
a TPU fetch a term's postings as one dense slice) is uploaded as flat arrays,
and querying becomes a fixed-shape, fully data-parallel program:

  1. *chain gather* — every query term's blocks are fetched in one gather of
     shape (Q*T*MB, B) from the block array (MB = max blocks per term);
  2. *parallel Double-VByte decode* — terminator flag bits -> per-byte code
     index via cumulative ops -> payload shift/combine; the escape-pairing
     automaton of Algorithm 2 runs as one short lax.scan across byte
     positions, vectorized over every block in flight;
  3. *docid reconstruction* — per-block prefix sums of d-gaps plus a
     cumulative sum of leading b-gaps along each chain (§3.2's skip data);
  4. *scoring* — TF×IDF scatter-add into a dense per-shard accumulator and
     top-k, or conjunctive counting (a docid matches iff its hit count equals
     the number of query terms).

Everything below is pure jnp (the oracle); kernels/dvbyte_decode provides the
Pallas VMEM-tiled implementation of step 2 and tests assert equivalence.

The decoded-postings layout is (NBLK, B) "one potential value per byte
position" with a validity mask — no dynamic shapes anywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .blockstore import _OFF_NPTR, H
from .collate import is_collated
from .dvbyte import dvbyte_decode_from
from .index import DynamicIndex


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceIndex:
    """Flat-array snapshot of a collated doc-level dynamic index."""

    blocks: jnp.ndarray      # (NB, B) uint8 — the index array I
    term_slot: jnp.ndarray   # (V,) i32 — first slot of each term's chain
    term_nblk: jnp.ndarray   # (V,) i32 — chain length in blocks
    term_skip: jnp.ndarray   # (V,) i32 — byte offset of postings in head
    term_nx: jnp.ndarray     # (V,) i32 — tail write cursor (bytes)
    term_ft: jnp.ndarray     # (V,) i32 — document frequency f_t
    num_docs: int            # static
    F: int                   # static fold threshold

    def tree_flatten(self):
        return ((self.blocks, self.term_slot, self.term_nblk, self.term_skip,
                 self.term_nx, self.term_ft), (self.num_docs, self.F))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, num_docs=aux[0], F=aux[1])


def build_device_image(index: DynamicIndex, vocab: list[bytes],
                       pad_blocks: int | None = None) -> DeviceIndex:
    """Snapshot a *collated, Const-mode, doc-level* index for the device."""
    store = index.store
    if not store.const_mode:
        raise ValueError("device images require Const blocks (B-addressable)")
    if index.word_level:
        raise ValueError("device images are doc-level")
    if not is_collated(index):
        raise ValueError("collate() the index before snapshotting (§5.5)")
    B = store.B
    V = len(vocab)
    slot = np.zeros(V, np.int32)
    nblk = np.zeros(V, np.int32)
    skip = np.zeros(V, np.int32)
    nxs = np.zeros(V, np.int32)
    fts = np.zeros(V, np.int32)
    for i, t in enumerate(vocab):
        h_ptr = index.lookup(t)
        if h_ptr is None:
            continue
        hb = h_ptr * B
        chain = list(store.chain_slots(h_ptr))
        slot[i] = h_ptr
        nblk[i] = len(chain)
        skip[i] = store.head_fixed + int(store.I[hb + store.head_fixed - 1])
        nxs[i] = store.get_nx(hb)
        fts[i] = store.get_ft(hb)
    nb = store.nblocks
    if pad_blocks is not None:
        nb = max(nb, pad_blocks)
    blocks = np.zeros((nb, B), np.uint8)
    blocks[: store.nblocks] = store.I[: store.nblocks * B].reshape(-1, B)
    return DeviceIndex(
        blocks=jnp.asarray(blocks), term_slot=jnp.asarray(slot),
        term_nblk=jnp.asarray(nblk), term_skip=jnp.asarray(skip),
        term_nx=jnp.asarray(nxs), term_ft=jnp.asarray(fts),
        num_docs=index.num_docs, F=index.F)


# --------------------------------------------------------------------------
# incremental device-image refresh: frozen image + live delta (engine/)
# --------------------------------------------------------------------------
#
# A full ``collate()`` + ``build_device_image()`` is stop-the-world; the
# engine instead keeps ONE frozen collated image plus a small ``DeltaIndex``
# covering only postings appended since the freeze.  Docids are ordinal and
# every document's postings are written before the next document starts, so
# docs <= baseline.num_docs live wholly in the frozen image and newer docs
# wholly in the delta: the two docid spaces are disjoint and merging per-image
# results (top-k concat / bitmap OR) is exact.


@dataclass
class DeltaBaseline:
    """Per-term tail state captured at freeze time (host-side numpy).

    For each term id the delta decoder later needs: which block was the tail
    at the freeze (``tail_slot``), where its write cursor stood (``nx``), the
    last docid coded (``lastd`` — new in-tail postings are plain d-gaps from
    it), the tail block's first docid (``dnum`` — blocks appended later code
    their leading b-gap against it), and ``ft`` (so refresh can detect which
    terms changed at all).
    """

    tail_slot: np.ndarray   # (Vf,) i64
    nx: np.ndarray          # (Vf,) i64
    lastd: np.ndarray       # (Vf,) i64
    dnum: np.ndarray        # (Vf,) i64
    ft: np.ndarray          # (Vf,) i64
    num_docs: int           # N at freeze time
    nblocks: int            # store.nblocks at freeze time

    @property
    def vocab_size(self) -> int:
        return len(self.tail_slot)


def capture_delta_baseline(index: DynamicIndex,
                           vocab: list[bytes]) -> DeltaBaseline:
    """Record every term's tail state so later appends can be snapshotted
    incrementally.  Called at the same moment the frozen image is built."""
    store = index.store
    if not store.const_mode:
        raise ValueError("delta images require Const blocks")
    if index.word_level:
        raise ValueError("delta images are doc-level")
    V = len(vocab)
    B = store.B
    out = DeltaBaseline(
        tail_slot=np.zeros(V, np.int64), nx=np.zeros(V, np.int64),
        lastd=np.zeros(V, np.int64), dnum=np.zeros(V, np.int64),
        ft=np.zeros(V, np.int64), num_docs=index.num_docs,
        nblocks=store.nblocks)
    for i, t in enumerate(vocab):
        h_ptr = index.lookup(t)
        if h_ptr is None:
            continue
        hb = h_ptr * B
        t_ptr = store.get_tptr(hb)
        out.tail_slot[i] = t_ptr
        out.nx[i] = store.get_nx(hb)
        out.lastd[i] = store.get_lastd(hb)
        # slot 0 of the tail block is d_num while the block IS the tail —
        # exactly the window in which we read it (head included: its slot 0
        # is d_num until the chain grows).
        out.dnum[i] = store._get_u32(t_ptr * B + _OFF_NPTR)
        out.ft[i] = store.get_ft(hb)
    return out


@jax.tree_util.register_pytree_node_class
@dataclass
class DeltaIndex:
    """Flat-array snapshot of postings appended since a DeltaBaseline.

    Shares the block/decode layout of :class:`DeviceIndex` (so
    :func:`query_step` runs on it unchanged) plus two per-term docid bases:
    the first delta posting of a term is a d-gap from ``term_lastd0`` if it
    lands in the old tail block, while blocks appended after the freeze code
    b-gaps chained from ``term_dnum0`` (the old tail's first docid).
    """

    blocks: jnp.ndarray      # (ND, B) uint8 — compacted delta blocks
    term_slot: jnp.ndarray   # (V,) i32 — first delta block per term
    term_nblk: jnp.ndarray   # (V,) i32 — delta chain length (0 = unchanged)
    term_skip: jnp.ndarray   # (V,) i32 — start byte inside the first block
    term_nx: jnp.ndarray     # (V,) i32 — tail write cursor (bytes)
    term_ft: jnp.ndarray     # (V,) i32 — GLOBAL f_t (for exact idf)
    term_lastd0: jnp.ndarray  # (V,) i32 — last docid coded before the freeze
    term_dnum0: jnp.ndarray  # (V,) i32 — first docid of the first delta block
    num_docs: int            # static docid-space capacity (not live N)
    F: int                   # static fold threshold

    def tree_flatten(self):
        return ((self.blocks, self.term_slot, self.term_nblk, self.term_skip,
                 self.term_nx, self.term_ft, self.term_lastd0,
                 self.term_dnum0), (self.num_docs, self.F))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, num_docs=aux[0], F=aux[1])


def build_delta_image(index: DynamicIndex, vocab: list[bytes],
                      baseline: DeltaBaseline, *, num_docs: int,
                      pad_vocab: int | None = None,
                      pad_blocks: int | None = None,
                      global_ft: np.ndarray | None = None) -> DeltaIndex:
    """Snapshot only the blocks appended (or still filling) since ``baseline``.

    Cost is proportional to the delta, not the index: unchanged terms are
    detected by an ``f_t`` comparison and contribute nothing; changed terms
    copy their old tail block plus any blocks allocated after the freeze.
    No ``collate()`` involved — chains are compacted on the fly into the
    fresh delta block array, so the device gather stays contiguous.

    ``global_ft`` is the current per-term-id f_t array (e.g. the engine's
    incrementally maintained counters).  When given, changed terms are
    short-listed with one vectorized comparison against ``baseline.ft`` and
    unchanged terms are never touched at all; without it, every term pays a
    lookup + head-field read (O(V) per refresh).
    """
    store = index.store
    if not store.const_mode:
        raise ValueError("delta images require Const blocks")
    if index.word_level:
        raise ValueError("delta images are doc-level")
    B = store.B
    V = len(vocab)
    Vp = max(V, pad_vocab or 0)
    Vf = baseline.vocab_size
    slot = np.zeros(Vp, np.int32)
    nblk = np.zeros(Vp, np.int32)
    skip = np.zeros(Vp, np.int32)
    nxs = np.zeros(Vp, np.int32)
    fts = np.zeros(Vp, np.int32)
    lastd0 = np.zeros(Vp, np.int32)
    dnum0 = np.zeros(Vp, np.int32)
    if global_ft is not None:
        fts[:V] = global_ft[:V]
        changed = np.flatnonzero(
            np.concatenate([np.asarray(global_ft[:Vf]) != baseline.ft[:V],
                            np.ones(V - min(Vf, V), bool)]))
        candidates = [(int(i), vocab[int(i)]) for i in changed]
    else:
        candidates = list(enumerate(vocab))
    chunks: list[np.ndarray] = []
    write = 0
    for i, t in candidates:
        h_ptr = index.lookup(t)
        if h_ptr is None:
            continue
        hb = h_ptr * B
        cur_ft = store.get_ft(hb)
        fts[i] = cur_ft
        if i < Vf and cur_ft == baseline.ft[i]:
            continue  # no postings since the freeze
        if i < Vf and baseline.ft[i] > 0:
            first_slot = int(baseline.tail_slot[i])
            skip[i] = int(baseline.nx[i])
            lastd0[i] = int(baseline.lastd[i])
            dnum0[i] = int(baseline.dnum[i])
        else:
            # term born after the freeze: the delta is its whole chain and
            # the head's leading code is an absolute docid (lastd starts 0)
            first_slot = h_ptr
            skip[i] = store.head_fixed + int(store.I[hb + store.head_fixed - 1])
            lastd0[i] = 0
            (g, _), _ = dvbyte_decode_from(store.I, hb + skip[i], store.F)
            dnum0[i] = g  # d_num of the head = its first docid
        # walk old-tail -> current tail via n_ptr links
        t_ptr = store.get_tptr(hb)
        chain = [first_slot]
        p = first_slot
        while p != t_ptr:
            p = store._get_u32(p * B + _OFF_NPTR)
            chain.append(p)
        slot[i] = write
        nblk[i] = len(chain)
        nxs[i] = store.get_nx(hb)
        for ptr in chain:
            chunks.append(store.I[ptr * B:(ptr + 1) * B])
        write += len(chain)
    nd = max(write, pad_blocks or 0, 1)
    blocks = np.zeros((nd, B), np.uint8)
    if chunks:
        blocks[:write] = np.stack(chunks)
    return DeltaIndex(
        blocks=jnp.asarray(blocks), term_slot=jnp.asarray(slot),
        term_nblk=jnp.asarray(nblk), term_skip=jnp.asarray(skip),
        term_nx=jnp.asarray(nxs), term_ft=jnp.asarray(fts),
        term_lastd0=jnp.asarray(lastd0), term_dnum0=jnp.asarray(dnum0),
        num_docs=num_docs, F=index.F)


def with_global_stats(image: DeviceIndex, term_ft: np.ndarray,
                      num_docs: int, pad_vocab: int | None = None
                      ) -> DeviceIndex:
    """Rebase a frozen image's scoring statistics to the LIVE collection.

    Merged frozen+delta querying is only exact if both sides weight postings
    with the global f_t and N; the frozen block bytes stay untouched — only
    the per-term metadata arrays are re-uploaded (and zero-padded so term ids
    minted after the freeze gather empty chains instead of clipping).
    """
    V = image.term_slot.shape[0]
    Vp = max(V, pad_vocab or 0)

    def pad(x):
        return jnp.pad(x, (0, Vp - x.shape[0]))

    ft = np.zeros(Vp, np.int32)
    ft[:min(len(term_ft), Vp)] = term_ft[:Vp]
    return replace(image, term_slot=pad(image.term_slot),
                   term_nblk=pad(image.term_nblk),
                   term_skip=pad(image.term_skip),
                   term_nx=pad(image.term_nx),
                   term_ft=jnp.asarray(ft), num_docs=num_docs)


# --------------------------------------------------------------------------
# step 2: parallel Double-VByte block decode (pure-jnp oracle for the kernel)
# --------------------------------------------------------------------------


def decode_blocks(blocks: jnp.ndarray, start: jnp.ndarray, end: jnp.ndarray,
                  F: int):
    """Decode a batch of B-byte blocks of Double-VByte postings.

    Args:
      blocks: (NB, B) uint8
      start:  (NB,) i32 — first payload byte (head skip or H)
      end:    (NB,) i32 — one past the last payload byte (nx or B)
      F:      fold threshold
    Returns (g, f, valid): each (NB, B); ``valid[i, j]`` marks byte position
    j as the terminator of a *primary* code in block i, with g/f the decoded
    pair (b-gap semantics for the first valid pair of each block preserved —
    the caller handles chaining).
    """
    b = blocks.astype(jnp.int32)
    NB, B = b.shape
    pos = jnp.arange(B, dtype=jnp.int32)[None, :]
    inside = (pos >= start[:, None]) & (pos < end[:, None])
    term = ((b & 0x80) == 0) & inside           # terminator bytes
    # start-of-code = previous terminator position + 1 (clamped to `start`)
    prev_term = jnp.where(term, pos, -1)
    prev_term = jax.lax.associative_scan(jnp.maximum, prev_term, axis=1)
    code_start = jnp.concatenate(
        [jnp.full((NB, 1), -1, jnp.int32), prev_term[:, :-1]], axis=1) + 1
    code_start = jnp.maximum(code_start, start[:, None])
    pos_in_code = pos - code_start
    payload = (b & 0x7F) << (7 * jnp.clip(pos_in_code, 0, 4))
    payload = jnp.where(inside, payload, 0)
    csum = jnp.cumsum(payload, axis=1)
    csum_at_start = jnp.take_along_axis(
        jnp.pad(csum, ((0, 0), (1, 0))), code_start, axis=1)
    value = jnp.where(term, csum - csum_at_start, 0)
    is_value = term & (value > 0)               # null sentinel masks out
    # Algorithm 2 unfold: pair escapes (value % F == 0) with the next value.
    mod = value % F

    def body(carry, x):
        # carry: does the *previous value* (not byte) await its escape pair?
        prev_esc = carry
        isv, v, m = x
        consumed = isv & prev_esc
        primary = isv & ~consumed
        esc_now = primary & (m == 0)
        g = jnp.where(m > 0, 1 + v // F, v // F)
        f = jnp.where(m > 0, m, 0)
        # a consumed value completes its predecessor's escape: emit nothing
        # here, but patch f onto the predecessor via the second output
        fpatch = jnp.where(consumed, F + v - 1, 0)
        # the carry only changes at value positions (byte gaps preserve it)
        new_carry = jnp.where(isv, esc_now, prev_esc)
        return new_carry, (primary, g, f, fpatch)

    xs = (jnp.swapaxes(is_value, 0, 1), jnp.swapaxes(value, 0, 1),
          jnp.swapaxes(mod, 0, 1))
    init = jnp.zeros(NB, bool)
    # unroll: keeps HLO cost_analysis exact (while bodies count once) and
    # the body is a handful of elementwise vector ops over (NB,)
    _, (primary, g, f, fpatch) = jax.lax.scan(body, init, xs, unroll=True)
    primary = jnp.swapaxes(primary, 0, 1)
    g = jnp.swapaxes(g, 0, 1)
    f = jnp.swapaxes(f, 0, 1)
    fpatch = jnp.swapaxes(fpatch, 0, 1)
    # shift fpatch one value-slot left: the consumed value sits at the NEXT
    # terminator position after its primary; scatter back via the same
    # associative trick — for each primary with f == 0, take the fpatch of
    # the next value position.  Positions are sparse; use a reverse scan that
    # propagates the nearest fpatch to the left.
    nxt = jax.lax.associative_scan(
        lambda a, b: jnp.where(b != 0, b, a),
        jnp.where(fpatch > 0, fpatch, 0), axis=1, reverse=True)
    f = jnp.where(primary & (f == 0), nxt, f)
    valid = primary
    return g, f, valid


# --------------------------------------------------------------------------
# step 4 helper: a log1p the device computes to f32 precision
# --------------------------------------------------------------------------


_LN2_HI = np.float32(6.9313812256e-01)   # 17 significant bits: e*hi is exact
_LN2_LO = np.float32(9.0580006145e-06)
_SQRT2 = np.float32(1.4142135)


def precise_log1p(x: jnp.ndarray) -> jnp.ndarray:
    """``log1p(x)`` for ``x >= 0`` in f32, to a few ulps on every backend.

    XLA's f32 ``log``/``log1p`` on a TPU v5e are off by up to 3.7e-4
    relative (measured against f64 over integers, ratios N/f_t and values
    in (0, 2)), which moves ranked scores past the 1e-5 the device answers
    are held to against the host's f64 scoring.  This uses only multiply,
    add, divide and bit operations, which the TPU rounds like the CPU.
    Both branches evaluate log(m) = 2 atanh(s), s = (m - 1)/(m + 1), whose
    series to s^9 is exact to f32 for |s| < 0.172:
      * x < sqrt(2) - 1: m = 1 + x taken as s = x / (2 + x), so no
        cancellation (XLA folds ``(1 + x) - 1`` to ``x``, so a rounding
        correction built from it would vanish);
      * otherwise u = 1 + x is split as 2^e * m with m in [sqrt(1/2),
        sqrt(2)); log(u) >= 0.34 there, so rounding 1 + x costs < 2 ulps.
    """
    x = x.astype(jnp.float32)
    u = 1.0 + x
    bits = jax.lax.bitcast_convert_type(u, jnp.int32)
    e = (bits >> 23) - 127
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                     jnp.float32)            # [1, 2)
    big = m > _SQRT2
    m = jnp.where(big, m * 0.5, m)
    fm = m - 1.0                                            # exact
    small = x < _SQRT2 - 1.0
    s = jnp.where(small, x / (2.0 + x), fm / (2.0 + fm))
    ef = jnp.where(small, 0, e + big.astype(jnp.int32)).astype(jnp.float32)
    z = s * s
    poly = z * (2.0 / 3 + z * (2.0 / 5 + z * (2.0 / 7 + z * (2.0 / 9))))
    log_m = 2.0 * s + s * poly
    return ef * _LN2_HI + (log_m + ef * _LN2_LO)


# --------------------------------------------------------------------------
# steps 1+3+4: full batched query
# --------------------------------------------------------------------------


MAX_BLOCKS = 64  # per-term chain-length cap for the gather (pad/truncate)


@partial(jax.jit, static_argnames=("k", "mode", "max_blocks", "decode_fn"))
def query_step(image: DeviceIndex, qterms: jnp.ndarray, qmask: jnp.ndarray,
               k: int = 10, mode: str = "ranked",
               max_blocks: int = MAX_BLOCKS, decode_fn=None,
               doclens: jnp.ndarray | None = None,
               n_stat: jnp.ndarray | None = None,
               avg_stat: jnp.ndarray | None = None):
    """Batched query execution against a device image.

    Args:
      qterms: (Q, T) i32 term ids (padded);  qmask: (Q, T) bool.
      mode: "ranked" (top-k TF×IDF, dense accumulator), "ranked_sparse"
        (top-k TF×IDF, sort-based), "bm25" (top-k BM25, sort-based —
        requires ``doclens`` (N+1,) f32; paper §6.2's future work), or
        "conjunctive" (hit bitmap counts).
      n_stat: optional dynamic collection size used for idf/avgdl statistics;
        defaults to ``image.num_docs``.  The engine's frozen+delta path sizes
        accumulators by a fixed capacity (``image.num_docs``) but must score
        with the live N, which changes every refresh — passing it dynamically
        avoids a recompile per ingested document.
      avg_stat: optional average document length for BM25.  Defaults to
        ``doclens[1:].sum() / n_stat`` — correct when ``doclens`` covers
        the whole collection, but a document-partitioned shard's local
        doclens sum is NOT the collection's, so its fan-out layer passes
        the fleet-wide average here.
    Returns (top docids (Q, k) i32, top scores (Q, k) f32) for ranked
    modes, or (matches (Q, N) bool, counts) for conjunctive mode.

    ``image`` may also be a :class:`DeltaIndex`; the only difference is docid
    reconstruction, which chains from the delta's per-term bases instead of
    zero (see ``DeltaIndex`` docstring).
    """
    B = image.blocks.shape[1]
    Q, T = qterms.shape
    flat_terms = qterms.reshape(-1)
    slot = image.term_slot[flat_terms]
    nblk = image.term_nblk[flat_terms]
    skip = image.term_skip[flat_terms]
    nx = image.term_nx[flat_terms]
    # ---- step 1: contiguous chain gather (collation makes this a slice) ----
    bidx = slot[:, None] + jnp.arange(max_blocks, dtype=jnp.int32)[None, :]
    bvalid = (jnp.arange(max_blocks)[None, :] < nblk[:, None]) \
        & qmask.reshape(-1)[:, None]
    bidx = jnp.where(bvalid, bidx, 0)
    gathered = image.blocks[bidx.reshape(-1)]          # (QT*MB, B)
    # per-block payload bounds
    is_head = jnp.broadcast_to(jnp.arange(max_blocks)[None, :] == 0,
                               (Q * T, max_blocks))
    is_tail = (jnp.arange(max_blocks)[None, :] == (nblk - 1)[:, None])
    start = jnp.where(is_head, skip[:, None], H).reshape(-1)
    end = jnp.where(is_tail, nx[:, None], B).reshape(-1)
    end = jnp.where(bvalid.reshape(-1), end, 0)        # invalid block: empty
    # ---- step 2: parallel decode ----
    fn = decode_fn if decode_fn is not None else decode_blocks
    g, f, valid = fn(gathered, start, end, image.F)    # (QT*MB, B)
    g = g.reshape(Q * T, max_blocks, B)
    f = f.reshape(Q * T, max_blocks, B)
    valid = valid.reshape(Q * T, max_blocks, B)
    # ---- step 3: docid reconstruction ----
    gv = jnp.where(valid, g, 0)
    within = jnp.cumsum(gv, axis=2)                    # in-block gap sums
    # leading value of each block is a b-gap (or the absolute first docid for
    # the head, since last_d starts at 0): chain first-docids = cumsum of the
    # per-block first gaps
    first_gap = jnp.max(jnp.where(
        jnp.cumsum(valid, axis=2) == 1, gv, 0), axis=2)  # (QT, MB)
    if isinstance(image, DeltaIndex):
        # delta chains don't start at docid 0: the first block's leading code
        # is a d-gap from lastd0 (it continues the old tail), while later
        # blocks chain b-gaps from dnum0 (the old tail's first docid)
        lastd0 = image.term_lastd0[flat_terms]
        dnum0 = image.term_dnum0[flat_terms]
        cum = jnp.cumsum(first_gap, axis=1)
        bf0 = lastd0[:, None] + first_gap[:, :1]
        bfr = dnum0[:, None] + (cum - first_gap[:, :1])
        block_first = jnp.concatenate([bf0, bfr[:, 1:]], axis=1)
    else:
        block_first = jnp.cumsum(first_gap, axis=1)    # absolute first docids
    docid = block_first[:, :, None] + (within - first_gap[:, :, None])
    docid = jnp.where(valid, docid, 0)                 # (QT, MB, B)
    # ---- step 4: scoring ----
    N = image.num_docs
    Ns = jnp.float32(N) if n_stat is None else n_stat.astype(jnp.float32)
    flat_docs = docid.reshape(Q, -1)
    if mode == "conjunctive":
        hits = jnp.zeros((Q, N + 1), jnp.int32)
        ones = valid.reshape(Q, -1).astype(jnp.int32)
        hits = jax.vmap(lambda h, dd, oo: h.at[dd].add(oo))(hits, flat_docs,
                                                            ones)
        nterms = qmask.sum(axis=1)
        matches = (hits[:, 1:] == nterms[:, None]) & (nterms[:, None] > 0)
        return matches, matches.sum(axis=1)
    ft = jnp.maximum(image.term_ft[flat_terms], 1).astype(jnp.float32)
    if mode == "bm25":
        # Okapi BM25 (k1=0.9, b=0.4): saturated tf with length normalization
        k1, b = 0.9, 0.4
        idf = precise_log1p((Ns - ft + 0.5) / (ft + 0.5))
        idf = (idf * qmask.reshape(-1)).reshape(Q, T)
        dl = doclens[docid.reshape(Q, -1)]                  # (Q, P)
        avgdl = (jnp.maximum(doclens[1:].sum() / Ns, 1e-9)
                 if avg_stat is None
                 else jnp.maximum(avg_stat.astype(jnp.float32), 1e-9))
        fv = jnp.where(valid, f, 0).astype(jnp.float32).reshape(Q, -1)
        tf = (fv * (k1 + 1.0)) / (fv + k1 * (1.0 - b + b * dl / avgdl))
        w = (tf.reshape(Q, T, max_blocks, B)
             * idf[:, :, None, None]).reshape(Q, -1)
    else:
        idf = precise_log1p(Ns / ft)
        idf = (idf * qmask.reshape(-1)).reshape(Q, T)
        # the barrier keeps XLA from re-deriving the log inside every
        # consumer of w (the sort gather below): without it the four-chip
        # mesh step took over 6 minutes to compile for a v5e, 21 s with it
        w = jax.lax.optimization_barrier(
            precise_log1p(jnp.where(valid, f, 0)))
        w = w.reshape(Q, T, max_blocks, B) * idf[:, :, None, None]
        w = w.reshape(Q, -1)
    if mode in ("ranked_sparse", "bm25"):
        # §Perf H1: sort-based sparse aggregation.  The dense accumulator
        # touches (Q, N) floats (N = shard docs, >> touched postings); here
        # cost is O(Q * P log P) on P = T*max_blocks*B posting slots only.
        order = jnp.argsort(flat_docs, axis=1)
        d_s = jnp.take_along_axis(flat_docs, order, axis=1)   # (Q, P)
        w_s = jnp.take_along_axis(w, order, axis=1)
        P = d_s.shape[1]
        nxt = jnp.concatenate(
            [d_s[:, 1:], jnp.full((Q, 1), -1, d_s.dtype)], axis=1)
        is_end = d_s != nxt                                   # run ends
        # each term holds a docid at most once, so a docid's run is at most
        # T long: sum it from the T - 1 slots before its end.  (A difference
        # of prefix sums over the whole row loses 1e-5 of a score to f32
        # cancellation once a query touches ~10^4 postings.)
        run = w_s
        for j in range(1, min(T, P)):
            same = jnp.concatenate(
                [jnp.zeros((Q, j), bool), d_s[:, j:] == d_s[:, :-j]], axis=1)
            prev = jnp.concatenate(
                [jnp.zeros((Q, j), w_s.dtype), w_s[:, :-j]], axis=1)
            run = run + jnp.where(same, prev, 0.0)
        run_score = jnp.where(is_end & (d_s > 0), run, -jnp.inf)
        # k may exceed the posting-slot count (top_k requires k <= minor
        # dim); clamping is exact — distinct scored docids never exceed P
        top_s, pos_k = jax.lax.top_k(run_score, min(k, P))
        top_d = jnp.take_along_axis(d_s, pos_k, axis=1)
        return top_d.astype(jnp.int32), top_s
    scores = jnp.zeros((Q, N + 1), jnp.float32)
    scores = jax.vmap(lambda s, dd, ww: s.at[dd].add(ww))(scores, flat_docs, w)
    scores = scores.at[:, 0].set(-jnp.inf)
    top_s, top_d = jax.lax.top_k(scores, min(k, N + 1))  # clamp: k <= cols
    return top_d.astype(jnp.int32), top_s
