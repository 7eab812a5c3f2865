"""Shared tile math for the fused decode→score→top-k query kernel.

One function — :func:`fused_tile` — implements the whole query pipeline
(Double-VByte decode, docid reconstruction across frozen+delta chain rows,
weight accumulation, top-k / conjunctive matching) as straight-line jnp over
fixed shapes.  Both flavours of the public op execute EXACTLY this function:

* the reference flavour calls it inline over the launch's pools;
* the Pallas flavour calls it inside a ``pallas_call`` body, one
  invocation over the same whole pools (kernel.py).

Because the arithmetic is identical (same ops on the same shapes), the two
flavours produce byte-identical results — the differential tests assert
exact float equality, not tolerances.

Decode here is *scan-free*: the escape-pairing automaton of Algorithm 2
(``c_{i+1} = escape_i & ~c_i``) has the closed form

    consumed(i)  ⇔  the run of consecutive raw-escape values immediately
                    before value i has odd length,

because a raw non-escape value (``value % F != 0``) always resets the
automaton and a run of raw escapes alternates primary/consumed.  The run
length is ``(rank_i - 1) - rank_of_last_non_escape_before_i``, both
computable with one cumsum and one cummax over byte positions — no
``lax.scan``/``fori_loop``, so the whole decode is a handful of log-step
vector ops (exactly what the VPU wants).  All shifts are ``pad``+``slice``
(measured ~3× cheaper than the roll/iota/where idiom on XLA:CPU — the roll
materializes a wrapped copy plus a mask per level; the pad shifts in the
fill value directly).

The tile consumes a tuple of per-image *parts* — (frozen, delta), each one
*flat pool* of chain blocks for the whole launch: prep packs every live
query's actual chain blocks query-major, then term-major, into NS =
pow2(Σ owned blocks) slots, so no slot pads for the vocabulary's longest
chain or the batch's longest query.  Each slot carries its (query, term) segment id, its query row
``qix``, docid-chaining bases and idf weight.  Chaining then runs as
*segmented* log-step scans along the one slot axis (segments are contiguous
and unique per (query, term), so plain Hillis–Steele with a same-segment
guard is exact).  Row bases ``lastd0``/``dnum0`` are (0, -1) for frozen
segments (the -1 sentinel means "use the head block's first gap", reducing
to the absolute cumsum of leading b-gaps) and the delta's captured tail
state for delta segments (first value = d-gap from ``lastd0``, later blocks
chain b-gaps from ``dnum0`` — see ``core.device_index.DeltaIndex``).

The stages carry ``jax.named_scope`` names — ``decode``, ``accumulate``
and ``topk`` here, ``gather`` in ``ops._prep_image`` — so a device trace
or the lowered HLO says which stage each operation belongs to.

Aggregation is a *dense scatter over the docid capacity*: every decoded
posting adds its weight (or hit count) into a (Qn, cap+1) accumulator,
through the flat index ``qix·(cap+1) + docid``, and top-k runs over each
row — docids are the top-k indices themselves, and
equal scores tie-break toward the smaller index, which IS the canonical
(score desc, docid asc) order.  Frozen and delta docid spaces are disjoint,
so accumulating both parts into one array is exact.  Pad slots decode to
nothing: their postings add exactly 0 to column 0 of row 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.device_index import precise_log1p

BM25_K1 = 0.9
BM25_B = 0.4


def _shift_right(x: jnp.ndarray, shift: int, axis: int,
                 fill) -> jnp.ndarray:
    """Shift ``x`` right along ``axis``, filling the head with ``fill``
    (pad+slice: one fused op per level, no wrapped copy, no mask)."""
    n = x.shape[axis]
    cfg = [(0, 0, 0)] * x.ndim
    cfg[axis] = (shift, 0, 0)
    return jax.lax.pad(jax.lax.slice_in_dim(x, 0, n - shift, axis=axis),
                       jnp.asarray(fill, x.dtype), cfg)


def _cummax(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Unrolled Hillis–Steele inclusive running maximum along ``axis``."""
    n = x.shape[axis]
    lo = jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer) \
        else -jnp.inf
    shift = 1
    while shift < n:
        x = jnp.maximum(x, _shift_right(x, shift, axis, lo))
        shift *= 2
    return x


def _cumsum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Unrolled Hillis–Steele inclusive prefix sum along ``axis``."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        x = x + _shift_right(x, shift, axis, 0)
        shift *= 2
    return x


def _seg_cumsum(x: jnp.ndarray, seg: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Segmented inclusive prefix sum along ``axis``: resets wherever the
    segment id changes.  Exact for CONTIGUOUS segments: after the level-s
    step, position i holds the sum of its last s same-segment predecessors,
    and the same-segment guard keeps windows disjoint across levels."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        same = seg == _shift_right(seg, shift, axis, -1)
        x = x + jnp.where(same, _shift_right(x, shift, axis, 0), 0)
        shift *= 2
    return x


def _seg_cummax(x: jnp.ndarray, seg: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Segmented inclusive running maximum along ``axis`` (same guard)."""
    n = x.shape[axis]
    lo = jnp.iinfo(x.dtype).min
    shift = 1
    while shift < n:
        same = seg == _shift_right(seg, shift, axis, -1)
        x = jnp.maximum(x, jnp.where(same, _shift_right(x, shift, axis, lo),
                                     lo))
        shift *= 2
    return x


def _hold_last_right(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Nearest non-zero at-or-right of each position (log-step hold-last)."""
    n = x.shape[axis]
    rev = jnp.flip(x, axis=axis)
    shift = 1
    while shift < n:
        rev = jnp.where(rev > 0, rev, _shift_right(rev, shift, axis, 0))
        shift *= 2
    return jnp.flip(rev, axis=axis)


def decode_blocks_parallel(blocks: jnp.ndarray, start: jnp.ndarray,
                           end: jnp.ndarray, F: int):
    """Scan-free Double-VByte block decode (same contract as
    ``core.device_index.decode_blocks``: (NB, B) blocks → (g, f, valid)).

    Steps 1–4 match the existing decoders (terminator flags, prev-terminator
    cummax, payload shift/cumsum); step 5 (escape pairing) uses the
    run-length-parity closed form instead of a sequential automaton.
    """
    b = blocks.astype(jnp.int32)
    NB, B = b.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (NB, B), 1)
    start = start.reshape(NB, 1)
    end = end.reshape(NB, 1)
    inside = (pos >= start) & (pos < end)
    term = ((b & 0x80) == 0) & inside
    prev_term = _cummax(jnp.where(term, pos, -1), axis=1)
    code_start = jnp.maximum(_shift_right(prev_term, 1, 1, -1) + 1, start)
    pos_in_code = jnp.clip(pos - code_start, 0, 4)
    payload = jnp.where(inside, (b & 0x7F) << (7 * pos_in_code), 0)
    csum = _cumsum(payload, axis=1)
    prev_csum = _cummax(
        jnp.where(term, csum, jnp.iinfo(jnp.int32).min), axis=1)
    prev_csum = jnp.maximum(_shift_right(prev_csum, 1, 1, 0), 0)
    value = jnp.where(term, csum - prev_csum, 0)
    is_value = term & (value > 0)
    mod = value % F
    # --- Algorithm 2 unfold, run-length-parity form -----------------------
    # rank of each value among the row's values (1-based, at value positions)
    rank = _cumsum(is_value.astype(jnp.int32), axis=1)
    non_esc = is_value & (mod != 0)
    # rank of the last raw NON-escape value strictly before this position
    last_ne = _cummax(jnp.where(non_esc, rank, 0), axis=1)
    last_ne = jnp.maximum(_shift_right(last_ne, 1, 1, 0), 0)
    # values (last_ne, rank-1] are all raw escapes; odd run ⇒ consumed
    consumed = is_value & (((rank - 1 - last_ne) & 1) == 1)
    primary = is_value & ~consumed
    g = jnp.where(primary, jnp.where(mod > 0, 1 + value // F, value // F), 0)
    f = jnp.where(primary & (mod > 0), mod, 0)
    # a consumed value holds F + v - 1, patched onto its primary (the
    # immediately preceding value): nearest consumed-value to the right
    fpatch = _hold_last_right(jnp.where(consumed, F + value - 1, 0), axis=1)
    f = jnp.where(primary & (f == 0), fpatch, f)
    return g, f, primary


def _part_postings(part, F: int):
    """Decode one image's flat pool into per-posting (docid, f, valid).

    ``part`` is (gat, start, end, seg, lastd0, dnum0, widf, qix): gat
    (NS, B) chain blocks packed query-major then term-major, seg (NS,) the
    owning (query, term) segment id (one id past the last for pad slots),
    lastd0/dnum0/widf (NS,) the owning term's chaining bases and idf weight
    per slot, qix (NS,) the owning query's row.
    """
    gat, start, end, seg, lastd0, dnum0 = part[:6]
    g, f, valid = decode_blocks_parallel(gat, start, end, F)   # (NS, B)
    # ---- docid reconstruction (uniform frozen/delta chaining) ------------
    gv = jnp.where(valid, g, 0)
    within = _cumsum(gv, axis=1)
    vcum = _cumsum(valid.astype(jnp.int32), axis=1)
    first_gap = jnp.max(jnp.where(vcum == 1, gv, 0), axis=1)   # (NS,)
    # chain arithmetic per segment: the head block's first docid is
    # lastd0 + its first gap; later blocks sit at dnum_eff + the running
    # sum of first gaps (head's excluded), dnum_eff resolving the frozen
    # -1 sentinel to the head block's own first gap
    is_head = seg != _shift_right(seg, 1, 0, -1)
    fg_head = jnp.maximum(_seg_cummax(
        jnp.where(is_head, first_gap, jnp.iinfo(jnp.int32).min), seg,
        axis=0), 0)
    s_cum = _seg_cumsum(first_gap, seg, axis=0)
    dnum_eff = jnp.where(dnum0 < 0, fg_head, dnum0)
    block_first = jnp.where(is_head, lastd0 + first_gap,
                            dnum_eff + (s_cum - fg_head))
    docid = block_first[:, None] + (within - first_gap[:, None])
    docid = jnp.where(valid, docid, 0)                 # (NS, B)
    return docid, f, valid


def _scatter_add(acc: jnp.ndarray, qix: jnp.ndarray, docid: jnp.ndarray,
                 vals: jnp.ndarray, width: int) -> jnp.ndarray:
    """Add ``vals`` (NS, B) into the flat accumulator at
    ``qix·width + docid``: row ``qix`` of its (Qn, width) view."""
    idx = qix[:, None] * width + docid
    return acc.at[idx.reshape(-1)].add(vals.reshape(-1))


def _unpack_alive(alive: jnp.ndarray, cap: int) -> jnp.ndarray:
    """(words,) uint32 little-endian liveness bitmask → (cap+1,) bool.

    Bit ``d`` of the mask (word ``d >> 5``, bit ``d & 31``) is document
    ``d``'s liveness.  Packed storage keeps the device-resident mask at
    1 bit/docid instead of the 32 bits/docid a dense f32 mask cost — the
    unpack is a gather + shift over an iota, fused into the surrounding
    program, so no dense mask ever lands in HBM."""
    idx = jnp.arange(cap + 1, dtype=jnp.int32)
    return ((alive[idx >> 5] >> (idx & 31).astype(jnp.uint32)) & 1) != 0


def fused_tile(parts, nterms, doclens, bm25_norm, *, mode: str, k: int,
               F: int, cap: int, alive=None):
    """Decode → docids → score → select for a launch's queries.

    Args:
      parts: per-image flat pools (gat, start, end, seg, lastd0, dnum0,
        widf, qix) — gat (NS_i, B) uint8 chain blocks (per-image pool
        size), start/end (NS_i,) i32 payload byte bounds (end 0 = pad
        slot), seg (NS_i,) i32 (query, term) segment ids, lastd0/dnum0
        (NS_i,) i32 docid-chaining bases (dnum0 -1 ⇒ frozen absolute
        chain), widf (NS_i,) f32 idf weights (0 for pad slots), qix
        (NS_i,) i32 query rows (0 for pad slots).
      nterms: (Qn,) i32 — live terms per query (conjunctive only).
      doclens: (cap+1,) f32 — document lengths (bm25 only, else shape (1,)).
      bm25_norm: (2,) f32 — (k1*(1-b), k1*b/avgdl) (bm25 only).
      mode: "conjunctive" | "ranked_tfidf" | "bm25".
      k, F, cap: static top-k size, fold threshold, docid capacity.
      alive: optional (ceil((cap+1)/32),) uint32 packed little-endian
        liveness bitmask (bit ``d`` clear at tombstoned docids and at
        index 0) — dead documents' postings still decode (they live in
        the uploaded images until the next freeze compacts them away) but
        are masked out of the accumulator before selection, so the fused
        path matches the host path under deletes.

    Returns ``matches (Qn, cap+1) bool`` for conjunctive, else
    ``(top_d (Qn, kk) i32, top_s (Qn, kk) f32)`` with kk = min(k, cap+1),
    descending score, ties broken by ascending docid (canonical order).
    """
    Qn, W = nterms.shape[0], cap + 1
    if mode == "conjunctive":
        hits = jnp.zeros(Qn * W, jnp.int32)
        for part in parts:
            with jax.named_scope("decode"):
                docid, _f, valid = _part_postings(part, F)
            with jax.named_scope("accumulate"):
                hits = _scatter_add(hits, part[-1], docid,
                                    valid.astype(jnp.int32), W)
        with jax.named_scope("topk"):
            hits = hits.reshape(Qn, W)
            matches = (hits == nterms[:, None]) & (nterms[:, None] > 0)
            if alive is not None:
                matches = matches & _unpack_alive(alive, cap)[None, :]
            return matches.at[:, 0].set(False)
    score = jnp.zeros(Qn * W, jnp.float32)
    for part in parts:
        with jax.named_scope("decode"):
            docid, f, valid = _part_postings(part, F)
        with jax.named_scope("accumulate"):
            *_, widf, qix = part
            fv = jnp.where(valid, f, 0).astype(jnp.float32)
            if mode == "bm25":
                dl = doclens[docid]                    # (NS, B)
                tf = (fv * (BM25_K1 + 1.0)) / (
                    fv + bm25_norm[0] + bm25_norm[1] * dl)
                w = tf * widf[:, None]
            else:
                w = precise_log1p(fv) * widf[:, None]
            w = jnp.where(valid, w, 0.0)
            score = _scatter_add(score, qix, docid, w, W)
    with jax.named_scope("topk"):
        score = score.reshape(Qn, W)
        if alive is not None:
            # mask by select, not multiply: a fully-deleted term's padded
            # idf could be ±inf, and inf * 0 would poison the accumulator
            score = jnp.where(_unpack_alive(alive, cap)[None, :], score,
                              0.0)
        # docids are the accumulator indices: top_k ties prefer the smaller
        # index, i.e. the smaller docid — canonical order for free.  Absent
        # docids hold exactly 0.0 and every real match scores > 0 (idf >
        # 0), so the caller's s > 0 filter drops them.
        top_s, top_d = jax.lax.top_k(score, min(k, cap + 1))
        return top_d.astype(jnp.int32), top_s
