"""Device backend: resident frozen image + incrementally refreshed delta.

The naive TPU path re-runs ``collate()`` + ``build_device_image()`` on every
ingest — stop-the-world, which breaks the paper's immediate-access property
exactly where it matters.  The :class:`ResidentImageManager` instead keeps:

  * a **resident frozen image**: the collated snapshot from the last full
    collation (``Engine.collate_now``), uploaded ONCE per freeze epoch —
    its block array stays on device across queries and refreshes; only the
    per-term statistics are rebased to the live collection at each refresh
    (``with_global_stats``);
  * a **delta image**: a :class:`~repro.core.device_index.DeltaIndex`
    snapshotting only blocks appended since the freeze (cost ∝ delta);

and the backends answer queries by running the fused decode→score→top-k
kernel (``kernels/fused_query``) over BOTH images in one launch.  Because
docids are ordinal and each document's postings are written atomically,
frozen and delta docid spaces are disjoint — merging them inside one
posting pool is exact, verified against the host backend by the
differential tests.

The manager is shared by the ``device`` backend (reference flavour of the
fused op — the oracle) and the ``pallas`` backend (the Pallas kernel
flavour), so a mixed query stream pays for at most one resident image and
one delta rebuild per engine version.

**Delta-compaction policy** (fragmentation threshold): an incremental
refresh whose *projected* delta — new blocks since the freeze plus one
copied tail block per changed term — exceeds both an absolute floor and a
fraction of the store falls back to a full collation first.  Beyond that
threshold the python chain-walk of ``build_delta_image`` costs more than
collating outright (measured in BENCH_engine.json's delta section), so
incremental refresh would otherwise be the slower option exactly when the
delta is largest.  The projection is computed from O(V) counter
comparisons BEFORE paying the walk.

Shapes are bucketed (vocab, block count, chain length, batch, and docid
capacity all round up to powers of two) so steady-state serving reuses
compiled programs; a refresh after ingest re-traces only when a bucket
grows.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

from ..core.device_index import (
    DeviceIndex,
    build_delta_image,
    build_device_image,
    capture_delta_baseline,
    query_step,
    with_global_stats,
)
from ..kernels import registry
from .backends import Backend, UnsupportedQueryError
from .types import POSITIONAL_MODES, Query, QueryResult


def _pow2(n: int, floor: int = 1) -> int:
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


class ResidentImageManager:
    """Owns the device-resident (frozen, delta) image pair for one engine.

    Lifecycle counters double as the amortization evidence the benchmarks
    record: ``frozen_uploads`` bumps only at freeze (collation) time while
    ``batches_served`` bumps per fused launch — steady-state serving shows
    many batches per upload.  ``launch_buckets`` counts launches per
    compiled-program key (mode, k, Qn, T, per-image pool sizes, frozen and
    delta block counts, vocab and doc capacity, liveness mask or not): the
    number of distinct keys is the number of programs serving compiled.
    """

    def __init__(self, engine, decode_fn=None):
        self.engine = engine
        self.decode_fn = decode_fn
        self._frozen_raw: DeviceIndex | None = None   # as built at freeze
        self._baseline = None                          # DeltaBaseline
        self._frozen = None             # writer_only — stats-rebased frozen
        self._delta = None              # writer_only — DeltaIndex
        self._doclens = None                           # (cap+1,) f32 device
        self._alive = None              # packed uint32 liveness bits or None
        self._n_stat = None
        self._avg_stat = None                          # fleet avgdl (sharded)
        self._synced_version = -1                      # writer_only
        self._frozen_mb = 1                            # max_blocks, frozen
        self._delta_mb = 1                             # max_blocks, delta
        self._nblk_np = None            # writer_only — host (frozen, delta)
        #                                                per-term chain sizes
        self._doc_cap = 1024
        self._vocab_cap = 64
        self.epoch = 0                                 # freeze epochs seen
        self.frozen_uploads = 0                        # resident-image uploads
        self.batches_served = 0                        # fused launches
        self.launch_buckets: Counter = Counter()
        self._bucket_ix: dict = {}      # writer_only — key -> first-seen #

    # ------------------------------------------------------------------
    # image lifecycle
    # ------------------------------------------------------------------

    def freeze(self) -> None:
        """Adopt the engine's (just-collated) index as the frozen image and
        rebase the delta to empty.  Called by ``Engine.collate_now`` — the
        ONLY point at which the full block array is re-uploaded."""
        eng = self.engine
        with eng.spans.span("repro.device.upload"):
            self._frozen_raw = build_device_image(eng.index, eng.vocab)
            self._baseline = capture_delta_baseline(eng.index, eng.vocab)
        self._frozen_mb = _pow2(int(self._frozen_raw.term_nblk.max())
                                if eng.vocab else 1)
        self._frozen = None        # stale metadata: rebuild from _frozen_raw
        self._synced_version = -1  # force a refresh before the next query
        self.epoch += 1
        self.frozen_uploads += 1
        eng.stats_counters.resident_uploads += 1

    def _projected_delta_blocks(self, local_fts: np.ndarray) -> int:
        """Upper-bound estimate of the delta a refresh would build: blocks
        allocated since the freeze + one copied tail block per changed term.
        O(V) vectorized counter compares — no chain walk."""
        base = self._baseline
        store = self.engine.index.store
        Vf = min(base.vocab_size, len(local_fts))
        changed = int(np.count_nonzero(local_fts[:Vf] != base.ft[:Vf]))
        changed += int(np.count_nonzero(local_fts[Vf:] > 0))
        return (store.nblocks - base.nblocks) + changed

    def _maybe_compact(self, local_fts: np.ndarray) -> bool:
        """Fragmentation-threshold compaction: fall back to a full collation
        when the projected delta exceeds the policy bounds (both the
        absolute block floor AND the store fraction must trip — the floor
        keeps small indexes on the honest incremental path)."""
        eng = self.engine
        frac = eng.delta_compact_frac
        if frac is None or self._baseline is None:
            return False
        projected = self._projected_delta_blocks(local_fts)
        total = max(1, eng.index.store.nblocks)
        if (projected <= eng.delta_compact_min_blocks
                or projected <= frac * total):
            return False
        eng.collate_now()          # re-freezes: baseline + resident image
        eng.stats_counters.delta_compactions += 1
        return True

    def refresh(self) -> bool:
        """Incremental device-image refresh: snapshot only post-freeze blocks.

        Returns True if anything was rebuilt.  ``collate()`` runs here only
        when the compaction policy trips (projected delta past the
        fragmentation threshold); below it, this is the honest
        immediate-access path for the device backends.  A
        ``repro.device.refresh`` span whose ``rebuilt`` id says which.
        """
        eng = self.engine
        stale = self._synced_version != eng.version
        with eng.spans.span("repro.device.refresh", rebuilt=int(stale)):
            if stale:
                self._rebuild()
        return stale

    def _rebuild(self) -> None:
        import jax.numpy as jnp
        eng = self.engine
        if not eng.device_capable:
            raise UnsupportedQueryError(
                "device images need a Const-mode doc-level index")
        if self._baseline is None:
            # never collated: an empty baseline makes the delta cover the
            # whole index, so the device path works before any collation
            self._frozen_raw = _empty_image(eng)
            self._baseline = capture_delta_baseline(eng.index, [])
        # scoring f_t (collection-wide under a fleet stats provider) vs the
        # engine's LOCAL counters: change detection in build_delta_image
        # compares against the freeze baseline's store-level f_t, so it must
        # see the local numbers — the global ones would flag every term of
        # a sharded engine as changed and blow the delta up to O(V)
        local_fts = np.asarray(eng._fts, dtype=np.int64)
        self._maybe_compact(local_fts)
        N = eng.index.num_docs
        doc_cap = max(self._doc_cap, _pow2(N + 1))
        vocab_cap = max(self._vocab_cap, _pow2(len(eng.vocab)))
        # scoring statistics: in a fleet, idf-N and avgdl are the
        # COLLECTION's; with tombstones outstanding they are the engine's
        # synthesized live counters — either way the delta must weight its
        # postings with the SAME f_t as the frozen image (exact merge)
        stats = eng.ranking_stats()
        fts = (stats.fts_for(eng.vocab) if stats is not None
               else np.asarray(eng._fts, dtype=np.int64))
        # the frozen image's chain metadata only changes when a bucket grows
        # or after a freeze; per-refresh work is just the f_t swap + delta
        if (self._frozen is None or doc_cap != self._doc_cap
                or vocab_cap != self._vocab_cap
                or self._frozen.term_slot.shape[0] != vocab_cap):
            self._frozen = with_global_stats(self._frozen_raw, fts, doc_cap,
                                             pad_vocab=vocab_cap)
        else:
            self._frozen = with_global_stats(self._frozen, fts, doc_cap)
        self._doc_cap, self._vocab_cap = doc_cap, vocab_cap
        delta = build_delta_image(eng.index, eng.vocab, self._baseline,
                                  num_docs=self._doc_cap,
                                  pad_vocab=self._vocab_cap,
                                  global_ft=local_fts)
        if stats is not None:
            # fleet or deletion-aware mode: override the delta's baked
            # store-level f_t with the collection-wide / live numbers
            ftp = np.zeros(int(delta.term_ft.shape[0]), np.int32)
            ftp[:min(len(fts), len(ftp))] = fts[:len(ftp)]
            delta.term_ft = jnp.asarray(ftp)
        nd = _pow2(int(delta.blocks.shape[0]))
        if nd > delta.blocks.shape[0]:
            delta.blocks = jnp.pad(
                delta.blocks, ((0, nd - delta.blocks.shape[0]), (0, 0)))
        self._delta = delta
        self._delta_mb = _pow2(int(delta.term_nblk.max())
                               if delta.term_nblk.shape[0] else 1)
        # host copy of both images' per-term chain sizes: fused_execute
        # sizes each launch's flat block pool from the batch's actual
        # chains (one small device→host pull per refresh, not per batch)
        self._nblk_np = (np.asarray(self._frozen.term_nblk),
                         np.asarray(delta.term_nblk))
        dl = np.zeros(self._doc_cap + 1, np.float32)
        dl[1:N + 1] = eng.doclens_array()[1:N + 1]
        self._doclens = jnp.asarray(dl)
        # liveness mask: tombstoned docids score 0 inside the fused kernel's
        # accumulator; None (the common case) skips masking entirely so the
        # no-delete path stays byte-identical to its pre-deletion programs.
        # Packed 1 bit/docid (little-endian uint32 words, unpacked on the
        # fly by the kernel) — 32x smaller resident than a dense f32 mask
        dead = eng.index.tombstones
        if dead:
            al = np.zeros(self._doc_cap + 1, bool)
            al[1:N + 1] = True
            al[np.fromiter(dead, np.int64, count=len(dead))] = False
            bits = np.packbits(al, bitorder="little")
            if bits.nbytes % 4:
                bits = np.pad(bits, (0, 4 - bits.nbytes % 4))
            self._alive = jnp.asarray(bits.view(np.uint32))
        else:
            self._alive = None
        if stats is None:
            self._n_stat = jnp.int32(N)
            self._avg_stat = None
        else:
            self._n_stat = jnp.int32(stats.num_docs)
            self._avg_stat = jnp.float32(stats.avg_doclen)
        self._synced_version = eng.version
        eng.stats_counters.delta_refreshes += 1

    @property
    def delta_blocks(self) -> int:
        """Live delta size in blocks (the auto-collation signal)."""
        if self._delta is None:
            return 0
        return int(self._delta.term_nblk.sum())

    @property
    def images(self):
        """The resident (frozen, delta) pair the fused kernel merges."""
        return (self._frozen, self._delta)

    @property
    def max_blocks(self) -> tuple:
        """Per-image chain caps, aligned with :attr:`images` — the delta
        suffix keeps its own (small) cap so its decode tile stays tiny."""
        return (self._frozen_mb, self._delta_mb)


def fused_execute(engine, resident: ResidentImageManager,
                  batch: list[Query], mode: str, k: int, *, flavor: str,
                  name: str, interpret: bool | None = None
                  ) -> list[QueryResult]:
    """Answer one (mode, k) query group with a single fused launch over the
    resident images.  Shared by the device (flavor="ref") and pallas
    (flavor="pallas") backends — identical math, one resident state.
    ``interpret`` applies to the pallas flavour only.  Its stages are the
    spans ``repro.device.prep``, ``.launch``, ``.wait`` (for the program
    to finish), ``.readback`` (the pull of its results) and ``.unpack``."""
    import jax
    spans = engine.spans
    results = [QueryResult(np.zeros(0, np.int64),
                           None if mode == "conjunctive"
                           else np.zeros(0, np.float64), name)
               for _ in batch]
    with spans.span("repro.device.prep", mode=mode):
        prep = _prep_launch(engine, resident, batch, mode)
    if prep is None:
        return results
    live, qt, qm, caps, owned = prep
    frozen, delta = resident.images
    key = (mode, k, int(qt.shape[0]), int(qt.shape[1]), caps,
           int(frozen.blocks.shape[0]), int(delta.blocks.shape[0]),
           int(frozen.term_slot.shape[0]), int(frozen.num_docs),
           resident._alive is not None)
    new_shape = key not in resident._bucket_ix
    if new_shape:
        resident._bucket_ix[key] = len(resident._bucket_ix)
    spec = registry.get("fused_query")
    with spans.span("repro.device.launch", mode=mode,
                    bucket=resident._bucket_ix[key],
                    new_shape=int(new_shape)):
        out = spec.fn(resident.images, qt, qm, mode=mode, k=k,
                      max_blocks=caps,
                      doclens=resident._doclens if mode == "bm25" else None,
                      n_stat=resident._n_stat, avg_stat=resident._avg_stat,
                      alive=resident._alive, flavor=flavor,
                      interpret=interpret)
    resident.batches_served += 1
    resident.launch_buckets[key] += 1
    engine.stats_counters.pool_slots += sum(caps)
    engine.stats_counters.pool_blocks += owned
    with spans.span("repro.device.wait", mode=mode):
        jax.block_until_ready(out)
    N = engine.index.num_docs
    if mode == "conjunctive":
        with spans.span("repro.device.readback", mode=mode):
            matches = np.asarray(out)
        with spans.span("repro.device.unpack", mode=mode):
            for row, i in enumerate(live):
                d = np.flatnonzero(matches[row, 1:]) + 1
                results[i] = QueryResult(d[d <= N].astype(np.int64), None,
                                         name)
        return results
    with spans.span("repro.device.readback", mode=mode):
        alld, alls = np.asarray(out[0]), np.asarray(out[1])
    with spans.span("repro.device.unpack", mode=mode):
        for row, i in enumerate(live):
            d, s = alld[row], alls[row]
            keep = (s > 0) & (d > 0)   # already in canonical order (top_k)
            results[i] = QueryResult(d[keep].astype(np.int64),
                                     s[keep].astype(np.float64), name)
    return results


def _prep_launch(eng, resident: ResidentImageManager, batch: list[Query],
                 mode: str):
    """Host prep of one launch: term ids, the padded ``qt``/``qm`` rows
    (uploaded), the per-image flat pool sizes and the chain blocks the
    launch owns over both images.  None when no query of the batch needs
    the device."""
    import jax.numpy as jnp
    # term-id resolution; conjunctive queries with an unknown term are
    # decided (empty) without touching the device
    tids: list[list[int] | None] = []
    for q in batch:
        ids = [eng.term_id(t) for t in q.terms]
        if mode == "conjunctive" and (None in ids or not ids):
            tids.append(None)
        else:
            tids.append([i for i in ids if i is not None])
    live = [i for i, ids in enumerate(tids) if ids]
    if not live:
        return None
    Qn = _pow2(len(live))
    T = _pow2(max(len(tids[i]) for i in live), floor=4)
    qt = np.zeros((Qn, T), np.int32)
    qm = np.zeros((Qn, T), bool)
    for row, i in enumerate(live):
        ids = tids[i]
        qt[row, :len(ids)] = ids
        qm[row, :len(ids)] = True
    qt, qm = jnp.asarray(qt), jnp.asarray(qm)
    if resident._nblk_np is None:
        resident.refresh()
    # flat pool size per image: pow2 of the chain blocks the launch's
    # queries own there together (pow2-bucketed so steady-state traffic
    # reuses compiled programs)
    ids = np.fromiter((t for i in live for t in tids[i]), np.int64)
    owned = [int(nblk[ids[ids < nblk.shape[0]]].sum())
             for nblk in resident._nblk_np]
    caps = tuple(_pow2(n, floor=8) for n in owned)
    return live, qt, qm, caps, sum(owned)


class DeviceBackend(Backend):
    """Oracle flavour of the fused device path (``flavor="ref"``): the same
    single-launch decode→score→top-k math as the Pallas kernel, run as
    plain XLA.  ``use_fused=False`` falls back to the legacy two-launch
    ``query_step`` + host-side merge (kept for differential testing)."""

    name = "device"

    def __init__(self, engine, decode_fn=None,
                 resident: ResidentImageManager | None = None,
                 use_fused: bool = True):
        super().__init__(engine)
        self.resident = resident if resident is not None \
            else ResidentImageManager(engine, decode_fn=decode_fn)
        self.use_fused = use_fused

    # lifecycle delegation (compat: Engine/benchmarks drive these here)
    def freeze(self) -> None:
        self.resident.freeze()

    def refresh(self) -> bool:
        return self.resident.refresh()

    @property
    def delta_blocks(self) -> int:
        return self.resident.delta_blocks

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def execute(self, query: Query) -> QueryResult:
        return self.execute_many([query])[0]

    def execute_many(self, queries: list[Query]) -> list[QueryResult]:
        if any(q.mode in POSITIONAL_MODES for q in queries):
            raise UnsupportedQueryError(
                "DeviceBackend does not implement positional query modes")
        self.resident.refresh()
        out: list[QueryResult | None] = [None] * len(queries)
        groups: dict[tuple[str, int], list[int]] = {}
        for i, q in enumerate(queries):
            groups.setdefault((q.mode, q.k), []).append(i)
        for (mode, k), idxs in groups.items():
            batch = [queries[i] for i in idxs]
            if self.use_fused:
                res = fused_execute(self.engine, self.resident, batch, mode,
                                    k, flavor="ref", name=self.name)
            else:
                res = self._run_group_split(batch, mode, k)
            for i, r in zip(idxs, res):
                out[i] = r
        return out  # type: ignore[return-value]

    def _run_group_split(self, batch: list[Query], mode: str,
                         k: int) -> list[QueryResult]:
        """Legacy path: one ``query_step`` per image, merged host-side."""
        import jax.numpy as jnp
        eng = self.engine
        mgr = self.resident
        if eng.index.tombstones:
            # per-image top-k truncation happens BEFORE any tombstone mask
            # could apply, so a dead doc can evict a live one from an
            # image's k; the fused path masks inside the accumulator —
            # delegate to it whenever deletes are outstanding
            return fused_execute(eng, mgr, batch, mode, k, flavor="ref",
                                 name=self.name)
        N = eng.index.num_docs
        tids: list[list[int] | None] = []
        for q in batch:
            ids = [eng.term_id(t) for t in q.terms]
            if mode == "conjunctive" and (None in ids or not ids):
                tids.append(None)
            else:
                tids.append([i for i in ids if i is not None])
        live = [i for i, ids in enumerate(tids) if ids]
        results = [QueryResult(np.zeros(0, np.int64),
                               None if mode == "conjunctive"
                               else np.zeros(0, np.float64), self.name)
                   for _ in batch]
        if not live:
            return results
        Qn = _pow2(len(live))
        T = _pow2(max(len(tids[i]) for i in live), floor=4)
        qt = np.zeros((Qn, T), np.int32)
        qm = np.zeros((Qn, T), bool)
        for row, i in enumerate(live):
            ids = tids[i]
            qt[row, :len(ids)] = ids
            qm[row, :len(ids)] = True
        qt, qm = jnp.asarray(qt), jnp.asarray(qm)
        kw = dict(max_blocks=mgr._frozen_mb, decode_fn=mgr.decode_fn,
                  n_stat=mgr._n_stat, avg_stat=mgr._avg_stat)
        kwd = dict(kw, max_blocks=mgr._delta_mb)
        if mode == "conjunctive":
            mf, _ = query_step(mgr._frozen, qt, qm, k=1,
                               mode="conjunctive", **kw)
            md, _ = query_step(mgr._delta, qt, qm, k=1,
                               mode="conjunctive", **kwd)
            matches = np.asarray(mf) | np.asarray(md)
            for row, i in enumerate(live):
                d = np.flatnonzero(matches[row]) + 1
                results[i] = QueryResult(d[d <= N].astype(np.int64), None,
                                         self.name)
            return results
        qmode = "bm25" if mode == "bm25" else "ranked"
        dl = mgr._doclens if mode == "bm25" else None
        df, sf = query_step(mgr._frozen, qt, qm, k=k, mode=qmode,
                            doclens=dl, **kw)
        dd, sd = query_step(mgr._delta, qt, qm, k=k, mode=qmode,
                            doclens=dl, **kwd)
        alld = np.concatenate([np.asarray(df), np.asarray(dd)], axis=1)
        alls = np.concatenate([np.asarray(sf), np.asarray(sd)], axis=1)
        for row, i in enumerate(live):
            d, s = alld[row], alls[row]
            keep = (s > 0) & (d > 0)
            d, s = d[keep], s[keep]
            order = np.argsort(-s, kind="stable")[:k]
            results[i] = QueryResult(d[order].astype(np.int64),
                                     s[order].astype(np.float64), self.name)
        return results


def _empty_image(engine) -> DeviceIndex:
    """A zero-term frozen image (pre-first-collation state)."""
    import jax.numpy as jnp
    B = engine.index.store.B
    z = jnp.zeros(0, jnp.int32)
    return DeviceIndex(blocks=jnp.zeros((1, B), jnp.uint8), term_slot=z,
                       term_nblk=z, term_skip=z, term_nx=z, term_ft=z,
                       num_docs=0, F=engine.index.F)
