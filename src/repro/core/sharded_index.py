"""Distributed immediate-access index: document-partitioned shard_map query.

This realizes the paper's Figure 2 at datacenter scale.  Each device owns one
*dynamic sub-shard* (a collated device image of its slice of the document
stream); ingest is a host-side concern (one writer per shard); queries fan
out to every shard and the per-shard top-k results are fused:

  mesh axes:  "data" (and "pod" when multi-pod) partition the document space;
              "model" partitions the query batch.

  query:      replicated over data/pod, sharded over model
  index:      sharded over (pod, data), replicated over model
  execution:  local decode+score (device_index.query_step)
              -> local top-k
              -> all_gather over (pod, data)
              -> merge top-k            (the paper's "results fused")

Conjunctive queries need no merge at all (docid spaces are disjoint): the
local hit bitmaps concatenate, so the collective is a pure reshard.

Local docids are 1..N_shard; global ids are formed inside the mapped
function as ``doc_offset[shard] + local``, where the offsets are the
exclusive prefix sum of the shards' own document counts
(:func:`shard_doc_offsets`) — exact even when shard sizes diverge.

Two layers live here:

  * the jitted ``shard_map`` query step below (device-mesh execution of one
    fused program across TPU shards), and
  * :class:`ShardedEngine` — the host-level fan-out that owns one
    ``repro.engine.Engine`` per shard and routes ``execute_many`` through
    the same unified engine API, so every shard independently plans
    host/device/Pallas execution and keeps its own frozen+delta device
    image fresh.
"""

from __future__ import annotations

import contextvars
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs import SpanTable, merge
from .device_index import DeviceIndex, decode_blocks, query_step


def stack_images(images: list[DeviceIndex]) -> DeviceIndex:
    """Concatenate per-shard images along a leading shard axis.

    All shards must share (V, B) and are padded to the max block count.
    ``num_docs`` of the stacked image is the TOTAL collection size (the sum
    over shards — it is a collection statistic, not a per-shard capacity;
    the per-shard docid capacity is the ``num_docs`` argument of
    :func:`make_sharded_query_step`, and per-shard rank offsets come from
    :func:`shard_doc_offsets`, so shards of unequal size globalize
    correctly).
    """
    nb = max(int(im.blocks.shape[0]) for im in images)
    B = images[0].blocks.shape[1]

    def padb(x):
        return jnp.pad(x, ((0, nb - x.shape[0]), (0, 0)))

    return DeviceIndex(
        blocks=jnp.concatenate([padb(im.blocks) for im in images]),
        term_slot=jnp.concatenate([im.term_slot for im in images]),
        term_nblk=jnp.concatenate([im.term_nblk for im in images]),
        term_skip=jnp.concatenate([im.term_skip for im in images]),
        term_nx=jnp.concatenate([im.term_nx for im in images]),
        term_ft=jnp.concatenate([im.term_ft for im in images]),
        num_docs=sum(im.num_docs for im in images),
        F=images[0].F)


def shard_doc_offsets(images: list[DeviceIndex]) -> "jnp.ndarray":
    """Per-shard global-docid offsets: shard i's local docid d maps to
    ``offsets[i] + d``.  Built from each shard's OWN ``num_docs`` (an
    exclusive prefix sum), so shards of different sizes pack the global
    docid space contiguously — a uniform ``rank * max(num_docs)`` stride
    would leave holes and, worse, disagree with any host-side mapping that
    concatenates the shard collections."""
    sizes = [int(im.num_docs) for im in images]
    off = [0]
    for s in sizes[:-1]:
        off.append(off[-1] + s)
    return jnp.asarray(off, dtype=jnp.int32)


def make_sharded_query_step(mesh, *, k: int = 10, max_blocks: int = 64,
                            num_docs: int = 1 << 20, F: int = 4,
                            decode_fn=None, mode: str = "ranked"):
    """Build the jitted sharded query step for ``mesh``.

    Index arrays are sharded over the document axes ("pod","data"), the query
    batch over "model".  Returns (fn, in_shardings, out_shardings) ready for
    ``jax.jit(...).lower()`` — launch/dryrun.py lowers exactly this.  The
    mapped function takes the six image arrays explicitly (pytree aux fields
    cannot carry shardings) plus the per-shard global-docid offsets
    (:func:`shard_doc_offsets` — each shard reads its OWN offset, so shards
    of unequal document count globalize exactly):
    fn(blocks, slot, nblk, skip, nx, ft, doc_offsets, qt, qm).

    ``num_docs`` is both the per-shard docid CAPACITY (accumulators are
    sized by it; every shard's local docids must fit) and the N the mapped
    scorer weights idf with.  For exact global ranked statistics, rebase
    each shard's ``term_ft`` to the collection-wide document frequencies
    via :func:`~repro.core.device_index.with_global_stats` — KEEPING each
    image's shard-local ``num_docs`` (``shard_doc_offsets`` prefix-sums it,
    so overwriting it with the global N corrupts every offset) — and pass
    the collection total as THIS function's ``num_docs``
    (tests/test_sharded_index.py's unequal-shard test is the reference
    recipe).  Shard-local ``term_ft`` gives the standard
    document-partitioned idf approximation instead, not a merge-exact
    score.
    """
    doc_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    img_specs = (P(doc_axes, None), P(doc_axes), P(doc_axes), P(doc_axes),
                 P(doc_axes), P(doc_axes))
    off_spec = P(doc_axes)
    q_spec = P("model", None)

    if mode == "conjunctive":
        # Boolean AND needs no score fusion at all: docid spaces are
        # disjoint, so the per-shard hit bitmaps simply tile the global
        # docid axis — output stays sharded (model x doc-axes), zero
        # cross-shard traffic beyond the replicated query broadcast.
        def fn_conj(blocks, slot, nblk, skip, nx, ft, offs, qterms, qmask):
            image = DeviceIndex(blocks, slot, nblk, skip, nx, ft,
                                num_docs=num_docs, F=F)
            matches, counts = query_step(
                image, qterms, qmask, k=k, mode="conjunctive",
                max_blocks=max_blocks, decode_fn=decode_fn)
            total = counts
            for ax in doc_axes:
                total = jax.lax.psum(total, ax)
            return matches, total

        in_specs = img_specs + (off_spec, q_spec, q_spec)
        out_specs = (P("model", doc_axes), P("model"))
        mapped = jax.shard_map(fn_conj, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        in_sharding = tuple(jax.NamedSharding(mesh, s) for s in in_specs)
        out_sharding = tuple(jax.NamedSharding(mesh, s) for s in out_specs)
        return mapped, in_sharding, out_sharding

    def fn(blocks, slot, nblk, skip, nx, ft, offs, qterms, qmask):
        image = DeviceIndex(blocks, slot, nblk, skip, nx, ft,
                            num_docs=num_docs, F=F)
        local_d, local_s = query_step(
            image, qterms, qmask, k=k, mode=mode,
            max_blocks=max_blocks, decode_fn=decode_fn)
        # globalize docids by this shard's own offset (exclusive prefix sum
        # of the preceding shards' num_docs — NOT a uniform rank stride,
        # which would misplace docids the moment shard sizes diverge)
        global_d = jnp.where(local_d > 0, local_d + offs[0], 0)
        # fuse: all-gather the per-shard top-k and re-select
        gs = local_s
        gd = global_d
        for ax in doc_axes:
            gs = jax.lax.all_gather(gs, ax, axis=0, tiled=False)
            gd = jax.lax.all_gather(gd, ax, axis=0, tiled=False)
        gs = gs.reshape(-1, local_s.shape[-2], k)    # (S, Qloc, k)
        gd = gd.reshape(-1, local_d.shape[-2], k)
        gs = jnp.moveaxis(gs, 0, 1).reshape(local_s.shape[-2], -1)
        gd = jnp.moveaxis(gd, 0, 1).reshape(local_d.shape[-2], -1)
        top_s, pos = jax.lax.top_k(gs, k)
        top_d = jnp.take_along_axis(gd, pos, axis=1)
        return top_d, top_s

    # NB: shard_map requires explicit specs for every input leaf
    in_specs = img_specs + (off_spec, q_spec, q_spec)
    out_specs = (P("model", None), P("model", None))
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    in_sharding = tuple(jax.NamedSharding(mesh, s) for s in in_specs)
    out_sharding = tuple(jax.NamedSharding(mesh, s) for s in out_specs)
    return mapped, in_sharding, out_sharding


def sharded_input_specs(mesh, *, shard_blocks: int, B: int = 64,
                        vocab: int = 1 << 17, qbatch: int = 256,
                        qterms: int = 8, num_docs: int = 1 << 20):
    """ShapeDtypeStruct stand-ins for the sharded query step (dry-run)."""
    nshards = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            nshards *= mesh.shape[ax]
    meta = jax.ShapeDtypeStruct((nshards * vocab,), jnp.int32)
    offs = jax.ShapeDtypeStruct((nshards,), jnp.int32)
    q = jax.ShapeDtypeStruct((qbatch, qterms), jnp.int32)
    m = jax.ShapeDtypeStruct((qbatch, qterms), jnp.bool_)
    return (jax.ShapeDtypeStruct((nshards * shard_blocks, B), jnp.uint8),
            meta, meta, meta, meta, meta, offs, q, m)


# --------------------------------------------------------------------------
# host-level shard fan-out through the unified engine
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class _FleetCounts:
    """Fleet-wide ingest counters, published as ONE immutable snapshot so a
    pool-thread reader (ranked scoring mid-fan-out) always sees a mutually
    consistent (version, N, total_tokens) triple — three separate counter
    fields could be observed mid-update between stores."""

    version: int        # bumps per ingested/deleted document (cache key)
    num_docs: int       # docid HORIZON (includes tombstoned — round-robin
    #                     assignment arithmetic must never renumber)
    total_tokens: int   # LIVE token total (decremented at delete)
    deleted_docs: int = 0   # tombstoned fleet-wide (live N = num_docs - this)


class ShardedEngine:
    """Document-partitioned fan-out of per-shard query engines — a
    first-class Engine: exact, parallel, and freeze-coordinated.

    Documents are assigned round-robin; each shard runs a full
    ``repro.engine.Engine`` (its planner may independently pick host,
    device, Pallas, or tiered execution, and its device image refreshes
    incrementally — each shard owns a
    :class:`~repro.engine.device_backend.ResidentImageManager`, so its
    frozen block array uploads once per shard freeze and batched fan-out
    queries reuse the per-shard resident images across flushes).  Queries
    fan out to every shard — on a thread pool, so fan-out wall-clock is
    the max over shards, not the sum — and results fuse:

      * boolean modes (conjunctive / phrase / proximity) — per-shard docid
        lists are globalized and concatenated (docid spaces are disjoint,
        no dedup needed);
      * ranked modes — per-shard top-k lists merge under the canonical tie
        order (higher score, then lower global docid).

    **Docid arithmetic** — round-robin assignment is pure arithmetic, no
    per-document maps: global docid ``g`` lives on shard ``(g-1) % S`` as
    local docid ``(g-1) // S + 1``; local ``l`` on shard ``s`` globalizes
    to ``(l-1)*S + s + 1``.  Globalization is one vectorized affine map and
    the engine carries O(1) routing state regardless of collection size.
    The map is strictly monotone per shard, so per-shard canonical tie
    order IS global canonical tie order — which is what makes the top-k
    merge exact at tied boundaries.

    **Exact global ranked statistics** — the fan-out maintains the
    collection-wide document frequencies, N, and total token count at
    ingest and hands every shard a :class:`~repro.core.query.
    CollectionStats` provider (the same rebasing seam the device
    frozen+delta path uses).  Shards therefore weight postings with exactly
    the numbers a single-engine oracle over the full stream would use, and
    the merged top-k is byte-identical to that oracle (same doubles, same
    canonical tie order) — no shard-local IDF approximation remains.

    **Coordinated freezes** — per-shard static-tier lifecycles register
    with one :class:`~repro.core.lifecycle.FreezeCoordinator`; at most
    ``max_in_flight`` background encodes run fleet-wide, and refused
    shards retry on any later fleet ingest (every queued shard is pumped
    per ingest — see the coordinator docstring) or via
    :meth:`drain_freezes`.

    **Serving integration** — ``version`` (bumps per ingested document) and
    ``lifecycle.epoch`` (composite tier epoch, bumps on any shard's swap)
    give ``serve.QueryService`` the same cache-key components a single
    engine exposes, so result caching and invalidation work unchanged.
    """

    def __init__(self, num_shards: int = 2, engine_factory=None,
                 max_in_flight: int = 1, parallel: bool = True,
                 **engine_kwargs):
        from ..engine import Engine
        from .lifecycle import FreezeCoordinator
        if engine_factory is None:
            def engine_factory():
                return Engine(**engine_kwargs)
        self.engines = [engine_factory() for _ in range(num_shards)]
        self.num_shards = len(self.engines)
        self._counts = _FleetCounts(0, 0, 0)            # published
        # term -> global DOCUMENT frequency
        self._ft: dict[bytes, int] = {}                 # gil_shared
        # per-shard global-f_t arrays aligned to each shard's term ids
        # (keyed by the identity of the engine's append-only vocab list),
        # value-updated incrementally at ingest and suffix-extended at read
        # time — a device-image refresh never re-walks the vocabulary
        self._gft_cache: dict[int, "np.ndarray"] = {}   # gil_shared
        # every shard scores with the fleet's collection-wide statistics
        for e in self.engines:
            e.stats_provider = self.collection_stats
        # fleet freeze scheduling: one coordinator owns every shard lifecycle
        self.coordinator = FreezeCoordinator(max_in_flight=max_in_flight)
        for e in self.engines:
            if getattr(e, "lifecycle", None) is not None:
                self.coordinator.register(e.lifecycle)
        # fleet-level spans (a QueryService flush over the fleet); every
        # shard engine keeps its own table, and stats() sums them all
        self.spans = SpanTable()
        self._pool = None
        if parallel and self.num_shards > 1:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=self.num_shards,
                thread_name_prefix="shard-fanout")

    def close(self) -> None:
        """Release the fan-out thread pool and join in-flight freezes.
        Idempotent; the engine degrades to serial fan-out afterwards —
        transient fleets (benchmarks, resize/rebuild cycles) should close
        rather than leak ``num_shards`` worker threads until exit."""
        for e in self.engines:
            if getattr(e, "lifecycle", None) is not None:
                e.lifecycle.wait()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self) -> "ShardedEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    # collection statistics (the exactness seam)
    # ------------------------------------------------------------------

    def collection_stats(self):
        """Fleet-wide (N, avg doclen, f_t) — what every ranked scorer and
        device-image refresh rebases with.  ``avg`` is total tokens over N,
        which equals the oracle's ``doclens[1:N+1].mean()`` bit-for-bit
        (integer sums below 2**53 are exact in float64)."""
        from .query import CollectionStats
        c = self._counts
        live = c.num_docs - c.deleted_docs
        return CollectionStats(
            num_docs=live,
            avg_doclen=c.total_tokens / live if live else 0.0,
            ft=self._ft,
            fts_cache=self._gft_cache)

    @property
    def version(self) -> int:
        """Bumps per ingested document (serving cache-key component)."""
        return self._counts.version

    @property
    def num_docs(self) -> int:
        return self._counts.num_docs

    @property
    def deleted_docs(self) -> int:
        return self._counts.deleted_docs

    @property
    def num_postings(self) -> int:
        return sum(e.index.num_postings for e in self.engines)

    @property
    def lifecycle(self):
        """The fleet coordinator: exposes the composite ``epoch`` the
        serving cache keys on (duck-compatible with a single engine's
        ``FreezeManager`` for that purpose)."""
        return self.coordinator

    # ------------------------------------------------------------------
    # ingest
    # ------------------------------------------------------------------

    def add_document(self, terms) -> int:
        """Ingest one document (single front-door thread — queries and
        ingest are serialized by the caller, the same one-writer model as
        ``Engine``/``QueryService``; the fan-out pool is only ever busy
        INSIDE ``execute_many``, never concurrently with an ingest)."""
        c = self._counts
        g = c.num_docs + 1
        shard = (g - 1) % self.num_shards
        # global stats BEFORE the shard ingest, so the maybe_freeze hooks
        # that fire inside it already see statistics covering this doc
        tbs = [t.encode() if isinstance(t, str) else t for t in terms]
        # resolve each shard's materialized aligned-f_t array once per doc
        # (most fleets have none until a device query materializes them)
        live = [(e._tid, arr) for e in self.engines
                if (arr := self._gft_cache.get(id(e.vocab))) is not None]
        for tb in dict.fromkeys(tbs):
            df = self._ft.get(tb, 0) + 1
            self._ft[tb] = df
            # keep the materialized per-shard aligned f_t arrays current
            # (terms a shard interns later are picked up by the suffix
            # extension in CollectionStats.fts_for)
            for tid_map, arr in live:
                tid = tid_map.get(tb)
                if tid is not None and tid < len(arr):
                    arr[tid] = df
        self._counts = _FleetCounts(c.version + 1, g,
                                    c.total_tokens + len(terms),
                                    c.deleted_docs)
        local = self.engines[shard].add_document(terms)
        assert local == (g - 1) // self.num_shards + 1
        # a global ingest changes every shard's scoring state (N, f_t, avg
        # all moved): bump the non-owner versions too so their device
        # images re-rebase statistics on the next refresh
        for s, e in enumerate(self.engines):
            if s != shard:
                e.version += 1
        # pump deferred freezes fleet-wide: the fleet shares ONE writer
        # thread (this method), so a shard whose encode-slot request was
        # refused may retry on ANY ingest — not only its own — which keeps
        # the coordinator's FIFO live even if routing ever skews away from
        # the queue head
        if self.coordinator.pending:
            for s, e in enumerate(self.engines):
                if s != shard and getattr(e, "lifecycle", None) is not None:
                    e.lifecycle.maybe_freeze()
        return g

    @property
    def word_level(self) -> bool:
        return self.engines[0].index.word_level

    def route_batch(self, prepared):
        """Assign global docids round-robin and update the fleet-wide
        statistics for a whole batch of
        :class:`~repro.core.prepare.PreparedDoc` records — WITHOUT touching
        any shard engine.  Returns ``(gids, per_shard, extra_bumps)``:

          * ``gids`` — the global docids, in submission order;
          * ``per_shard[s]`` — the sub-batch shard ``s`` owns, in local
            docid order (round-robin arithmetic: global ``g`` lands on
            shard ``(g-1) % S`` as local ``(g-1)//S + 1``);
          * ``extra_bumps[s]`` — the number of batch documents shard ``s``
            does NOT own.  A global ingest changes every shard's scoring
            state (N, f_t, avgdl all move), so each shard's version must
            advance by the FULL batch size: its own ingest bumps it by
            ``len(per_shard[s])``, and whoever applies the sub-batch adds
            ``extra_bumps[s]`` on top.  Splitting it this way keeps each
            shard engine's ``version`` written by exactly one thread in
            the pipelined path (its writer), never the router.

        This is the router half of the pipelined write path
        (``serve.ingest_pipeline``): it runs on the submitting thread —
        fleet counters and the global df map stay single-writer — while
        per-shard writer threads apply the returned sub-batches.  Global
        statistics are published BEFORE any shard ingest (one
        ``_FleetCounts`` store), so freeze hooks firing inside a shard's
        apply already see statistics covering the whole batch — the same
        order ``add_document`` uses.
        """
        c = self._counts
        S = self.num_shards
        base = c.num_docs
        gids = list(range(base + 1, base + len(prepared) + 1))
        per_shard: list[list] = [[] for _ in range(S)]
        df_delta: dict[bytes, int] = {}
        tokens = 0
        for i, p in enumerate(prepared):
            per_shard[(base + i) % S].append(p)
            tokens += p.doclen
            for tb in p.uniq:
                df_delta[tb] = df_delta.get(tb, 0) + 1
        live = [(e._tid, arr) for e in self.engines
                if (arr := self._gft_cache.get(id(e.vocab))) is not None]
        for tb, dd in df_delta.items():
            df = self._ft.get(tb, 0) + dd
            self._ft[tb] = df
            for tid_map, arr in live:
                tid = tid_map.get(tb)
                if tid is not None and tid < len(arr):
                    arr[tid] = df
        self._counts = _FleetCounts(c.version + len(prepared),
                                    base + len(prepared),
                                    c.total_tokens + tokens,
                                    c.deleted_docs)
        extra = [len(prepared) - len(per_shard[s]) for s in range(S)]
        return gids, per_shard, extra

    def add_documents(self, docs) -> list[int]:
        """Batched fleet ingest (synchronous: same single front-door
        thread model as ``add_document``; the pipelined variant lives in
        ``serve.ingest_pipeline``).  Answer-identical to a per-document
        loop — same global docids, same fleet statistics, same per-shard
        chains."""
        from .prepare import prepare_batch
        prepared = prepare_batch(docs, self.word_level)
        gids, per_shard, extra = self.route_batch(prepared)
        for s, e in enumerate(self.engines):
            if per_shard[s]:
                e.add_documents(per_shard[s])
            if extra[s]:
                e.version += extra[s]
        # pump deferred freezes fleet-wide (see add_document): every queued
        # shard may retry on any ingest
        if self.coordinator.pending:
            for e in self.engines:
                if getattr(e, "lifecycle", None) is not None:
                    e.lifecycle.maybe_freeze()
        return gids

    def delete_document(self, docid: int) -> None:
        """Tombstone one document fleet-wide (same single-writer model as
        ``add_document``).  The global docid routes to its owner shard by
        the round-robin arithmetic — no per-document map — and the owner's
        returned ``(tid, occurrences)`` pairs mirror the document-frequency
        decrements into the fleet's global ``_ft`` (and every materialized
        per-shard aligned f_t array), so every shard immediately scores
        with statistics of a collection that never held the document."""
        c = self._counts
        if not 1 <= docid <= c.num_docs:
            raise ValueError(f"docid {docid} out of range 1..{c.num_docs}")
        shard = (docid - 1) % self.num_shards
        local = (docid - 1) // self.num_shards + 1
        eng = self.engines[shard]
        doclen = eng._doclens[local]
        entry = eng.delete_document(local)  # raises on double delete
        live = [(e._tid, arr) for e in self.engines
                if (arr := self._gft_cache.get(id(e.vocab))) is not None]
        for tid, _occ in entry:
            tb = eng.vocab[tid]
            df = self._ft.get(tb, 0) - 1
            self._ft[tb] = df
            for tid_map, arr in live:
                t = tid_map.get(tb)
                if t is not None and t < len(arr):
                    arr[t] = df
        # horizon stays put (docid arithmetic is append-only); live token
        # total and the tombstone count move — published as ONE snapshot
        self._counts = _FleetCounts(c.version + 1, c.num_docs,
                                    c.total_tokens - doclen,
                                    c.deleted_docs + 1)
        # a delete changes every shard's scoring state (N, f_t, avg): bump
        # the non-owner versions so their device images re-rebase
        for s, e in enumerate(self.engines):
            if s != shard:
                e.version += 1

    def update_document(self, docid: int, terms) -> int:
        """Atomic-from-the-caller's-view revision: tombstone ``docid`` and
        ingest ``terms`` as a NEW document (new global docid, returned) —
        the same delete+add semantics as ``Engine.update_document``."""
        self.delete_document(docid)
        return self.add_document(terms)

    def collate_now(self) -> None:
        for e in self.engines:
            e.collate_now()

    def drain_freezes(self) -> None:
        """Run every due-or-deferred freeze to completion (tests, shutdown,
        bulk-load tails).  No ingest may run concurrently — this pumps the
        writer-thread side of deferred freezes that would otherwise wait
        for the next document.  Bails out (rather than spinning) if an
        epoch fails to advance — a crashed encode thread must not wedge
        shutdown."""
        mgrs = [e.lifecycle for e in self.engines
                if getattr(e, "lifecycle", None) is not None]
        while True:
            for m in mgrs:
                m.wait()
            before = [m.epoch for m in mgrs]
            if not any([m.maybe_freeze() for m in mgrs]):
                break
            for m in mgrs:
                m.wait()
            if [m.epoch for m in mgrs] == before:
                break
        for m in mgrs:
            m.wait()

    # ------------------------------------------------------------------
    # query fan-out
    # ------------------------------------------------------------------

    def execute(self, query):
        return self.execute_many([query])[0]

    def _globalize(self, shard: int, docids) -> "np.ndarray":
        """Vectorized round-robin globalization: (l-1)*S + shard + 1."""
        import numpy as np
        local = np.asarray(docids, dtype=np.int64)
        return (local - 1) * self.num_shards + shard + 1

    def execute_many(self, queries):
        """Fan a batch out to every shard engine (in parallel) and fuse per
        query.  Each shard result's docids are globalized arithmetically;
        the fused ``backend`` reports the SET of backends that actually
        served the shards (e.g. ``"host+tiered"``)."""
        import numpy as np

        from ..engine.types import QueryResult
        if self._pool is not None:
            # each shard runs in a copy of the caller's context, so its
            # spans carry the caller's flush id (repro.obs)
            ctxs = [contextvars.copy_context() for _ in self.engines]
            per_shard = list(self._pool.map(
                lambda e, c: c.run(e.execute_many, queries),
                self.engines, ctxs))
        else:
            per_shard = [e.execute_many(queries) for e in self.engines]
        out = []
        for qi, q in enumerate(queries):
            shard_res = [per_shard[s][qi] for s in range(self.num_shards)]
            backend = "+".join(sorted({r.backend for r in shard_res}))
            reason = f"sharded fan-out x{self.num_shards}"
            gids = np.concatenate([self._globalize(s, r.docids)
                                   for s, r in enumerate(shard_res)])
            if q.mode in ("conjunctive", "phrase", "proximity"):
                out.append(QueryResult(np.sort(gids), None, backend, reason))
            else:
                scores = np.concatenate([r.scores for r in shard_res])
                # canonical ranked tie order across shards: higher score
                # first, then lower GLOBAL docid (not shard arrival order)
                order = np.lexsort((gids, -scores))[:q.k]
                out.append(QueryResult(gids[order], scores[order],
                                       backend, reason))
        return out

    # ------------------------------------------------------------------
    # persistence (core/persist.py)
    # ------------------------------------------------------------------

    def snapshot(self, root: str, *, keep: int = 3,
                 quiesce: bool = False) -> str:
        """Persist the whole fleet under ``root`` — per-shard engine state
        plus the fleet counters and global term statistics, all published
        by ONE atomic rename (shards can never restore torn against each
        other).  Writer thread only.  ``quiesce=True`` joins in-flight
        shard encodes first so every shard's newest tier is captured."""
        from . import persist
        if quiesce:
            for e in self.engines:
                if getattr(e, "lifecycle", None) is not None:
                    e.lifecycle.quiesce()
        return persist.save_sharded(self, root, keep=keep)

    @classmethod
    def restore(cls, path_or_root: str, *, parallel: bool = True,
                max_in_flight: int | None = None,
                **engine_kwargs) -> "ShardedEngine":
        """Rebuild a fleet from a snapshot dir (or the newest under a
        root); per-shard ``engine_kwargs`` forward runtime knobs."""
        from . import persist
        return persist.restore_sharded(path_or_root, parallel=parallel,
                                       max_in_flight=max_in_flight,
                                       **engine_kwargs)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def stats(self):
        """One composite :class:`~repro.engine.types.EngineStats` for the
        fleet (summed counters, merged backend histogram, composite tier
        epoch).  Per-shard detail remains available as
        ``[e.stats() for e in engine.engines]``."""
        from ..engine.types import EngineStats
        agg = EngineStats()
        shard_spans = []
        for e in self.engines:
            s = e.stats()
            shard_spans.append(s.spans)
            agg.deleted_docs += s.deleted_docs
            agg.tombstones_compacted += s.tombstones_compacted
            agg.num_postings += s.num_postings
            agg.num_words += s.num_words
            agg.queries += s.queries
            agg.query_batches += s.query_batches
            agg.query_time_s += s.query_time_s
            agg.ingest_docs += s.ingest_docs
            agg.ingest_batches += s.ingest_batches
            agg.ingest_time_s += s.ingest_time_s
            agg.collations += s.collations
            agg.delta_refreshes += s.delta_refreshes
            agg.delta_compactions += s.delta_compactions
            agg.resident_uploads += s.resident_uploads
            agg.pool_slots += s.pool_slots
            agg.pool_blocks += s.pool_blocks
            agg.freezes += s.freezes
            for k, v in s.by_backend.items():
                agg.by_backend[k] = agg.by_backend.get(k, 0) + v
        agg.spans = merge(self.spans.snapshot(), *shard_spans)
        agg.num_docs = self.num_docs
        agg.vocab_size = len(self._ft)
        agg.tier_epoch = self.coordinator.epoch
        agg.num_shards = self.num_shards
        return agg
