"""Run the index's main path once on a TPU and check every answer.

    python chip_smoke.py                      # one chip, full WSJ1_LIKE
    python chip_smoke.py --docs 20000         # a cut collection
    python chip_smoke.py --chips 4            # the mesh phase, on 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --docs 3000
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --chips 4 --docs 2000

One process, no children.  With one chip it ingests a synthetic
WSJ1-calibrated collection through ``QueryService(Engine(...))``, freezes
it (``collate_now`` uploads the frozen image), answers a log of
conjunctive / ranked_tfidf / bm25 queries on the device, keeps ingesting
without a freeze and queries again (the delta image must serve the new
documents), deletes a few matching documents (they must vanish), and
checks every device answer against the host backend.  With ``--chips 4``
it runs only the document-sharded mesh program (``make_sharded_query_step``
on a (4, 1) mesh) against a single-collection host oracle.

The last line of standard output is ``{"ok": true, "device": {...}}`` only
when every check passed on a TPU.  Without a TPU the script exits non-zero
at once, except under ``--rehearse``, which runs every phase on the CPU at a
small ``--docs`` and never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RTOL = 1e-5                 # device f32 vs host f64 scores
BATCH = 32                  # queries per QueryService flush
N_QUERIES = 288             # 9 single-mode batches of BATCH
MODES = ("conjunctive", "ranked_tfidf", "bm25")
INGEST_BATCH = 4096         # larger batches amortize per-term appends
DEEP_K = 1 << 20            # ranks every document of any --docs here
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check failed; the script exits non-zero and prints no result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time of each named phase, printed as it ends."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        log(f"phase {name}: {dt:.3f} s")


class CompileCounter:
    """Counts XLA backend compiles through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


# ----------------------------------------------------------------------------
# device check
# ----------------------------------------------------------------------------


def device_check(args) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"jax {jax.__version__}; platform {info['platform']}; "
        f"device_kind {info['kind']}; device count {info['count']}")
    if info["platform"] != "tpu" and not args.rehearse:
        raise SmokeFailure(
            f"no TPU: JAX found platform {info['platform']!r} "
            f"({info['kind']}, {info['count']} device(s)); "
            "use --rehearse for a CPU rehearsal")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"found {len(devs)}")
    return info


# ----------------------------------------------------------------------------
# corpus and query log
# ----------------------------------------------------------------------------


def doc_stream(n_docs: int, seed: int):
    """The first ``n_docs`` WSJ1_LIKE documents generated from ``seed``."""
    from repro.data.corpus import WSJ1_LIKE, SyntheticCorpus
    spec = dataclasses.replace(WSJ1_LIKE, n_docs=n_docs, seed=seed)
    return SyntheticCorpus(spec).doc_terms()


def doc_batches(stream, n: int, phases: Phases):
    """The next ``n`` documents of ``stream`` in ingest batches; generation
    time is its own phase ("generate"), not the ingest's."""
    while n:
        take = min(INGEST_BATCH, n)
        t0 = time.perf_counter()
        batch = [next(stream) for _ in range(take)]
        phases.seconds["generate"] = (phases.seconds.get("generate", 0.0)
                                      + time.perf_counter() - t0)
        n -= take
        yield batch


def query_log(index, seed: int):
    """Mid-frequency query log (the benchmarks' rule) in single-mode
    batches of BATCH, modes cycling batch by batch."""
    from benchmarks.common import queries
    from repro.engine import Query
    terms = queries(index, n=N_QUERIES, max_terms=4, seed=seed)
    return [[Query(terms=tuple(str(t) for t in ts),
                   mode=MODES[b % len(MODES)], k=10)
             for ts in terms[b * BATCH:(b + 1) * BATCH]]
            for b in range(N_QUERIES // BATCH)]


# ----------------------------------------------------------------------------
# answers and the oracle
# ----------------------------------------------------------------------------


def serve(svc, batches):
    """Submit each batch through the service (it flushes at BATCH)."""
    out = []
    for batch in batches:
        tickets = [svc.submit(q) for q in batch]
        svc.flush()
        out.append([t.result for t in tickets])
    return out


def rel_diff(a, b) -> float:
    """Largest relative difference of two aligned score vectors."""
    import numpy as np
    if not len(b):
        return 0.0
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _near(a, b) -> bool:
    import numpy as np
    return bool(np.isclose(a, b, rtol=RTOL, atol=0.0))


def same_answer(host_query, q, dev, ref) -> tuple[bool, int]:
    """(equal, k-boundary swaps).  Conjunctive answers must be equal.
    Ranked scores must agree to RTOL position by position; docids must be
    equal except inside a run of scores within RTOL of each other, where
    the run's docid sets must be equal, or, for the run at the k boundary,
    every device docid must score the same on the host (checked with a
    query deep enough to rank every document through ``host_query``)."""
    import numpy as np
    if q.mode == "conjunctive":
        return bool(np.array_equal(dev.docids, ref.docids)), 0
    n = len(ref.docids)
    if len(dev.docids) != n or not np.allclose(dev.scores, ref.scores,
                                               rtol=RTOL, atol=0.0):
        return False, 0
    s = ref.scores
    start, swaps = 0, 0
    for i in range(1, n + 1):
        if i < n and _near(s[i], s[i - 1]):
            continue
        got, want = dev.docids[start:i], ref.docids[start:i]
        if set(got.tolist()) != set(want.tolist()):
            if i < n or n < q.k:
                return False, swaps
            deep = host_query(dataclasses.replace(q, k=DEEP_K,
                                                  backend="host"))
            host = dict(zip(deep.docids.tolist(), deep.scores.tolist()))
            for d, sc in zip(got.tolist(), dev.scores[start:i].tolist()):
                if d not in host or not _near(sc, host[d]):
                    return False, swaps
            swaps += 1
        start = i
    return True, swaps


def check_against_host(svc, batches, results, label: str) -> None:
    host = serve(svc, [[dataclasses.replace(q, backend="host") for q in b]
                       for b in batches])
    bad, swaps, n, worst = [], 0, 0, 0.0
    for batch, dev_b, ref_b in zip(batches, results, host):
        for q, dev, ref in zip(batch, dev_b, ref_b):
            ok, sw = same_answer(svc.query, q, dev, ref)
            swaps += sw
            n += 1
            if q.mode != "conjunctive" and len(dev.scores) == len(ref.scores):
                worst = max(worst, rel_diff(dev.scores, ref.scores))
            if not ok:
                bad.append((q, dev, ref))
    for q, dev, ref in bad[:5]:
        log(f"  MISMATCH {q.mode} {q.terms}: device "
            f"{dev.docids[:10].tolist()} host {ref.docids[:10].tolist()}")
        if q.mode != "conjunctive":
            log(f"    scores device {dev.scores[:10].tolist()} host "
                f"{ref.scores[:10].tolist()}")
    log(f"oracle {label}: {n - len(bad)}/{n} device answers equal to the "
        f"host backend ({swaps} tied k-boundary swaps verified; largest "
        f"relative score difference {worst:.3e}, limit {RTOL:g})")
    check(not bad, f"{len(bad)} device answers differ from the host "
                   f"oracle ({label})")


def backends_of(results) -> dict:
    out: dict[str, int] = {}
    for batch in results:
        for r in batch:
            out[r.backend] = out.get(r.backend, 0) + 1
    return out


def check_device_served(results, label: str) -> None:
    seen = backends_of(results)
    log(f"backends {label}: {seen}")
    check(set(seen) == {"device"},
          f"batched results not all served by the device ({label}): {seen}")


# ----------------------------------------------------------------------------
# one chip: ingest -> freeze -> query -> ingest more -> delete
# ----------------------------------------------------------------------------


def one_chip(args, phases: Phases) -> None:
    import jax
    import numpy as np

    from repro.engine import Engine
    from repro.serve import QueryService

    compiles = CompileCounter()
    n_docs = args.docs
    n_post = 1000
    check(n_docs > 2 * n_post,
          f"--docs {n_docs} leaves too little before the freeze")
    eng = Engine(B=64, growth="const")
    svc = QueryService(eng, max_batch=BATCH, cache_size=0)
    docs = doc_stream(n_docs, args.seed)
    with phases("ingest"):
        for batch in doc_batches(docs, n_docs - n_post, phases):
            svc.ingest_batch(batch)
    with phases("freeze"):
        eng.collate_now()
        eng.resident.refresh()
        jax.block_until_ready(eng.resident.images)
    horizon = eng.index.num_docs
    st = eng.stats()
    log(f"frozen: docs {st.num_docs}, postings {st.num_postings}, "
        f"vocabulary {st.vocab_size}, "
        f"bytes/posting {eng.index.bytes_per_posting():.4f}, "
        f"frozen blocks {int(eng.resident.images[0].blocks.shape[0])}")
    check(st.resident_uploads >= 1, "collate_now uploaded no frozen image")
    batches = query_log(eng.index, args.seed)

    with phases("query"):
        res = serve(svc, batches)
    check_device_served(res, "after freeze")
    with phases("oracle"):
        check_against_host(svc, batches, res, "after freeze")

    with phases("single"):
        singles = [b[0] for b in batches[:len(MODES)]]
        served = set()
        for q in singles:
            r = svc.query(q)
            ref = svc.query(dataclasses.replace(q, backend="host"))
            served.add(r.backend)
            check(r.backend != "pallas", "single query served by pallas")
            check(same_answer(svc.query, q, r, ref)[0],
                  f"single {q.mode} query differs from the host")
        log(f"single-query flushes: {len(singles)} served by "
            f"{sorted(served)}")

    with phases("ingest_post"):
        for batch in doc_batches(docs, n_post, phases):
            svc.ingest_batch(batch)
    with phases("query_delta"):
        res = serve(svc, batches)
    check_device_served(res, "with delta")
    st = eng.stats()
    check(st.collations == 1 and st.delta_compactions == 0,
          "a collation ran after the freeze; the delta was not exercised")
    delta_blocks = eng.resident.delta_blocks
    newer = sorted({int(d) for b in res for r in b for d in r.docids
                    if d > horizon})
    log(f"immediate access: {n_docs - horizon} docs ingested after the "
        f"freeze at docid {horizon}; delta image {delta_blocks} blocks; "
        f"{len(newer)} distinct post-freeze docids in device answers")
    check(delta_blocks > 0, "the delta image is empty")
    check(newer, "no device answer holds a document ingested after the "
                 "freeze")
    with phases("oracle_delta"):
        check_against_host(svc, batches, res, "with delta")

    older = sorted({int(d) for b in res for r in b for d in r.docids
                    if 0 < d <= horizon})
    rng = np.random.default_rng(args.seed)
    victims = sorted(rng.choice(newer, size=min(3, len(newer)),
                                replace=False).tolist()
                     + rng.choice(older, size=min(3, len(older)),
                                  replace=False).tolist())
    for d in victims:
        svc.delete(d)
    with phases("query_deleted"):
        res = serve(svc, batches)
    check_device_served(res, "after deletes")
    left = sorted({int(d) for b in res for r in b for d in r.docids}
                  & set(victims))
    log(f"deleted docids {victims}; still in device answers: {left}")
    check(not left, f"deleted docids {left} still answered by the device")
    with phases("oracle_deleted"):
        check_against_host(svc, batches, res, "after deletes")

    before = compiles.n
    with phases("second_pass"):
        again = serve(svc, batches)
    second = compiles.n - before
    log(f"compiles during the second pass over the same batches: {second} "
        f"(all passes: {compiles.n})")
    check(second == 0, f"{second} compiles during the second pass")
    check(all(np.array_equal(a.docids, b.docids)
              for x, y in zip(again, res) for a, b in zip(x, y)),
          "the second pass answered differently")

    st = eng.stats()
    mgr = eng.resident
    log(f"uploads {st.resident_uploads}; fused batches served "
        f"{mgr.batches_served}; delta refreshes {st.delta_refreshes}; "
        f"queries by backend {st.by_backend}")
    check("pallas" not in st.by_backend, "a query was served by pallas")
    check(mgr.batches_served > 0, "no fused batch was served")
    log(f"compiled launch buckets ({len(mgr.launch_buckets)}), as (mode, k, "
        "Qn, T, packed caps, frozen blocks, delta blocks, vocab cap, doc "
        "cap, liveness mask): launches")
    for key, n in sorted(mgr.launch_buckets.items(), key=str):
        log(f"  {key}: {n}")
    mem = jax.devices()[0].memory_stats()
    log("peak_bytes_in_use "
        + (str(mem["peak_bytes_in_use"]) if mem and "peak_bytes_in_use"
           in mem else "not reported by this backend"))


# ----------------------------------------------------------------------------
# four chips: document-sharded mesh program vs one host collection
# ----------------------------------------------------------------------------


def four_chips(args, phases: Phases) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType

    from repro.core.collate import collate
    from repro.core.device_index import build_device_image, with_global_stats
    from repro.core.index import DynamicIndex
    from repro.core.sharded_index import (make_sharded_query_step,
                                          shard_doc_offsets, stack_images)
    from repro.engine import Engine, Query
    from repro.engine.types import QueryResult

    S = args.chips
    n_docs = args.docs
    bounds = [n_docs * s // S for s in range(S + 1)]
    whole = Engine(B=64, growth="const")
    shards = [DynamicIndex(B=64, growth="const") for _ in range(S)]
    with phases("ingest"):
        for batch in doc_batches(doc_stream(n_docs, args.seed), n_docs,
                                 phases):
            lo = whole.index.num_docs
            whole.add_documents(batch)
            for s in range(S):       # contiguous document ranges
                a, b = max(bounds[s], lo), min(bounds[s + 1], lo + len(batch))
                if a < b:
                    shards[s].add_documents(batch[a - lo:b - lo])
    vocab = whole.vocab
    with phases("build_images"):
        images = [build_device_image(collate(sh), vocab) for sh in shards]
        gft = np.stack([np.asarray(im.term_ft) for im in images]).sum(axis=0)
        images = [with_global_stats(im, gft, im.num_docs) for im in images]
        img = stack_images(images)
        offs = shard_doc_offsets(images)
    sizes = [im.num_docs for im in images]
    log(f"shards: docs {sizes}, offsets {np.asarray(offs).tolist()}, "
        f"blocks/shard {int(img.blocks.shape[0]) // S}, vocabulary "
        f"{len(vocab)}, postings {whole.index.num_postings}")

    from benchmarks.common import queries
    terms = queries(whole.index, n=BATCH, max_terms=4, seed=args.seed)
    T = 4
    qt = np.zeros((BATCH, T), np.int32)
    qm = np.zeros((BATCH, T), bool)
    for i, ts in enumerate(terms):
        ids = [whole.term_id(str(t)) for t in ts]
        qt[i, :len(ids)] = ids
        qm[i, :len(ids)] = True
    nblk = np.stack([np.asarray(im.term_nblk) for im in images])
    mb = 1 << (int(nblk[:, qt[qm]].max()) - 1).bit_length()
    mesh = jax.make_mesh((S, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:S])
    cap = max(sizes)
    total = sum(sizes)
    for mode in ("ranked_sparse", "conjunctive"):
        fn, ins, outs = make_sharded_query_step(
            mesh, k=10, max_blocks=mb, F=img.F, mode=mode,
            num_docs=total if mode != "conjunctive" else cap)
        args_in = [jax.device_put(a, sh) for a, sh in zip(
            (img.blocks, img.term_slot, img.term_nblk, img.term_skip,
             img.term_nx, img.term_ft, offs, jnp.asarray(qt),
             jnp.asarray(qm)), ins)]
        if mode == "ranked_sparse":
            held = [(str(sh.device), sh.index[0].start)
                    for sh in args_in[0].addressable_shards]
            log(f"block array shards (device, first block row): {held}")
            check(len({d for d, _ in held}) == S,
                  f"block array not spread over {S} devices: {held}")
        jf = jax.jit(fn, in_shardings=ins, out_shardings=outs)
        with phases(f"mesh_{mode}"):
            out = jax.block_until_ready(jf(*args_in))
        qmode = "conjunctive" if mode == "conjunctive" else "ranked_tfidf"
        qs = [Query(terms=tuple(str(t) for t in ts), mode=qmode, k=10,
                    backend="host") for ts in terms]
        with phases(f"oracle_{mode}"):
            ref = whole.execute_many(qs)
        bad, worst = 0, 0.0
        offs_h = np.asarray(offs)
        for i, (q, r) in enumerate(zip(qs, ref)):
            if mode == "conjunctive":
                cols = np.flatnonzero(np.asarray(out[0][i]))
                gids = offs_h[cols // cap] + cols % cap + 1
                ok = np.array_equal(np.sort(gids), r.docids) \
                    and int(out[1][i]) == len(r.docids)
            else:
                d, s = np.asarray(out[0][i]), np.asarray(out[1][i])
                keep = np.isfinite(s) & (s > 0) & (d > 0)
                dev = QueryResult(d[keep].astype(np.int64),
                                  s[keep].astype(np.float64), "mesh")
                ok = same_answer(whole.execute, q, dev, r)[0]
                if len(dev.scores) == len(r.scores):
                    worst = max(worst, rel_diff(dev.scores, r.scores))
            if not ok:
                bad += 1
                got = (gids if mode == "conjunctive" else dev.docids)
                log(f"  MISMATCH {mode} {q.terms}: mesh "
                    f"{np.asarray(got)[:10].tolist()} host "
                    f"{r.docids[:10].tolist()}")
                if mode != "conjunctive":
                    log(f"    scores mesh {dev.scores[:10].tolist()} "
                        f"host {r.scores[:10].tolist()}")
        log(f"mesh {mode}: {len(qs) - bad}/{len(qs)} answers equal to the "
            f"single-collection host oracle (max_blocks {mb}"
            + ("" if mode == "conjunctive" else
               f"; largest relative score difference {worst:.3e}, "
               f"limit {RTOL:g}") + ")")
        check(not bad, f"{bad} mesh {mode} answers differ from the oracle")
    for dev in jax.devices()[:S]:
        mem = dev.memory_stats()
        log(f"{dev}: peak_bytes_in_use "
            + (str(mem["peak_bytes_in_use"]) if mem and "peak_bytes_in_use"
               in mem else "not reported by this backend"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--docs", type=int, default=None,
                    help="documents to generate (default: the full "
                         "WSJ1_LIKE collection on one chip, a quarter of "
                         "it across four)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on the CPU; never prints ok")
    args = ap.parse_args()
    if args.rehearse and args.chips > 1 and "device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    from repro.data.corpus import WSJ1_LIKE
    if args.docs is None:
        # four chips: the host ingests the collection twice (shards and
        # oracle) while four chips are held, so take a quarter of it
        args.docs = (WSJ1_LIKE.n_docs if args.chips == 1
                     else WSJ1_LIKE.n_docs // 4)
    phases = Phases()
    t0 = time.perf_counter()
    info = device_check(args)
    from repro.jax_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"collection: WSJ1_LIKE, {args.docs} of {WSJ1_LIKE.n_docs} docs, "
        f"seed {args.seed}")
    (four_chips if args.chips > 1 else one_chip)(args, phases)
    log("phase seconds: " + json.dumps(
        {k: round(v, 3) for k, v in phases.seconds.items()}))
    log(f"total wall seconds: {time.perf_counter() - t0:.3f}")
    if args.rehearse:
        log("rehearsal passed on "
            f"{info['platform']}; this is not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
