"""Production traffic bench: Zipf mixed streams, tail latency, recovery.

    PYTHONPATH=src python benchmarks/traffic_bench.py \
        [--events 3000] [--out BENCH_engine.json] [--report FILE] [--smoke]

Runs the :mod:`repro.serve.traffic` open-loop driver over the shared
synthetic corpus in four scenarios — {1 shard, 4 shards} x {quiet tier,
freeze storm} — and records p50/p99/p999 latency, result-cache hit rate,
and availability (must be zero gap even mid-storm) into a new ``traffic``
section of ``BENCH_engine.json`` (merged; every other section the engine
bench wrote is preserved).  The freeze-storm scenarios run an aggressive
background :class:`FreezePolicy` so tier swaps land mid-stream; the fleet
scenario additionally exercises the coordinated (``max_in_flight=1``)
encode budget.

Each scenario is judged against a generous-margin :class:`SLOSpec` (CI
machines are noisy; the SLO catches order-of-magnitude regressions and the
hard zero-availability-gap invariant, not microseconds).  The full
percentile report also lands in ``--report`` (default
``traffic_report.json``) for the CI build artifact.

A recovery measurement rides along: after the single-engine storm run the
engine is snapshotted (``Engine.snapshot``) and restored, timing both and
verifying a spot-check query answers byte-identically — the bench-side echo
of the differential proof in tests/test_persist.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import corpus  # noqa: E402

from repro.core.lifecycle import FreezePolicy  # noqa: E402
from repro.core.sharded_index import ShardedEngine  # noqa: E402
from repro.engine import Engine  # noqa: E402
from repro.engine.types import Query  # noqa: E402
from repro.jax_cache import enable_compile_cache  # noqa: E402
from repro.serve import (  # noqa: E402
    SLOSpec,
    WorkloadSpec,
    generate_schedule,
    run_traffic,
)

#: Generous CI margins: these bound order-of-magnitude regressions (and the
#: hard zero-gap invariant), not steady-state microseconds — CI machines
#: are shared and noisy.  tests/test_traffic.py asserts against the same
#: specs, so bench and tests cannot drift apart.
#:
#: Mixed ingest+query streams carry NO cache-hit SLO: immediate access means
#: every ingest bumps the engine version and invalidates the result cache,
#: so with ingest every ~4 events the steady-state hit rate is ~0 by design
#: (the read-only replay scenario is where the cache earns its keep).
CI_SLO = SLOSpec(p50_ms=500.0, p99_ms=5000.0, p999_ms=20000.0,
                 max_availability_gap=0)
#: Read-only replay: 64 distinct Zipf-popular queries repeated across the
#: run with no invalidation — the Zipf head alone clears 20% easily.
READONLY_SLO = SLOSpec(p50_ms=500.0, p99_ms=5000.0, p999_ms=20000.0,
                       min_cache_hit_rate=0.2, max_availability_gap=0)
#: Storm scenarios (freeze every 40 docs, and the delete storms on top of
#: that) deliberately run the engine in degraded mode: the single writer
#: thread spends most of the run behind background encodes, and every
#: delete flushes pending queries first (consistency: a pending query must
#: not miss a document that was alive at its submission), so batching
#: collapses.  The SLO story there is degraded-but-BOUNDED latency with the
#: zero-availability-gap invariant fully intact — judging storms against
#: the quiet-stream p50 just measures the host machine's speed (the same
#: committed schedule lands either side of 500 ms across runs of an
#: unchanged tree).
STORM_SLO = SLOSpec(p50_ms=3000.0, p99_ms=10000.0, p999_ms=30000.0,
                    max_availability_gap=0)

STORM_POLICY = dict(every_docs=40, background=True)
QUIET_POLICY = dict(every_docs=1_000_000, background=True)


def ranked_vocab(docs) -> list[str]:
    """Vocabulary sorted by descending collection frequency — rank 1 is the
    most common term, which is what the Zipf term draw expects."""
    counts = Counter(t for d in docs for t in d)
    return [t for t, _ in counts.most_common()]


def make_spec(seed: int, events: int, ingest_fraction: float = 0.25,
              delete_fraction: float = 0.0) -> WorkloadSpec:
    return WorkloadSpec(seed=seed, num_events=events,
                        ingest_fraction=ingest_fraction,
                        delete_fraction=delete_fraction,
                        num_distinct_queries=64, max_terms=3,
                        modes=("conjunctive", "ranked_tfidf", "bm25"))


def run_scenario(*, shards: int, storm: bool, schedule, docs,
                 preload: int = 0, slo: SLOSpec = CI_SLO,
                 backend: str | None = "host"):
    """Build a fresh engine/fleet, optionally pre-ingest ``preload`` docs
    (read-only replay), drive the schedule, judge against ``slo``.
    Returns ``(result_dict, engine)`` — engine still live for the recovery
    measurement; caller owns nothing else (background encodes joined).

    ``backend`` defaults to host routing: this container's device path is
    interpret-mode (no accelerator), so its per-shape compile cost would
    swamp every percentile with a ~70s artifact that says nothing about
    serving behavior.  The harness measures the serving layer — batching,
    cache, freeze availability — which is backend-independent; pass
    ``backend=None`` to let the measured-crossover planner route."""
    policy = FreezePolicy(**(STORM_POLICY if storm else QUIET_POLICY))
    if shards == 1:
        engine = Engine(tier_policy=policy, force_backend=backend)
        closer = (lambda: engine.lifecycle.wait())
    else:
        engine = ShardedEngine(num_shards=shards, max_in_flight=1,
                               tier_policy=policy, force_backend=backend)
        closer = engine.close
    try:
        for d in docs[:preload]:
            engine.add_document(d)
        report = run_traffic(engine, schedule, docs)
        ev = slo.evaluate(report)
        out = report.to_dict()
        out["shards"] = shards
        out["freeze_storm"] = storm
        out["slo"] = {"ok": ev["ok"], "violations": ev["violations"]}
        return out, engine
    finally:
        closer()


def snapshot_recovery_point(engine: Engine) -> dict:
    """Time snapshot + restore of the post-traffic engine and spot-check a
    restored query byte-identically (the full six-mode differential lives
    in tests/test_persist.py)."""
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        snap = engine.snapshot(td)
        save_s = time.perf_counter() - t0
        size = sum(os.path.getsize(os.path.join(dp, f))
                   for dp, _, fs in os.walk(snap) for f in fs)
        t0 = time.perf_counter()
        restored = Engine.restore(td)
        restore_s = time.perf_counter() - t0
        q = Query(terms=("w0", "w1"), mode="bm25")
        a, b = engine.execute(q), restored.execute(q)
        identical = (np.array_equal(a.docids, b.docids)
                     and np.array_equal(a.scores, b.scores))
    return {"save_ms": save_s * 1e3, "restore_ms": restore_s * 1e3,
            "snapshot_bytes": size, "spot_check_identical": bool(identical),
            "num_docs": engine.index.num_docs,
            "tier_epoch": engine.lifecycle.epoch}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=3000)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--report", default="traffic_report.json",
                    help="standalone percentile report (CI build artifact)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-fast scale: few hundred events")
    ap.add_argument("--backend", default="host",
                    choices=["host", "tiered", "device", "pallas", "default"],
                    help="force_backend for every engine; 'default' lets the "
                         "measured-crossover planner route (slow without a "
                         "real accelerator: interpret-mode compile cost)")
    args = ap.parse_args()
    enable_compile_cache()
    backend = None if args.backend == "default" else args.backend

    events = 400 if args.smoke else args.events
    docs = corpus(600 if args.smoke else 1500)
    vocab = ranked_vocab(docs)
    spec = make_spec(args.seed, events)
    schedule = generate_schedule(spec, vocab)
    n_q = sum(e.kind == "query" for e in schedule)
    print(f"traffic: {events} events ({n_q} queries, "
          f"{events - n_q} ingests), |vocab|={len(vocab)}")
    ro_spec = make_spec(args.seed + 1, events, ingest_fraction=0.0)
    ro_schedule = generate_schedule(ro_spec, vocab)
    # delete storm: heavy tombstoning under an aggressive freeze policy, so
    # freeze-time compaction and deletion-aware serving run concurrently —
    # judged against the same zero-availability-gap SLO as every scenario
    del_spec = make_spec(args.seed + 2, events, ingest_fraction=0.25,
                         delete_fraction=0.2)
    del_schedule = generate_schedule(del_spec, vocab)

    plan = [(f"shards{s}" + ("_storm" if st else ""),
             dict(shards=s, storm=st, schedule=schedule, docs=docs,
                  slo=STORM_SLO if st else CI_SLO, backend=backend))
            for s in (1, 4) for st in (False, True)]
    plan.append(("shards1_readonly",
                 dict(shards=1, storm=False, schedule=ro_schedule, docs=docs,
                      preload=len(docs) // 2, slo=READONLY_SLO,
                      backend=backend)))
    plan.append(("shards1_delete_storm",
                 dict(shards=1, storm=True, schedule=del_schedule, docs=docs,
                      slo=STORM_SLO, backend=backend)))
    plan.append(("shards4_delete_storm",
                 dict(shards=4, storm=True, schedule=del_schedule, docs=docs,
                      slo=STORM_SLO, backend=backend)))

    scenarios = {}
    recovery = None
    for name, kw in plan:
        t0 = time.perf_counter()
        result, engine = run_scenario(**kw)
        print(f"  {name:16s} p50={result['p50_ms']:.2f}ms "
              f"p99={result['p99_ms']:.2f}ms "
              f"p999={result['p999_ms']:.2f}ms "
              f"hit_rate={result['cache_hit_rate']:.2f} "
              f"gap={result['availability_gap']} "
              f"deletes={result['num_deletes']} "
              f"freezes={result['freezes']} "
              f"slo={'OK' if result['slo']['ok'] else 'VIOLATED'} "
              f"({time.perf_counter() - t0:.1f}s)")
        scenarios[name] = result
        if name == "shards1_storm":
            recovery = snapshot_recovery_point(engine)
            print(f"  recovery: save {recovery['save_ms']:.1f}ms, "
                  f"restore {recovery['restore_ms']:.1f}ms, "
                  f"{recovery['snapshot_bytes']} bytes, spot-check "
                  f"{'OK' if recovery['spot_check_identical'] else 'FAIL'}")

    traffic = {
        "config": {"events": events, "seed": args.seed,
                   "smoke": args.smoke, "backend": args.backend,
                   "num_docs_corpus": len(docs),
                   "ingest_fraction": spec.ingest_fraction,
                   "delete_storm_delete_fraction": del_spec.delete_fraction,
                   "num_distinct_queries": spec.num_distinct_queries,
                   "modes": list(spec.modes)},
        "slo": {"mixed": CI_SLO.to_dict(),
                "readonly": READONLY_SLO.to_dict(),
                "storm": STORM_SLO.to_dict()},
        "scenarios": scenarios,
        "recovery": recovery,
    }

    payload = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            payload = json.load(f)
    payload["traffic"] = traffic
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    with open(args.report, "w") as f:
        json.dump(traffic, f, indent=2)
    print(f"wrote {args.out} (traffic section) and {args.report}")

    bad = [n for n, s in scenarios.items() if not s["slo"]["ok"]]
    gaps = [n for n, s in scenarios.items() if s["availability_gap"]]
    if gaps:
        print(f"AVAILABILITY GAP in {gaps}", file=sys.stderr)
        return 1
    if bad:
        print(f"SLO violations in {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
