"""One cell's system under test, built once, and its measured windows.

:class:`Cell` generates the configuration's collection, ingests it through
the program's ``QueryService(Engine(...)).ingest_batch`` in set-up batches
and freezes it (``collate_now`` uploads the frozen image).  Where the
traffic names ``delta_docs``, that many further documents of the same
stream are then ingested without a freeze, so the window's queries run over
the frozen image and a delta image with rebased statistics, as a live index
does between compactions.  :meth:`Cell.window` builds one seed's schedule,
runs every one of its flush groups once (so every launch shape the window
uses is compiled in set-up, whatever the program's bucketing), and drives
the window.  ``run.py`` makes one window per process; ``tools.py`` makes
several on one build.

Where the traffic names ``ingest``, the window also ingests the stream
that continues the collection (``corpus.extend``), rendered to term
strings in set-up: each window takes the next documents of the stream, so
on one build the windows' horizons follow one another.  The comparison
then judges each answer at its own horizon (:meth:`Cell.check`).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass

import numpy as np

from . import corpus, driver, reference, roofline, schedule
from . import trace as tracing

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """XLA backend compiles, through JAX's monitoring events: ``n`` counts
    every compile request (a persistent-cache hit included, since JAX
    times the request), ``hits`` the requests the cache answered."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1

    def _on_hit(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def counters(eng) -> dict:
    s = dataclasses.asdict(eng.stats())
    s["batches_served"] = eng.resident.batches_served
    s["launch_keys"] = len(eng.resident.launch_buckets)
    return s


def chain_lengths(eng) -> list[np.ndarray]:
    return [np.asarray(img.term_nblk) for img in eng.resident.images]


@dataclass
class Run:
    """One window and everything a per-layer reader may read."""

    schedule: schedule.Schedule
    window: driver.Window
    before: dict
    after: dict
    compiles: int
    bytes_per_posting: float
    trace: tracing.Trace | None = None
    peaks: dict | None = None
    work_bytes: int = 0

    def delta(self, key: str):
        return self.after[key] - self.before[key]

    def served(self, backend: str) -> int:
        return (self.after["by_backend"].get(backend, 0)
                - self.before["by_backend"].get(backend, 0))


class Cell:
    def __init__(self, config: dict, traffic: dict, log=print):
        import jax

        from repro.engine import Engine
        from repro.serve import QueryService
        self.config, self.traffic, self.log = config, traffic, log
        self.compiles = CompileCounter()
        n_docs = int(config["n_docs"])
        n_delta = int(traffic.get("delta_docs", 0))
        if "ingest" in traffic and "ingest_unacked" not in traffic["limits"]:
            raise ValueError("an ingest traffic's limits name ingest_unacked")
        self.horizon = n_docs + n_delta      # documents acknowledged
        self.base = self.horizon             # where the stream starts
        t = time.perf_counter()
        self.coll = coll = collection(config, self.base)
        log(f"generated {n_docs} + {n_delta} docs, {int(coll.ptr[-1])} "
            f"tokens in {time.perf_counter() - t:.3f} s")
        self.eng = eng = Engine(B=int(config["B"]), growth=config["growth"])
        self.svc = QueryService(eng, max_batch=int(config["max_batch"]),
                                cache_size=0)
        step = int(config["setup_ingest_batch"])
        t = time.perf_counter()
        self.ingest(0, n_docs, step)
        t_ingest = time.perf_counter() - t
        t = time.perf_counter()
        eng.collate_now()
        self.ingest(n_docs, self.horizon, step)
        eng.resident.refresh()
        jax.block_until_ready(eng.resident.images)
        log(f"ingest {t_ingest:.3f} s, freeze and delta "
            f"{time.perf_counter() - t:.3f} s; {eng.index.num_postings} "
            f"postings, {eng.index.bytes_per_posting():.4f} bytes/posting; "
            f"chain blocks per image {[int(c.sum()) for c in chain_lengths(eng)]}")
        cf = np.bincount(coll.ids, minlength=int(config["universe"]))
        self.ranked = np.argsort(-cf, kind="stable")[
            :int(np.count_nonzero(cf))]
        self._ref = None

    def ingest(self, lo: int, hi: int, step: int) -> None:
        for a in range(lo, hi, step):
            self.svc.ingest_batch(self.coll.docs(a, min(a + step, hi)))

    def stream(self, sched: schedule.Schedule) -> list:
        """The window's ingest batches, the stream's next documents after
        the horizon, as term strings."""
        if sched.ingest_at_s is None:
            return []
        bd, h = sched.batch_docs, self.horizon
        c = self.config
        corpus.extend(self.coll, self.base,
                      h - self.base + bd * len(sched.ingest_at_s),
                      float(c["words_per_doc"]), float(c["zipf_s"]),
                      int(c["universe"]), int(c["corpus_seed"]))
        return [self.coll.docs(h + bd * j, h + bd * (j + 1))
                for j in range(len(sched.ingest_at_s))]

    # -- one window ------------------------------------------------------

    def queries(self, sched: schedule.Schedule) -> list:
        from repro.engine import Query
        names = self.coll.names
        return [Query(terms=tuple(names[t] for t in ts), mode=m, k=sched.k)
                for ts, m in zip(sched.terms, sched.modes)]

    def warm(self, sched, queries) -> None:
        """Submit and flush every flush group of the window once, through
        the same service calls the window makes."""
        for grp in sched.groups:
            for i in grp:
                self.svc.submit(queries[i])
            self.svc.flush()

    def window(self, seed: int, seconds: float, trace_dir: str | None = None,
               before_window=None) -> Run:
        """Warm, then drive one window of ``seed``'s schedule.
        ``before_window()`` runs last in set-up; with ``trace_dir`` the
        window is traced there and the launches' roofline work recorded."""
        import jax
        eng = self.eng
        sched = schedule.build(self.traffic, self.ranked, seed, seconds)
        queries = self.queries(sched)
        batches = self.stream(sched)
        t = time.perf_counter()
        self.warm(sched, queries)
        self.log(f"warm-up: {len(sched.groups)} groups in "
                 f"{time.perf_counter() - t:.3f} s ({self.compiles.n} "
                 f"compiles so far, {self.compiles.hits} from the cache)")
        before = counters(eng)
        n_compiles = self.compiles.n
        work = [0]
        on_flush = None
        if trace_dir is not None:
            nblk = [eng.version, chain_lengths(eng)]
            block = int(self.config["B"])

            def on_flush(grp, results):
                if nblk[0] != eng.version:       # an ingest moved the delta
                    nblk[:] = [eng.version, chain_lengths(eng)]
                uniq = {queries[i] for i, r in zip(grp, results)
                        if r is not None and r.backend == "device"}
                work[0] += roofline.launch_bytes(
                    [[t for t in map(eng.term_id, q.terms) if t is not None]
                     for q in uniq], nblk[1], block, sched.k)

            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
        # The built index is tens of millions of Python objects; a full
        # collection inside the window walks all of them and stalls the
        # host for seconds.  Set-up ends with them moved out of the
        # collector's reach, as a long-running server would.
        gc.collect()
        gc.freeze()
        if before_window is not None:
            before_window()
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("window"):
            w = driver.drive(self.svc, sched, queries, seconds,
                             on_flush=on_flush, batches=batches,
                             horizon=self.horizon)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.unfreeze()
        self.horizon += sum(len(d) for d in batches)
        after = counters(eng)
        run = Run(sched, w, before, after, self.compiles.n - n_compiles,
                  eng.index.bytes_per_posting(), work_bytes=work[0])
        self.log(
            f"window: {len(sched.at_s)} queries in {len(w.flushes)} flushes,"
            f" {w.length_s:.3f} s; by backend "
            f"{ {k: run.served(k) for k in after['by_backend']} }; "
            f"{run.compiles} compiles; {len(w.ingests)} ingest batches")
        return run

    def close(self) -> None:
        """Free the program's state (host and device)."""
        self.svc = self.eng = None

    # -- the comparison --------------------------------------------------

    def check(self, run: Run, control: bool = False) -> reference.Readings:
        """Compare every answer of the window (or, with ``control``, the
        bfloat16 reference's answers to the same queries, at each flush's
        horizon) with the reference.  An answer is judged at its flush's
        horizon and, where it fails a limit there, at each horizon an
        ingest batch left between its query's submission and that flush,
        down to the submission's: the immediate-access rule lets a query
        miss what was acknowledged after it was submitted, nothing
        before.  It passes where one of those horizons passes every limit
        of an answer; else its readings are those of the flush's horizon.
        ``ingest_unacked`` counts the window's documents that
        ``ingest_batch`` did not acknowledge with the next docids."""
        if self._ref is None or self._ref.n != self.coll.n:
            self._ref = reference.Reference(self.coll.ptr, self.coll.ids)
        ref, limits = self._ref, self.traffic["limits"]
        w, sched = run.window, run.schedule
        acked = [x.horizon for x in w.ingests]
        out = reference.Readings()
        for i, res in enumerate(w.results):
            mode, terms = sched.modes[i], sched.terms[i]
            if res is None:
                out.unanswered += 1
                continue
            h = w.flushes[w.flush_of[i]].horizon
            if control:
                got_d, got_s, _ = ref.answer(terms, mode, sched.k, h,
                                             precision=reference.BF16)
                tries = [h]
            else:
                got_d, got_s = res.docids, res.scores
                sub = int(w.submit_horizon[i])
                tries = sorted({h, sub} | {a for a in acked if sub < a < h},
                               reverse=True)
            for hz in tries:
                r = reference.compare_one(mode, got_d, got_s,
                                          ref.answer(terms, mode, sched.k, hz))
                if hz == h:
                    first = r
                if passes(r, limits):
                    break
            else:
                r = first
            r.worst = {"rank_gap": (i, mode), "score_gap": (i, mode)}
            out.add(r)
        bd = sched.batch_docs
        for x in w.ingests:
            want = range(x.horizon - bd + 1, x.horizon + 1)
            hit = sum(int(a) == b for a, b in zip(x.docids, want))
            out.ingest_unacked += max(len(x.docids), bd) - hit
        return out

    def ingest_report(self, run: Run) -> dict | None:
        """The window's write stream: documents and UTF-8 bytes ingested,
        MB (10^6 bytes) per minute of the window, and in ms the p50 and
        maximum of ack latency (a batch's end minus its scheduled time) and
        of start lateness (its start minus its scheduled time).  None for a
        traffic without ingest."""
        sched, w = run.schedule, run.window
        if sched.ingest_at_s is None:
            return None
        docs = sched.batch_docs * len(w.ingests)
        lo = w.ingests[0].horizon - sched.batch_docs if w.ingests else 0
        nbytes = self.coll.utf8_bytes(lo, lo + docs) if docs else 0
        out = {"docs": docs, "utf8_bytes": nbytes,
               "mb_per_min": nbytes / 1e6 / (w.length_s / 60.0)}
        for name, ms in (
                ("ack_ms", [1e3 * (x.end - x.at) for x in w.ingests]),
                ("late_ms", [1e3 * (x.start - x.at) for x in w.ingests])):
            out[f"{name}_p50"] = float(np.median(ms)) if ms else None
            out[f"{name}_max"] = float(np.max(ms)) if ms else None
        return out


def collection(config: dict, n_docs: int) -> corpus.Collection:
    """The configuration's first ``n_docs`` documents."""
    return corpus.generate(n_docs, float(config["words_per_doc"]),
                           float(config["zipf_s"]), int(config["universe"]),
                           int(config["corpus_seed"]))


ANSWER_CHECKS = ("unanswered", "conj_wrong", "rank_gap", "score_gap")
CHECKS = ANSWER_CHECKS + ("ingest_unacked",)


def passes(readings: reference.Readings, limits: dict) -> bool:
    return all(getattr(readings, name) <= limits[name]
               for name in ANSWER_CHECKS)


def verdict(readings: reference.Readings, limits: dict):
    """(correct, {name: {value, limit}}) for the compared numbers: those
    of every answer, and the others where the traffic's limits name
    them."""
    checks = {name: {"value": getattr(readings, name), "limit": limits[name]}
              for name in CHECKS if name in ANSWER_CHECKS or name in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
