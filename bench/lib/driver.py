"""The open-loop driver: one thread, queries and ingests on schedule.

Each query is submitted to the ``QueryService`` at its scheduled arrival.
A flush group (``schedule.flush_groups``) is flushed as soon as it is
complete and closed: at its 32nd query, or when its deadline since its
first arrival has passed.  A query's latency is its flush's completion
minus its **scheduled** arrival, always, so a driver that falls behind
charges the wait to the system (the program's ``serve/traffic.py`` charged
from the earlier of schedule and submission, which lets a driver that runs
ahead hide queueing).

Where the schedule has ingest batches, each runs ``svc.ingest_batch`` at
its scheduled time on the same thread.  Every event (arrival, flush,
ingest) runs in the order it is scheduled, an ingest first on a tie; one
that runs late is late, never rescheduled.  The driver counts the
documents acknowledged (the horizon) when each query is submitted and when
each flush begins: the immediate-access rule lets an answer miss only the
documents acknowledged after its query's submission.

``TraceAnnotation`` spans named ``flush`` and ``ingest`` wrap each flush
and each ingest batch, so a device trace can say what the host was doing
in each gap.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Flush:
    group: int
    start: float          # seconds from the window's start
    end: float
    horizon: int = 0      # documents acknowledged when it began


@dataclass
class Ingest:
    at: float             # scheduled, seconds from the window's start
    start: float
    end: float
    docids: list          # what ``ingest_batch`` returned
    horizon: int          # documents acknowledged once it returned


@dataclass
class Window:
    """What one window did, in the driver's own records."""

    length_s: float = 0.0             # start to the last completion
    flushes: list[Flush] = field(default_factory=list)
    flush_of: np.ndarray | None = None    # query -> index into flushes
    results: list = field(default_factory=list)
    submit_horizon: np.ndarray | None = None  # query -> horizon at submit
    ingests: list[Ingest] = field(default_factory=list)


def drive(svc, schedule, queries: list, seconds: float,
          on_flush=None, batches=(), horizon: int = 0) -> Window:
    """Run one window; ``queries[i]`` is the engine ``Query`` of schedule
    entry ``i`` and ``batches[b]`` the documents of ingest batch ``b``,
    as term strings.  ``horizon`` is the documents acknowledged before the
    window.  ``on_flush(idxs, results)`` runs after each flush, outside
    the flush's span, with the results of the group's queries."""
    from jax.profiler import TraceAnnotation
    at, groups, close = schedule.at_s, schedule.groups, schedule.close_s
    ing = schedule.ingest_at_s if schedule.ingest_at_s is not None else ()
    n, nb = len(at), len(ing)
    tickets = [None] * n
    flush_of = np.full(n, -1, np.int64)
    submit_h = np.full(n, -1, np.int64)
    w = Window()
    clock = time.perf_counter
    t0 = clock()
    i = g = b = 0
    while g < len(groups) or b < nb:
        now = clock() - t0
        if g < len(groups):
            grp = groups[g]
            last = grp[-1]
            due = at[i] if i <= last else close[g]
        else:
            due = np.inf
        if b < nb and ing[b] <= due:
            if ing[b] > now:
                time.sleep(min(ing[b] - now, 0.05))
                continue
            start = clock() - t0
            with TraceAnnotation("ingest"):
                ids = svc.ingest_batch(batches[b])
            horizon += len(batches[b])
            w.ingests.append(Ingest(float(ing[b]), start, clock() - t0,
                                    list(ids), horizon))
            b += 1
            continue
        if due > now:
            time.sleep(min(due - now, 0.05))
            continue
        if i <= last and not (i == last and len(grp) == svc.max_batch):
            submit_h[i] = horizon
            tickets[i] = svc.submit(queries[i])
            i += 1
            continue
        # the group is complete: its last arrival (a full group) or its
        # deadline (a partial one) makes it due now
        start = clock() - t0
        with TraceAnnotation("flush"):
            if i <= last:
                submit_h[i] = horizon
                tickets[i] = svc.submit(queries[i])   # fills: auto-flush
                i += 1
            svc.flush()
        w.flushes.append(Flush(g, start, clock() - t0, horizon))
        flush_of[grp] = len(w.flushes) - 1
        if on_flush is not None:
            on_flush(grp, [tickets[j].result for j in grp])
        g += 1
    w.length_s = max(seconds, clock() - t0)
    w.flush_of = flush_of
    w.submit_horizon = submit_h
    w.results = [t.result for t in tickets]
    return w


def latencies(schedule, w: Window) -> np.ndarray:
    """Completion minus scheduled arrival, seconds, per query."""
    ends = np.array([f.end for f in w.flushes])
    return ends[w.flush_of] - schedule.at_s
