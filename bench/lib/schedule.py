"""Seed-pure query schedules and the flush groups they imply.

The idea is the program's ``serve/workload.py`` (schedules that are pure
functions of their seed, terms drawn over the frequency-ranked vocabulary,
modes cycled), rewritten here for one traffic file's rules:

* ``rate_qps`` arrivals over the window.  The count is fixed at
  ``round(rate * seconds)`` and the times are sorted uniform draws: a
  Poisson process conditioned on its count, so every seed offers the same
  load and only the order and spacing change;
* query lengths from ``terms``: ``uniform`` over ``[min, max]``, or
  ``binomial``: ``min`` plus Binomial(``max - min``, p) with the mean given;
* terms from the band ``ranks[skip:top]`` of the vocabulary ranked by
  document frequency, picked ``uniform``-ly or by ``zipf`` over the band,
  without repeats inside one query;
* modes cycled query by query;
* where the traffic has ``ingest`` (``{"docs_per_s": r, "batch_docs": b}``),
  ``round(r * seconds / b)`` ingest batches of ``b`` documents, at sorted
  uniform times over the window from a stream of their own.  A traffic
  without ``ingest`` gives the schedule it gave before the key existed.

No clock, no global random state: the same seed and inputs give the same
schedule.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed works."""
    return np.random.default_rng(np.random.SeedSequence(
        [seed % (1 << 64), stream]))


@dataclass
class Schedule:
    at_s: np.ndarray            # (n,) scheduled arrival, seconds from start
    terms: list[tuple[int, ...]]  # term ids of each query
    modes: list[str]
    k: int
    groups: list[list[int]]     # flush groups: consecutive query indices
    close_s: np.ndarray         # (len(groups),) when each group may flush
    ingest_at_s: np.ndarray | None = None   # (batches,) scheduled ingests
    batch_docs: int = 0         # documents in each ingest batch


def _lengths(rule: dict, n: int, rng) -> np.ndarray:
    lo, hi = int(rule["min"]), int(rule["max"])
    if rule.get("length", "uniform") == "uniform":
        return rng.integers(lo, hi + 1, n)
    p = (float(rule["mean"]) - lo) / (hi - lo)
    return lo + rng.binomial(hi - lo, p, n)


def _picks(band: np.ndarray, rule: dict, lengths: np.ndarray, rng):
    if rule.get("pick", "uniform") == "uniform":
        cdf = np.arange(1, len(band) + 1, dtype=np.float64) / len(band)
    else:
        w = np.arange(1, len(band) + 1, dtype=np.float64) ** -float(rule["s"])
        cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    out = []
    for nt in lengths.tolist():
        got: list[int] = []
        while len(got) < nt:
            draw = np.searchsorted(cdf, rng.random(4 * nt), side="right")
            for j in draw.tolist():
                t = int(band[j])
                if t not in got:
                    got.append(t)
                    if len(got) == nt:
                        break
        out.append(tuple(got))
    return out


def flush_groups(at_s: np.ndarray, max_batch: int, deadline_s: float):
    """Consecutive arrivals grouped the way a size-or-deadline batcher
    closes them: a group ends at ``max_batch`` queries or at its first
    arrival plus ``deadline_s``.  Returns (groups, close times)."""
    groups, close = [], []
    i, n = 0, len(at_s)
    while i < n:
        end = float(at_s[i]) + deadline_s
        j = i
        while j < n and j - i < max_batch and at_s[j] < end:
            j += 1
        groups.append(list(range(i, j)))
        close.append(float(at_s[j - 1]) if j - i == max_batch else end)
        i = j
    return groups, np.asarray(close)


def build(traffic: dict, ranked_terms: np.ndarray, seed: int,
          seconds: float) -> Schedule:
    """The window's queries for ``seed``.  ``ranked_terms`` holds term ids
    by descending document frequency."""
    n = max(1, round(float(traffic["rate_qps"]) * seconds))
    at = np.sort(rng_for(seed, 1).uniform(0.0, seconds, n))
    rule = traffic["terms"]
    band = ranked_terms[int(rule.get("skip", 0)):rule.get("top")]
    rng = rng_for(seed, 2)
    terms = _picks(band, rule, _lengths(rule, n, rng), rng)
    modes = traffic["modes"]
    groups, close = flush_groups(at, int(traffic["max_batch"]),
                                 float(traffic["deadline_ms"]) / 1e3)
    out = Schedule(at_s=at, terms=terms,
                   modes=[modes[i % len(modes)] for i in range(n)],
                   k=int(traffic["k"]), groups=groups, close_s=close)
    if "ingest" in traffic:
        rule = traffic["ingest"]
        out.batch_docs = int(rule["batch_docs"])
        nb = round(float(rule["docs_per_s"]) * seconds / out.batch_docs)
        out.ingest_at_s = np.sort(rng_for(seed, 3).uniform(0.0, seconds, nb))
    return out


def with_ingest(traffic: dict, docs_per_s: float, batch_docs: int) -> dict:
    """``traffic`` with a write stream of ``docs_per_s`` documents a second
    in batches of ``batch_docs`` overlaid, and the limit 0 on
    ``ingest_unacked``."""
    return dict(traffic, ingest={"docs_per_s": docs_per_s,
                                 "batch_docs": batch_docs},
                limits=dict(traffic["limits"], ingest_unacked=0))
