"""The plain reference, its lower-precision control, and the comparison that
decides ``correct``.

The reference reads only the generated collection (term ids per document)
and the queries: one sort turns the documents into per-term docid lists,
and each query is answered by a direct scan of its terms' lists cut at the
query's horizon (the documents ingested before the flush that served it).
Scoring follows the semantics the engine documents, in float64:

* ``conjunctive``: every document holding all terms, ascending docid;
* ``ranked_tfidf``: sum over terms of log(1 + f) * log(1 + N / f_t);
* ``bm25``: sum over terms of idf * f (k1 + 1) / (f + k1 (1 - b + b dl / avgdl))
  with idf = log(1 + (N - f_t + 0.5) / (f_t + 0.5)), k1 = 0.9, b = 0.4;

top-k by descending score, ties to the lower docid.  Nothing here imports
the program.

The control is the same reference computed in bfloat16, the precision
below the engine's float32 scores: each factor, each per-posting weight and
each running sum is rounded to bfloat16.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import ml_dtypes
import numpy as np

K1, B = 0.9, 0.4
BF16 = ml_dtypes.bfloat16


class Reference:
    """Per-term docid lists over documents 1..n of a collection."""

    def __init__(self, ptr: np.ndarray, ids: np.ndarray):
        self.n = n = len(ptr) - 1
        lens = np.diff(ptr)
        doc = np.repeat(np.arange(1, n + 1, dtype=np.int64), lens)
        key, f = np.unique(ids.astype(np.int64) * (n + 1) + doc,
                           return_counts=True)
        term = key // (n + 1)
        self.docs = key % (n + 1)
        self.f = f.astype(np.float64)
        self.tptr = np.searchsorted(term, np.arange(int(ids.max()) + 2))
        self.cumlen = np.concatenate([[0], np.cumsum(lens)]).astype(np.float64)
        self.doclen = np.concatenate([[0], lens]).astype(np.float64)

    def postings(self, t: int, horizon: int):
        if t + 1 >= len(self.tptr):
            return np.zeros(0, np.int64), np.zeros(0)
        lo, hi = self.tptr[t], self.tptr[t + 1]
        d = self.docs[lo:hi]
        c = int(np.searchsorted(d, horizon, side="right"))
        return d[:c], self.f[lo:lo + c]

    def answer(self, terms, mode: str, k: int, horizon: int,
               precision=np.float64):
        """(docids, scores, dense scores) of one query at ``horizon``; the
        dense score vector is None for conjunctive."""
        lists = [self.postings(t, horizon) for t in terms]
        if mode == "conjunctive":
            if not lists or any(len(d) == 0 for d, _ in lists):
                return np.zeros(0, np.int64), None, None
            out = lists[0][0]
            for d, _ in lists[1:]:
                out = np.intersect1d(out, d, assume_unique=True)
            return out, None, None
        N = float(horizon)
        avg = self.cumlen[horizon] / horizon
        low = precision is not np.float64
        cast = (lambda x: np.asarray(x, np.float64).astype(BF16)) if low \
            else (lambda x: np.asarray(x, np.float64))
        scores = np.zeros(horizon + 1, BF16 if low else np.float64)
        for d, f in lists:
            ft = float(len(d))
            if not ft:
                continue
            if mode == "bm25":
                idf = cast(np.log1p((N - ft + 0.5) / (ft + 0.5)))
                norm = cast(K1 * (1.0 - B + B * self.doclen[d] / avg))
                tf = cast(cast(f * (K1 + 1.0)) / cast(cast(f) + norm))
            else:
                idf = cast(np.log1p(N / ft))
                tf = cast(np.log1p(f))
            scores[d] = scores[d] + cast(tf * idf)
        s64 = scores.astype(np.float64)
        nz = np.flatnonzero(s64 > 0)
        top = nz[np.lexsort((nz, -s64[nz]))[:k]]
        return top.astype(np.int64), s64[top], s64


@dataclass
class Readings:
    """The numbers ``correct`` compares, each the worst over the answers."""

    unanswered: int = 0      # queries due in the window with no answer
    conj_wrong: int = 0      # conjunctive answers not equal to the reference
    rank_gap: float = 0.0    # served doc's reference score below the best
    score_gap: float = 0.0   # served score vs the reference's for that doc
    ingest_unacked: int = 0  # ingested documents not acknowledged with
    #                          the next docids
    compared: int = 0
    worst: dict = field(default_factory=dict)

    def add(self, other: "Readings") -> None:
        self.unanswered += other.unanswered
        self.conj_wrong += other.conj_wrong
        self.compared += other.compared
        for name in ("rank_gap", "score_gap"):
            if getattr(other, name) > getattr(self, name):
                setattr(self, name, getattr(other, name))
                self.worst[name] = other.worst.get(name)


def compare_one(mode: str, served_d, served_s, ref) -> Readings:
    """Readings of one served answer against the reference's ``ref``
    (docids, scores, dense scores)."""
    r = Readings(compared=1)
    ref_d, ref_s, dense = ref
    served_d = np.asarray(served_d, np.int64)
    if mode == "conjunctive":
        r.conj_wrong = int(not np.array_equal(served_d, ref_d))
        return r
    served_s = np.asarray(served_s, np.float64)
    n = len(ref_d)
    inside = (served_d >= 1) & (served_d < len(dense))
    ref_of = np.where(inside, dense[np.where(inside, served_d, 0)], 0.0)
    m = min(n, len(served_d))
    rank = 0.0
    if len(served_d) != n or len(np.unique(served_d)) != len(served_d):
        rank = 1.0
    if m:
        rank = max(rank, float(np.max((ref_s[:m] - ref_of[:m]) / ref_s[:m])))
        score = np.where(ref_of > 0, np.abs(served_s - ref_of)
                         / np.where(ref_of > 0, ref_of, 1.0), 1.0)
        r.score_gap = float(score.max())
    r.rank_gap = max(rank, 0.0)
    return r
