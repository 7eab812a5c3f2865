"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the single real device; only launch/dryrun.py forces 512 fake devices."""

import os

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--sanitize", action="store_true", default=False,
        help="run every test under the repro.analysis concurrency "
             "sanitizer (instrumented locks + race detection); the "
             "REPRO_SANITIZE=1 env flag is equivalent")


@pytest.fixture(autouse=True)
def sanitizer(request):
    """Under ``--sanitize`` / ``REPRO_SANITIZE=1``: instrument every lock
    created by repro/test code for the duration of the test and fail it on
    any lock-order inversion or detected race.  Otherwise yields None at
    zero cost.  Tests that *deliberately* seed violations construct their
    own private :class:`Sanitizer` (never ``enable()``-d), so their
    findings land in the private instance, not here."""
    want = request.config.getoption("--sanitize") \
        or os.environ.get("REPRO_SANITIZE", "") not in ("", "0")
    if not want:
        yield None
        return
    from repro.analysis.sanitizer import Sanitizer
    san = Sanitizer(name=request.node.name)
    san.enable()
    try:
        yield san
    finally:
        san.disable()
        assert not san.findings, \
            f"concurrency sanitizer findings:\n{san.report()}"

try:
    # Hypothesis profiles (selected with --hypothesis-profile=NAME):
    #   * ci   — deterministic (derandomize=True + a fixed example budget)
    #            so the fast `-m "not slow"` CI job can never flake on a
    #            fresh random draw; tier-1 runs the default randomized
    #            profile (hypothesis's stock 100-example budget).
    #   * dev  — bigger example budget for local property hunting.
    # The property tests deliberately pin only deadline=None, so these
    # profile budgets are the single knob for example counts.  Local runs
    # without hypothesis installed simply skip the property modules (they
    # importorskip), so this must stay optional.
    from hypothesis import settings

    settings.register_profile("ci", max_examples=40, derandomize=True,
                              deadline=None)
    settings.register_profile("dev", max_examples=200, deadline=None)
except ImportError:  # pragma: no cover - hypothesis is optional locally
    pass


@pytest.fixture(scope="session")
def zipf_docs():
    """A small Zipfian document collection shared across test modules."""
    rng = np.random.default_rng(1234)
    vocab = [f"w{i}" for i in range(400)]
    probs = 1.0 / np.arange(1, 401) ** 1.07
    probs /= probs.sum()
    docs = [[vocab[i] for i in rng.choice(400, size=rng.integers(8, 150),
                                          p=probs)]
            for _ in range(500)]
    return vocab, docs


@pytest.fixture(scope="session")
def host_mesh():
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def naive_phrase(docs, terms):
    """Brute-force phrase oracle: scan raw token lists for the consecutive
    phrase (1-based docids).  Shared by the phrase differential tests in
    test_query.py and test_lifecycle.py so the oracle cannot drift."""
    terms = list(terms)
    return [i + 1 for i, d in enumerate(docs)
            if any(list(d[j:j + len(terms)]) == terms
                   for j in range(len(d) - len(terms) + 1))]


def naive_proximity(docs, terms, window):
    """Brute-force proximity oracle over raw token lists (1-based docids):
    a doc matches iff some window [lo, lo+window] contains at least m_t
    occurrences of each query term t, where m_t is t's multiplicity in the
    query (repeated terms bind DISTINCT positions).  Enumerates every
    occurrence position as a candidate window start — O(n^2) per doc,
    deliberately nothing like the cursor operator's two-pointer sweep."""
    need = {}
    for t in terms:
        need[t] = need.get(t, 0) + 1
    out = []
    for i, d in enumerate(docs):
        pos = {t: [j for j, x in enumerate(d) if x == t] for t in need}
        if any(len(pos[t]) < m for t, m in need.items()):
            continue
        starts = sorted(p for ps in pos.values() for p in ps)
        if any(all(sum(lo <= p <= lo + window for p in pos[t]) >= m
                   for t, m in need.items())
               for lo in starts):
            out.append(i + 1)
    return out


def naive_ranked(docs, terms, k=10, mode="tfidf", k1=0.9, b=0.4, alpha=1.0):
    """Brute-force doc-level ranked oracle computing true f_{t,d} / f_t from
    the raw token lists, with the same float64 operations and per-document
    accumulation order (query-term order) as the index scorers, so scores
    are bitwise-comparable.  Tie order: higher score, then lower docid.
    Returns (docids, scores) — the top-k."""
    N = len(docs)
    doclens = np.asarray([0] + [len(d) for d in docs], dtype=np.float64)
    avg = float(doclens[1:N + 1].mean()) if N else 0.0
    df = {t: sum(t in d for d in docs) for t in set(terms)}
    scores = np.zeros(N + 1, dtype=np.float64)
    for t in terms:  # repeated query terms contribute once per slot
        ft = df[t]
        if ft == 0:
            continue
        for i, d in enumerate(docs, start=1):
            f = d.count(t)
            if not f:
                continue
            if mode == "tfidf":
                scores[i] += np.log1p(np.float64(f)) * np.log1p(N / ft)
            else:
                idf = np.log(1.0 + (N - ft + 0.5) / (ft + 0.5))
                tf = (f * (k1 + 1.0)) / (
                    f + k1 * (1.0 - b + b * doclens[i] / max(avg, 1e-9)))
                scores[i] += idf * tf
    if mode == "bm25_prox":
        for i, d in enumerate(docs, start=1):
            if not scores[i]:
                continue
            pos = [[j for j, x in enumerate(d, start=1) if x == t]
                   for t in dict.fromkeys(terms)]
            dists = [abs(p - q) for a in range(len(pos))
                     for bb in range(a + 1, len(pos))
                     for p in pos[a] for q in pos[bb]]
            delta = min(dists) if dists else None
            scores[i] += np.log(alpha + (np.exp(-float(delta))
                                         if delta is not None else 0.0))
    nz = np.flatnonzero(scores)
    order = np.lexsort((nz, -scores[nz]))[:k]
    top = nz[order]
    return top.astype(np.int64), scores[top]
