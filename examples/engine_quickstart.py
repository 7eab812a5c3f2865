"""Unified query engine quickstart: one API over three backends.

    PYTHONPATH=src python examples/engine_quickstart.py

Demonstrates the engine lifecycle end to end:

  1. ingest through the Engine (doclens/vocab/f_t tracked for you);
  2. query mid-stream on the host cursors and the device path (the XLA
     flavour of the fused kernel; the Pallas flavour does not compile for
     a TPU, so it is left out) and watch the planner route;
  3. collate once (the freeze), keep ingesting, and query the device
     backend again: the frozen image plus the incremental DeltaImage answer
     for documents the device has never been collated over;
  4. serve an interleaved ingest/query stream through QueryService.
"""

import numpy as np

from repro.core.collate import collation_stats
from repro.data.corpus import CorpusSpec, SyntheticCorpus
from repro.engine import Engine, Query
from repro.serve import QueryService

corpus = SyntheticCorpus(CorpusSpec(n_docs=1200, words_per_doc=120,
                                    universe=2400, seed=4))
docs = list(corpus.doc_terms())

# (pass auto_collate_delta_frac=0.5 to bound the delta by re-freezing
#  automatically; left off here so step 3 shows a single explicit freeze)
eng = Engine(B=64, growth="const")
for d in docs[:700]:
    eng.add_document(d)

sample = [t for t in docs[0][:4]]
print(f"ingested {eng.index.num_docs} docs; probe terms: {sample[:2]}")

# -- 2: same query, host and device ----------------------------------------
q = Query(terms=tuple(sample[:2]), mode="ranked_tfidf", k=5)
for backend in ("host", "device"):
    r = eng.execute(Query(terms=q.terms, mode=q.mode, k=q.k,
                          backend=backend))
    print(f"  {backend:7s} top-5 docs {r.docids.tolist()} "
          f"scores {np.round(r.scores, 3).tolist()}")

auto = eng.execute_many([q] * 8)[0]
print(f"planner routed a batch of 8 to: {auto.backend} ({auto.reason})")

# -- 3: freeze once, keep ingesting, device stays current -----------------
eng.collate_now()
print(f"\ncollated (freeze): frag now "
      f"{collation_stats(eng.index)['frag_ratio']:.3f}")
for d in docs[700:]:
    eng.add_document(d)
r = eng.execute(Query(terms=q.terms, mode="conjunctive", backend="device"))
post_freeze = int((r.docids > 700).sum())
print(f"device conjunctive sees {len(r.docids)} docs, {post_freeze} of them "
      f"ingested after the freeze — no re-collation "
      f"(collations={eng.stats().collations}, "
      f"delta_refreshes={eng.stats().delta_refreshes})")

# -- 4: serving loop -------------------------------------------------------
svc = QueryService(eng, max_batch=8)
ops = []
for i, d in enumerate(SyntheticCorpus(CorpusSpec(
        n_docs=200, words_per_doc=120, universe=2400, seed=5)).doc_terms()):
    ops.append(("doc", d))
    if i % 3 == 0:
        ops.append(("query", Query(terms=tuple(sample[:2]),
                                   mode="bm25", k=3)))
tickets = svc.run_stream(ops)
print(f"\nserved {len(tickets)} queries interleaved with 200 ingests; "
      f"result cache {svc.cache_stats()}")
for name, (n, total_s, self_s) in sorted(eng.stats().spans.items()):
    print(f"  span {name}: {n} x, {1e3 * total_s:.1f} ms total, "
          f"{1e3 * self_s:.1f} ms self")
print(f"final stats: {eng.stats()}")
