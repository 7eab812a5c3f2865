"""Serving driver: immediate-access index ingest+query service (the paper's
workload) or LM decode with the Triangle-paged KV cache.

``--mode index``: streams synthetic documents into a DynamicIndex while
serving conjunctive + ranked queries between ingest batches — the paper's
interleaved operation stream (§4.5/§4.6), reporting ingest and query
latencies.

``--mode lm``: batched token-by-token decode of a reduced LM with the paged
KV cache from repro.serve (Triangle page growth).
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def serve_index(n_docs: int, n_queries: int):
    from repro.core.index import DynamicIndex
    from repro.core.query import conjunctive_query, ranked_disjunctive_taat
    from repro.data.corpus import CorpusSpec, SyntheticCorpus

    corpus = SyntheticCorpus(CorpusSpec(n_docs=n_docs, words_per_doc=120,
                                        universe=50_000))
    idx = DynamicIndex(B=64, growth="const")
    rng = np.random.default_rng(0)
    seen_terms: list[str] = []
    q_lat, i_lat = [], []
    qi = 0
    for d, doc in enumerate(corpus.doc_terms()):
        t0 = time.perf_counter()
        idx.add_document(doc)
        i_lat.append(time.perf_counter() - t0)
        if d < 50:
            seen_terms.extend(doc[:5])
        # interleave queries with ingest (immediate access)
        if d % 10 == 9 and seen_terms:
            terms = list(rng.choice(seen_terms,
                                    size=min(3, len(seen_terms))))
            t0 = time.perf_counter()
            if qi % 2 == 0:
                conjunctive_query(idx, terms)
            else:
                ranked_disjunctive_taat(idx, terms, k=10)
            q_lat.append(time.perf_counter() - t0)
            qi += 1
            if qi >= n_queries:
                break
    print(f"[serve-index] docs={idx.num_docs} postings={idx.num_postings} "
          f"bytes/posting={idx.bytes_per_posting():.3f}")
    print(f"[serve-index] ingest mean {np.mean(i_lat)*1e6:.1f}us/doc; "
          f"query mean {np.mean(q_lat)*1e3:.2f}ms "
          f"p95 {np.percentile(q_lat, 95)*1e3:.2f}ms over {qi} queries")


def serve_lm(steps: int):
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import reduced_lm
    from repro.configs import get_arch
    from repro.models import lm as lm_mod
    from repro.serve import PagedKVCache

    mesh = make_host_mesh()
    cfg = reduced_lm(get_arch("llama3.2-3b").cfg)
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 128
    pool = PagedKVCache(n_pages=256, page_tokens=16, policy="triangle")
    for b in range(B):
        pool.add_sequence(b)
    with mesh:
        serve = jax.jit(lm_mod.make_serve_step(cfg, mesh),
                        static_argnames=())
        cache = {k: jnp.zeros(v.shape, v.dtype)
                 for k, v in lm_mod.make_cache_shape(cfg, B, S).items()}
        tok = jnp.zeros((B,), jnp.int32)
        t0 = time.perf_counter()
        for pos in range(steps):
            for b in range(B):
                pool.append_tokens(b, 1)
            logits, cache = serve(params, cache, tok, pos)
            tok = jnp.argmax(logits[:, :cfg.vocab], -1).astype(jnp.int32)
        dt = time.perf_counter() - t0
    ovh = [pool.overhead_tokens(b) for b in range(B)]
    print(f"[serve-lm] {steps} decode steps x {B} seqs in {dt:.2f}s "
          f"({dt/steps*1e3:.1f} ms/step); page overhead/seq {ovh} tokens")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["index", "lm"], default="index")
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--steps", type=int, default=32)
    args = ap.parse_args()
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "index":
        serve_index(args.docs, args.queries)
    else:
        serve_lm(args.steps)


if __name__ == "__main__":
    main()
