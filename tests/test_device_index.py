"""Device (JAX) query engine vs host engines; kernel-backed decode path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.collate import collate
from repro.core.device_index import (build_device_image, precise_log1p,
                                      query_step)
from repro.core.index import DynamicIndex
from repro.kernels.dvbyte_decode.ops import as_decode_fn


@pytest.fixture(scope="module")
def image(zipf_docs):
    vocab, docs = zipf_docs
    idx = DynamicIndex(B=64, growth="const")
    for doc in docs[:400]:
        idx.add_document(doc)
    col = collate(idx)
    img = build_device_image(col, [t.encode() for t in vocab])
    return vocab, col, img


@pytest.mark.parametrize("kind", ["tf", "idf", "bm25_idf", "wide"])
def test_precise_log1p_matches_f64(kind):
    """The device scoring log1p stays within a few f32 ulps of f64 on the
    arguments scoring feeds it, jitted (XLA folds naive rounding fixes)."""
    rng = np.random.default_rng(0)
    ft = np.arange(1, 100_001, dtype=np.float64)
    x = {"tf": np.arange(0, 100_001, dtype=np.float64),
         "idf": 98_732.0 / ft,
         "bm25_idf": np.maximum(98_732.0 - ft + 0.5, 0.5) / (ft + 0.5),
         "wide": np.exp(rng.uniform(-30.0, 16.0, 100_000))}[kind]
    x32 = x.astype(np.float32)
    got = np.asarray(jax.jit(precise_log1p)(jnp.asarray(x32)), np.float64)
    want = np.log1p(x32.astype(np.float64))
    assert np.all(got[want == 0] == 0)
    nz = want > 0
    rel = np.abs(got[nz] - want[nz]) / want[nz]
    assert rel.max() < 4e-7, (rel.max(), x32[nz][rel.argmax()])


def test_requires_collated(zipf_docs):
    vocab, docs = zipf_docs
    idx = DynamicIndex(B=64)
    for doc in docs[:50]:
        idx.add_document(doc)
    with pytest.raises(ValueError):
        build_device_image(idx, [t.encode() for t in vocab])


def test_ranked_matches_host(image):
    vocab, col, img = image
    rng = np.random.default_rng(0)
    mb = int(img.term_nblk.max())
    for _ in range(15):
        terms = rng.choice(150, size=rng.integers(1, 5), replace=False)
        qt = jnp.asarray([list(terms) + [0] * (5 - len(terms))], jnp.int32)
        qm = jnp.asarray([[1] * len(terms) + [0] * (5 - len(terms))], bool)
        d_dev, s_dev = query_step(img, qt, qm, k=10, max_blocks=mb)
        d_host, s_host = Q.ranked_disjunctive_taat(
            col, [vocab[i] for i in terms], k=10)
        got = np.sort(np.asarray(s_dev[0]))[::-1][: len(s_host)]
        assert np.allclose(got, s_host, rtol=1e-5)


def test_ranked_sparse_exact_over_many_postings():
    """Sort-based aggregation keeps f32 precision when a query touches
    ~25,000 postings (the four-chip phase's scale): summing runs as
    differences of whole-row prefix sums was off by ~1e-3 here."""
    docs = [["a"] * (1 + d % 3) + ["b"] * (d % 2) + ["c"] * (d % 5 > 0)
            for d in range(10_000)]
    idx = DynamicIndex(B=64, growth="const")
    for doc in docs:
        idx.add_document(doc)
    col = collate(idx)
    vocab = ["a", "b", "c"]
    img = build_device_image(col, [t.encode() for t in vocab])
    qt = jnp.asarray([[0, 1, 2]], jnp.int32)
    qm = jnp.ones((1, 3), bool)
    _, s_dev = query_step(img, qt, qm, k=10, mode="ranked_sparse",
                          max_blocks=int(img.term_nblk.max()))
    _, s_host = Q.ranked_disjunctive_taat(col, vocab, k=10)
    assert np.allclose(np.asarray(s_dev[0]), s_host, rtol=1e-6, atol=0)


def test_conjunctive_matches_host(image):
    vocab, col, img = image
    rng = np.random.default_rng(1)
    mb = int(img.term_nblk.max())
    for _ in range(15):
        terms = rng.choice(100, size=rng.integers(1, 4), replace=False)
        qt = jnp.asarray([list(terms) + [0] * (4 - len(terms))], jnp.int32)
        qm = jnp.asarray([[1] * len(terms) + [0] * (4 - len(terms))], bool)
        m, _ = query_step(img, qt, qm, mode="conjunctive", max_blocks=mb)
        got = (np.flatnonzero(np.asarray(m[0])) + 1).tolist()
        exp = Q.conjunctive_query(col, [vocab[i] for i in terms]).tolist()
        assert got == exp


def test_kernel_decode_path(image):
    """query_step with the Pallas decode kernel == pure-jnp decode path."""
    vocab, col, img = image
    mb = int(img.term_nblk.max())
    qt = jnp.asarray([[1, 5, 20, 0]], jnp.int32)
    qm = jnp.asarray([[1, 1, 1, 0]], bool)
    d1, s1 = query_step(img, qt, qm, k=10, max_blocks=mb)
    d2, s2 = query_step(img, qt, qm, k=10, max_blocks=mb,
                        decode_fn=as_decode_fn(F=4, tile=64))
    assert np.allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    assert np.asarray(d1).tolist() == np.asarray(d2).tolist()


def test_batched_queries(image):
    vocab, col, img = image
    mb = int(img.term_nblk.max())
    qt = jnp.asarray([[1, 2, 0], [3, 0, 0], [10, 20, 30]], jnp.int32)
    qm = jnp.asarray([[1, 1, 0], [1, 0, 0], [1, 1, 1]], bool)
    d, s = query_step(img, qt, qm, k=5, max_blocks=mb)
    assert d.shape == (3, 5) and s.shape == (3, 5)
    for qi, terms in enumerate(([1, 2], [3], [10, 20, 30])):
        dh, sh = Q.ranked_disjunctive_taat(col, [vocab[i] for i in terms],
                                           k=5)
        assert np.allclose(np.sort(np.asarray(s[qi]))[::-1][: len(sh)], sh,
                           rtol=1e-5)
