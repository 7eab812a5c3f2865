"""Seeded synthetic collection, the benchmark's own copy of the generator.

Copied from the program's ``data/corpus.py`` so that no later change to the
program can move the yardstick: term frequencies Zipf(s) over a universe of
term ranks, document lengths log-normal with mean ``words_per_doc``, and
term rank ``i`` rendered as the base-36 string of ``i + 31``.

The collection is kept as term-id arrays (what the plain reference reads);
:meth:`Collection.docs` renders a slice of it as term strings, the input
the engine ingests.  :func:`extend` continues a collection with the
stream a live window ingests, from a second generator.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def term_names(universe: int) -> np.ndarray:
    """The name of every rank below ``universe``, as an object array: rank
    ``i`` is the lower-case base-36 string of ``i + 31``."""
    v = np.arange(universe, dtype=np.int64) + 31
    width = len(np.base_repr(int(v[-1]), 36))
    alphabet = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyz",
                             np.uint8)
    digits = np.empty((universe, width), np.uint8)
    for j in range(width - 1, -1, -1):
        digits[:, j] = alphabet[v % 36]
        v //= 36
    packed = digits.view(f"S{width}").ravel()
    return np.char.lstrip(packed, b"0").astype(str).astype(object)


@dataclass
class Collection:
    """``n`` documents as a CSR of term ids (``ptr`` has n + 1 entries)."""

    ptr: np.ndarray        # (n + 1,) int64 token offsets
    ids: np.ndarray        # (tokens,) int32 term ranks, in document order
    names: np.ndarray      # (universe,) object: term rank -> string

    @property
    def n(self) -> int:
        return len(self.ptr) - 1

    def docs(self, lo: int, hi: int) -> list[list[str]]:
        """Documents ``lo..hi-1`` (0-based) as term-string lists."""
        p = self.ptr
        return [self.names[self.ids[p[d]:p[d + 1]]].tolist()
                for d in range(lo, hi)]

    def utf8_bytes(self, lo: int, hi: int) -> int:
        """UTF-8 bytes of documents ``lo..hi-1``, terms joined by one space."""
        lens = self._name_lens()
        p = self.ptr
        toks = int(lens[self.ids[p[lo]:p[hi]]].sum())
        return toks + int(p[hi] - p[lo]) - (hi - lo)

    def _name_lens(self) -> np.ndarray:
        if not hasattr(self, "_lens"):
            self._lens = np.fromiter((len(s) for s in self.names), np.int64,
                                     count=len(self.names))
        return self._lens


def _draw(n_docs: int, words_per_doc: float, zipf_s: float, universe: int,
          rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(ptr, ids) of ``n_docs`` documents drawn from ``rng``: every length
    first, then every token."""
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    p = ranks ** (-zipf_s)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    mu = np.log(words_per_doc) - 0.125     # log-normal mean = words_per_doc
    lens = np.maximum(2, rng.lognormal(mu, 0.5, n_docs)).astype(np.int64)
    ptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lens, out=ptr[1:])
    ids = np.searchsorted(cdf, rng.random(int(ptr[-1])),
                          side="right").astype(np.int32)
    return ptr, ids


def generate(n_docs: int, words_per_doc: float, zipf_s: float,
             universe: int, seed: int) -> Collection:
    """The first ``n_docs`` documents of the stream seeded by ``seed``."""
    ptr, ids = _draw(n_docs, words_per_doc, zipf_s, universe,
                     np.random.default_rng(seed))
    return Collection(ptr=ptr, ids=ids, names=term_names(universe))


STREAM_BLOCK = 1024


def extend(coll: Collection, base: int, n_docs: int, words_per_doc: float,
           zipf_s: float, universe: int, seed: int) -> None:
    """Append to ``coll`` the stream that continues it past document
    ``base`` until it holds ``base + n_docs`` documents or more.  The
    stream is drawn from a second generator, in blocks of
    ``STREAM_BLOCK`` documents, block ``j`` from the seed sequence
    ``(seed, 1, j)``: its documents are the same however many are asked
    for, and the first ``base`` are never touched."""
    while coll.n < base + n_docs:
        j = (coll.n - base) // STREAM_BLOCK
        ptr, ids = _draw(STREAM_BLOCK, words_per_doc, zipf_s, universe,
                         np.random.default_rng(np.random.SeedSequence(
                             [seed % (1 << 64), 1, j])))
        coll.ptr = np.concatenate([coll.ptr, coll.ptr[-1] + ptr[1:]])
        coll.ids = np.concatenate([coll.ids, ids])
