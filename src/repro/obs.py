"""Spans: the program's own timings, at its layer boundaries.

``SpanTable.span(name, **ids)`` times one section of the program and does
two things with it:

* it opens a ``jax.profiler.TraceAnnotation(name, **ids)``, so that under a
  profiler session the span lands on the host plane of the trace, on the
  same clock as the device's ``XLA Ops`` (with no session the annotation
  records nothing);
* it adds to the table, under ``name``: a count, the total seconds and the
  self seconds (the total less the spans nested in it on the same thread).

Ids named in :data:`CARRIED` pass down to every span opened inside the
one that set them (``QueryService.flush`` sets ``flush``), so the spans of
one flush share an identifier.  They travel in a ``contextvars`` context:
work handed to another thread takes them along by running in a copy of
the caller's context (``contextvars.copy_context().run``).

Spans sit at call boundaries (a flush, a batch, a launch, a freeze), never
inside a per-document, per-posting or per-query loop.  Every engine owns
one table; ``Engine.stats().spans`` exports it as ``{name: [count,
total_s, self_s]}``.
"""

from __future__ import annotations

import contextvars
import threading
from time import perf_counter as _clock

from jax.profiler import TraceAnnotation

#: Ids that nested spans inherit from the span that set them.
CARRIED = ("flush",)

_carried: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_obs_carried", default={})
_local = threading.local()          # .stack: open spans of this thread


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """One timed section; ``seconds`` holds its length once it has ended."""

    __slots__ = ("table", "name", "ids", "seconds", "_t0", "_child",
                 "_note", "_token")

    def __init__(self, table: "SpanTable", name: str, ids: dict):
        self.table, self.name, self.ids = table, name, ids
        self.seconds = 0.0
        self._child = 0.0
        self._token = None

    def __enter__(self) -> "Span":
        inherited = _carried.get()
        ids = {**inherited, **self.ids} if inherited else self.ids
        handed = {k: ids[k] for k in CARRIED if k in self.ids}
        if handed:
            self._token = _carried.set({**inherited, **handed})
        self._note = TraceAnnotation(self.name, **ids)
        self._note.__enter__()
        _stack().append(self)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = dur = _clock() - self._t0
        st = _stack()
        st.pop()
        if st:
            st[-1]._child += dur
        self._note.__exit__(*exc)
        if self._token is not None:
            _carried.reset(self._token)
        self.table.add(self.name, dur, dur - self._child)


class SpanTable:
    """Per-name count, total seconds and self seconds; thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: dict[str, list] = {}        # guarded_by: _lock

    def span(self, name: str, **ids) -> Span:
        """A context manager timing ``name`` (see the module docstring)."""
        return Span(self, name, ids)

    def add(self, name: str, total_s: float, self_s: float) -> None:
        with self._lock:
            row = self._rows.get(name)
            if row is None:
                self._rows[name] = [1, total_s, self_s]
            else:
                row[0] += 1
                row[1] += total_s
                row[2] += self_s

    def snapshot(self) -> dict[str, list]:
        """``{name: [count, total_s, self_s]}``, a copy."""
        with self._lock:
            return {k: list(v) for k, v in self._rows.items()}

    def total(self, name: str) -> float:
        with self._lock:
            row = self._rows.get(name)
            return row[1] if row is not None else 0.0


def merge(*snapshots: dict) -> dict[str, list]:
    """Sum span snapshots name by name (a fleet's shards)."""
    out: dict[str, list] = {}
    for snap in snapshots:
        for name, (n, tot, own) in snap.items():
            row = out.setdefault(name, [0, 0.0, 0.0])
            row[0] += n
            row[1] += tot
            row[2] += own
    return out


__all__ = ["CARRIED", "Span", "SpanTable", "merge"]
