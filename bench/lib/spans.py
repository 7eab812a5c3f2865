"""The program's span table (``EngineStats.spans``, ``repro.obs``) as the
counters before and after a window hold it: ``{name: [count, total_s,
self_s]}``.  A program without the table gives nothing to read."""

from __future__ import annotations


def delta(run, name: str):
    """(count, total_s, self_s) of the ``name`` spans in the window; None
    when the program keeps no span table."""
    before, after = run.before.get("spans"), run.after.get("spans")
    if before is None or after is None:
        return None
    b, a = before.get(name, (0, 0.0, 0.0)), after.get(name, (0, 0.0, 0.0))
    return tuple(y - x for x, y in zip(b, a))


def flushes(run) -> int | None:
    """The window's ``repro.service.flush`` spans, or None without a
    table."""
    d = delta(run, "repro.service.flush")
    return None if d is None else d[0]
