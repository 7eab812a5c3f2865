"""The program's own spans and stage names in a profiler trace.

``lib/trace.py`` keeps the device's operations and the benchmark's own
spans; this module reads, from the same ``.xplane.pb``, what the program
records itself:

* the host events named ``repro.*`` (``repro.obs`` spans), each with its
  ``flush`` id;
* each device operation's name-stack: the ``tf_op`` stat of its event
  metadata, which carries the ``jax.named_scope`` stages of the fused
  program (``gather``, ``decode``, ``accumulate``, ``topk``).
  ``ProfileData`` does not expose event metadata, so :func:`op_stacks`
  decodes it from the file's protobuf wire format (``XSpace`` →
  ``XPlane.event_metadata``).

The functions below combine it with a :class:`trace.Trace` of the same
file: where the device's idle time falls among the program's stages,
and how the fused program's device time splits among its own.
"""

from __future__ import annotations

from bisect import bisect_right
from dataclasses import dataclass, field

from .trace import DEVICE_PLANE, union

PREFIX = "repro."             # the program's span names
STACK_STAT = "tf_op"          # an operation's name-stack
STAGES = ("gather", "decode", "accumulate", "topk")


@dataclass
class Program:
    """Intervals in seconds on the profiler's clock."""

    spans: dict = field(default_factory=dict)   # name -> [(s, e, flush)]
    stacks: dict = field(default_factory=dict)  # plane -> {op: stack}


def read(path: str) -> Program:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    prog = Program(stacks=op_stacks(path))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    prog.spans.setdefault(ev.name, []).append(
                        (s, s + ev.duration_ns * 1e-9,
                         dict(ev.stats).get("flush")))
    for v in prog.spans.values():
        v.sort()
    return prog


# -- the trace's wire format ---------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _plane_stacks(buf, lo: int, hi: int):
    """(plane name, {event name: name-stack}) of one ``XPlane``; empty for
    a plane that is not a device's."""
    name, metas, stat_names = None, [], {}
    for num, v in _fields(buf, lo, hi):
        if num == 2:                        # XPlane.name
            name = _text(buf, v)
            if not DEVICE_PLANE.match(name):
                return name, {}
        elif num == 4:                      # event_metadata map entry
            metas.append(v)
        elif num == 5:                      # stat_metadata map entry
            for en, ev in _fields(buf, *v):
                if en == 2:
                    sm = dict(_fields(buf, *ev))
                    if 2 in sm:
                        stat_names[sm.get(1, 0)] = _text(buf, sm[2])
    stat_id = next((k for k, n in stat_names.items() if n == STACK_STAT),
                   None)
    out: dict = {}
    if stat_id is None:
        return name, out
    for entry in metas:
        for en, ev in _fields(buf, *entry):
            if en != 2:
                continue
            names, stack = [], None
            for mn, mv in _fields(buf, *ev):
                if mn in (2, 4):            # name, display_name
                    names.append(_text(buf, mv))
                elif mn == 5:               # XEventMetadata.stats
                    st = dict(_fields(buf, *mv))
                    if st.get(1, 0) != stat_id:
                        continue
                    if isinstance(st.get(5), tuple):    # str_value
                        stack = _text(buf, st[5])
                    elif 7 in st:                       # ref_value
                        stack = stat_names.get(st[7])
            if stack is not None:
                for n in names:
                    out[n] = stack
    return name, out


def op_stacks(path: str) -> dict:
    """{device plane: {operation name: name-stack}} of an ``.xplane.pb``."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for num, v in _fields(buf, 0, len(buf)):
        if num == 1:                        # XSpace.planes
            name, table = _plane_stacks(buf, *v)
            if table:
                out[name] = table
    return out


# -- readings ------------------------------------------------------------

def _busy_before(tr):
    """t -> seconds the first device was busy before ``t`` (by bisection
    over the union of its operations)."""
    merged = union((s, e) for s, e, _ in tr.devices[0].ops)
    starts = [s for s, _ in merged]
    cum = [0.0]
    for s, e in merged:
        cum.append(cum[-1] + (e - s))

    def before(t: float) -> float:
        j = bisect_right(starts, t)
        if j == 0:
            return 0.0
        s, e = merged[j - 1]
        return cum[j - 1] + (min(t, e) - s)

    return before


def _innermost(spans: dict, lo: float, hi: float):
    """[(start, end, name)]: ``[lo, hi]`` cut where the innermost open
    program span changes; None where the host is in no span."""
    evs = sorted(((s, e, name) for name, v in spans.items()
                  for s, e, _ in v if e > lo and s < hi),
                 key=lambda ev: (ev[0], -ev[1]))
    bounds = sorted({lo, hi} | {x for s, e, _ in evs for x in (s, e)
                                if lo < x < hi})
    out, live, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][0] <= a:
            live.append(evs[i])
            i += 1
        live = [ev for ev in live if ev[1] > a]
        inner = max(live, key=lambda ev: (ev[0], -ev[1]), default=None)
        out.append((a, b, inner[2] if inner else None))
    return out


def idle_by_stage(tr, prog: Program) -> dict:
    """Seconds of the window in which the first device is idle, by the
    innermost program span the host was in then (``None``: no span)."""
    before = _busy_before(tr)
    out: dict = {}
    for a, b, name in _innermost(prog.spans, *tr.window):
        idle = (b - a) - (before(b) - before(a))
        if idle > 0:
            out[name] = out.get(name, 0.0) + idle
    return out


def minus_device(tr, prog: Program, name: str) -> list[float]:
    """Per ``name`` span in the window: its length less the first
    device's busy time inside it."""
    win, before = tr.window, _busy_before(tr)
    return [(e - s) - (before(e) - before(s))
            for s, e, _ in prog.spans.get(name, [])
            if win[0] <= s <= win[1]]


def stage_time(tr, prog: Program, kernel: str = "fused_query") -> dict:
    """Device seconds of the operations inside the window's ``kernel``
    programs, by the first of :data:`STAGES` in each one's name-stack
    (``None``: no stage), summed over devices."""
    win = tr.window
    stacks: dict = {}
    for table in prog.stacks.values():
        stacks.update(table)
    out: dict = {}
    for d in tr.devices:
        progs = sorted((s, e) for s, e, name in d.modules
                       if kernel in name and win[0] <= s <= win[1])
        starts = [s for s, _ in progs]
        for s, e, name in d.ops:
            j = bisect_right(starts, s) - 1
            if j < 0 or s > progs[j][1]:
                continue
            parts = stacks.get(name, "").split("/")
            stage = next((p for p in parts if p in STAGES), None)
            out[stage] = out.get(stage, 0.0) + (e - s)
    return out
