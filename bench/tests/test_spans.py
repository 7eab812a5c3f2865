"""The readers of the program's span table and of its spans and stages in
a trace, on hand-built counters and a hand-recorded trace; every value
below is counted by hand."""

import importlib
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import stages as stages_tool
from bench.lib import program_trace
from bench.lib import trace as tracing
from bench.lib.cell import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ACCEPTED = ("queue_wait_ms", "launch_fill", "latency_p99_ms",
            "host_ms_per_flush", "device_query_share", "fused_ms_per_launch",
            "fused_query_roofline", "compiles_in_window", "device_idle_share",
            "bytes_per_posting")
NEW = ("prep_ms_per_flush", "unpack_ms_per_flush", "readback_ms_per_launch",
       "ingest_us_per_posting")

BEFORE = {
    "repro.service.flush": [10, 1.0, 0.1],
    "repro.engine.execute": [10, 0.9, 0.2],
    "repro.engine.plan": [10, 0.05, 0.05],
    "repro.device.refresh": [10, 0.01, 0.01],
    "repro.device.prep": [30, 0.1, 0.1],
    "repro.device.launch": [30, 0.06, 0.06],
    "repro.device.wait": [30, 0.3, 0.3],
    "repro.device.readback": [30, 0.03, 0.03],
    "repro.device.unpack": [30, 0.06, 0.06],
    "repro.engine.ingest": [5, 2.0, 2.0],
}
# four flushes of three launches in the window
AFTER = {
    "repro.service.flush": [14, 1.5, 0.12],
    "repro.engine.execute": [14, 1.38, 0.24],
    "repro.engine.plan": [14, 0.06, 0.06],
    "repro.device.refresh": [14, 0.012, 0.012],
    "repro.device.prep": [42, 0.12, 0.12],
    "repro.device.launch": [42, 0.072, 0.072],
    "repro.device.wait": [42, 0.7, 0.7],
    "repro.device.readback": [42, 0.036, 0.036],
    "repro.device.unpack": [42, 0.072, 0.072],
    "repro.engine.ingest": [5, 2.0, 2.0],
}


def counters(span_table, batches):
    c = {"queries": 100 + 10 * batches, "batches_served": batches,
         "by_backend": {"device": 100 + 10 * batches},
         "num_postings": 400_000}
    if span_table is not None:
        c["spans"] = span_table
    return c


def make_run(trace=None, with_spans=True):
    """Four flushes: queries due at 0.0, 0.1, 0.2, 0.3 s, the flushes over
    [0.05, 0.08], [0.15, 0.2], [0.25, 0.26], [0.35, 0.5]."""
    sched = SimpleNamespace(at_s=np.array([0.0, 0.1, 0.2, 0.3]))
    w = SimpleNamespace(
        flushes=[SimpleNamespace(start=s, end=e) for s, e in
                 ((0.05, 0.08), (0.15, 0.2), (0.25, 0.26), (0.35, 0.5))],
        flush_of=np.arange(4))
    return Run(sched, w,
               counters(BEFORE if with_spans else None, 30),
               counters(AFTER if with_spans else None, 42),
               compiles=0, bytes_per_posting=2.5, trace=trace,
               peaks={"hbm_bytes_per_s": 1e9}, work_bytes=10**6)


def read(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


@pytest.fixture
def recorded():
    """``test_trace.py``'s trace (window 0..10 s; device ops at 1-2,
    1.5-3, 6-7; a benchmark flush span 0.5-3.5; the program ran twice),
    plus an op at 8-8.5 outside any launch."""
    tr = tracing.Trace()
    tr.devices.append(tracing.Device(
        ops=[(1.0, 2.0, "fusion.1"), (1.5, 3.0, "sort.2"),
             (6.0, 7.0, "fusion.1"), (8.0, 8.5, "copy.3")],
        modules=[(1.0, 3.0, "jit_fused_query(7)"),
                 (6.0, 7.0, "jit_fused_query(7)"),
                 (20.0, 21.0, "jit_fused_query(7)")]))
    tr.spans = {"window": [(0.0, 10.0)], "flush": [(0.5, 3.5)]}
    return tr


@pytest.fixture
def program():
    """The program's spans of that flush, and the stages of its ops."""
    sp = {"repro.service.flush": [(0.5, 3.5)],
          "repro.device.prep": [(0.5, 1.0)],
          "repro.device.launch": [(1.0, 1.2)],
          "repro.device.wait": [(1.2, 3.0)],
          "repro.device.readback": [(3.0, 3.2)],
          "repro.device.unpack": [(3.2, 3.4)]}
    return program_trace.Program(
        spans={k: [(s, e, 1) for s, e in v] for k, v in sp.items()},
        stacks={"/device:TPU:0": {
            "fusion.1": "jit(fused_query)/accumulate/scatter-add:",
            "sort.2": "jit(fused_query)/topk/sort:"}})


def test_span_table_readers_count_by_hand():
    run = make_run()
    # self time in the window: flush .02 + execute .04 + plan .01 +
    # refresh .002 + prep .02 + launch .012 = .104 s over 4 flushes
    assert read("prep_ms_per_flush", run) == pytest.approx(26.0)
    # unpack .012 s over 4 flushes
    assert read("unpack_ms_per_flush", run) == pytest.approx(3.0)
    # readback .006 s over 12 launches
    assert read("readback_ms_per_launch", run) == pytest.approx(0.5)
    # set-up ingest 2.0 s over 400,000 postings
    assert read("ingest_us_per_posting", run) == pytest.approx(5.0)


@pytest.mark.parametrize("name", NEW)
def test_no_span_table_nothing_to_read(name):
    """The program before the span table: no value, no exception."""
    assert read(name, make_run(with_spans=False)) is None


def test_new_metrics_are_entries_of_both_cells():
    cells = [w["name"] for w in SPEC["workloads"]]
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == cells
        assert by_name[name]["source"] == "program_counter"
    assert by_name["ingest_us_per_posting"]["moves"] == "setup_s"


def test_accepted_readers_unmoved_by_the_span_table(recorded):
    """The ten accepted readers read the same with and without the span
    table in the counters."""
    with_table = {m: read(m, make_run(recorded)) for m in ACCEPTED}
    without = {m: read(m, make_run(recorded, with_spans=False))
               for m in ACCEPTED}
    assert with_table == without
    assert None not in with_table.values()
    assert with_table["host_ms_per_flush"] == pytest.approx(1000.0)
    assert with_table["device_idle_share"] == pytest.approx(65.0)


def test_trace_read_keeps_program_spans_apart(tmp_path):
    """The accepted reader keeps only the benchmark's own spans, so the
    program's ``repro.*`` annotations move none of its numbers."""
    f = jax.jit(lambda x: x * 2)
    f(1.0)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for n in range(2):
            with jax.profiler.TraceAnnotation("flush"):
                with jax.profiler.TraceAnnotation("repro.service.flush",
                                                  flush=n + 1):
                    with jax.profiler.TraceAnnotation("repro.device.wait",
                                                      flush=n + 1):
                        f(1.0).block_until_ready()
    jax.profiler.stop_trace()
    path = tracing.xplane_path(str(tmp_path))
    tr = tracing.read(path)
    assert set(tr.spans) == {"window", "flush"}
    assert len(tr.spans["flush"]) == 2
    prog = program_trace.read(path)
    assert set(prog.spans) == {"repro.service.flush", "repro.device.wait"}
    assert [f for _, _, f in prog.spans["repro.service.flush"]] == [1, 2]
    (fs, fe, _), _ = prog.spans["repro.service.flush"]
    (ws, we, _), _ = prog.spans["repro.device.wait"]
    assert fs <= ws <= we <= fe
    assert prog.stacks == {}           # the CPU has no device plane


def test_idle_split_by_the_innermost_span(recorded, program):
    idle = program_trace.idle_by_stage(recorded, program)
    # idle 0-1, 3-6, 7-8, 8.5-10: 0-0.5 in no span, 0.5-1.0 in prep,
    # 3.0-3.2 readback, 3.2-3.4 unpack, 3.4-3.5 the flush itself, the
    # rest in none
    assert idle == {None: pytest.approx(5.5),
                    "repro.device.prep": pytest.approx(0.5),
                    "repro.device.readback": pytest.approx(0.2),
                    "repro.device.unpack": pytest.approx(0.2),
                    "repro.service.flush": pytest.approx(0.1)}
    assert sum(idle.values()) == pytest.approx(10.0 - recorded.busy_s())


def test_span_less_device_time(recorded, program):
    assert program_trace.minus_device(
        recorded, program, "repro.device.readback") == [pytest.approx(0.2)]
    assert program_trace.minus_device(
        recorded, program, "repro.device.wait") == [pytest.approx(0.0)]


def test_fused_time_by_stage(recorded, program):
    # fusion.1 (accumulate) 1-2 and 6-7 inside the two launches in the
    # window, sort.2 (topk) 1.5-3; copy.3 runs outside any launch
    assert program_trace.stage_time(recorded, program) == {
        "accumulate": pytest.approx(2.0), "topk": pytest.approx(1.5)}


def test_stages_tool_on_the_recorded_trace(recorded, program):
    run = make_run(recorded)
    out = stages_tool.stages(run, program)
    assert out["idle_in_flush_pct"] == pytest.approx(10.0)
    assert out["idle_pct"]["none"] == pytest.approx(55.0)
    assert out["fused_pct"] == {"accumulate": pytest.approx(2.0 / 3.5 * 100),
                                "topk": pytest.approx(1.5 / 3.5 * 100)}
    assert out["spans"]["repro.device.readback"] == {
        "n": 1, "mean_ms": pytest.approx(200.0),
        "host_ms": pytest.approx(200.0)}
    assert out["per_flush"]["flushes"] == 4
    assert out["per_flush"]["launches_per_flush"] == 3.0
    assert out["per_flush"]["repro.device.unpack"]["total_ms"] == \
        pytest.approx(3.0)


# -- a hand-encoded trace file ---------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _stat(meta_id, s=None, ref=None):
    body = _field(1, meta_id)
    body += _field(5, s) if s is not None else _field(7, ref)
    return body


def _event_meta(mid, name, display, stats):
    body = _field(1, mid) + _field(2, name) + _field(4, display)
    body += b"".join(_field(5, st) for st in stats)
    return _field(4, _field(1, mid) + _field(2, body))


def _stat_meta(sid, name):
    return _field(5, _field(1, sid) + _field(2, _field(1, sid) +
                                            _field(2, name)))


def test_op_stacks_from_the_wire_format(tmp_path):
    """tf_op as a string and as a reference to an interned string; a host
    plane and a device plane without the stat give nothing."""
    device = (_field(1, 7) + _field(2, "/device:TPU:0")
              + _field(3, _field(2, "XLA Ops"))
              + _event_meta(1, "%fusion.1 = f32[8] fusion(...)", "fusion.1",
                            [_stat(2, s="hbm"),
                             _stat(1, s="jit(fused_query)/decode/add:")])
              + _event_meta(2, "%sort.2 = f32[8] sort(...)", "sort.2",
                            [_stat(1, ref=3)])
              + _event_meta(3, "%copy.3 = f32[8] copy(...)", "copy.3", [])
              + _stat_meta(1, "tf_op") + _stat_meta(2, "hlo_category")
              + _stat_meta(3, "jit(fused_query)/topk/sort:"))
    bare = _field(2, "/device:TPU:1") + _event_meta(1, "x", "x", [])
    host = (_field(2, "/host:CPU")
            + _event_meta(1, "repro.x", "repro.x", [_stat(1, s="no")])
            + _stat_meta(1, "tf_op"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host) + _field(1, bare)
                     + _field(3, "a warning"))
    assert program_trace.op_stacks(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(...)": "jit(fused_query)/decode/add:",
        "fusion.1": "jit(fused_query)/decode/add:",
        "%sort.2 = f32[8] sort(...)": "jit(fused_query)/topk/sort:",
        "sort.2": "jit(fused_query)/topk/sort:"}}
