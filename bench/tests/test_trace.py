"""The trace reduction on a small hand-recorded trace."""

import jax
import pytest

from bench.lib import trace as tracing


@pytest.fixture
def recorded():
    """Window 0..10 s; device ops at 1-2, 1.5-3 (overlap), 6-7; a flush
    span 0.5-3.5; the program ran twice."""
    tr = tracing.Trace()
    dev = tracing.Device(
        ops=[(1.0, 2.0, "fusion.1"), (1.5, 3.0, "sort.2"),
             (6.0, 7.0, "fusion.1")],
        modules=[(1.0, 3.0, "jit_fused_query(7)"),
                 (6.0, 7.0, "jit_fused_query(7)"),
                 (20.0, 21.0, "jit_fused_query(7)")])
    tr.devices.append(dev)
    tr.spans = {"window": [(0.0, 10.0)], "flush": [(0.5, 3.5)]}
    return tr


def test_busy_is_the_union_of_op_intervals(recorded):
    assert recorded.busy_s() == pytest.approx(3.0)
    assert recorded.window_s() == pytest.approx(10.0)


def test_module_time_counts_launches_inside_the_window(recorded):
    secs, n = recorded.module_time("fused_query")
    assert (secs, n) == (pytest.approx(3.0), 2)


def test_host_time_of_a_span_leaves_out_device_time(recorded):
    assert recorded.host_minus_device("flush") == [pytest.approx(1.0)]


def test_idle_gaps_are_named_by_the_host_span(recorded):
    gaps = recorded.idle_gaps()
    assert gaps[0] == ["none@3.000s", pytest.approx(3.0)]
    assert gaps[1] == ["none@7.000s", pytest.approx(3.0)]
    assert gaps[2] == ["flush@0.000s", pytest.approx(1.0)]
    assert [name for name, _ in recorded.top_ops()] == ["fusion.1", "sort.2"]


def test_read_finds_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: x * 2)
    f(1.0)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("flush"):
                f(1.0).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.read(tracing.xplane_path(str(tmp_path)))
    assert len(tr.spans["flush"]) == 2
    (ws, we), = tr.spans["window"]
    assert all(ws <= s <= e <= we for s, e in tr.spans["flush"])
    assert tr.devices == []            # the CPU has no device plane
    assert tr.busy_s() is None


def test_idle_gap_inside_an_ingest_is_named_ingest(recorded):
    recorded.spans["ingest"] = [(8.0, 9.0)]
    gaps = recorded.idle_gaps()
    assert gaps[0] == ["none@3.000s", pytest.approx(3.0)]
    assert gaps[1] == ["ingest@7.000s", pytest.approx(3.0)]
    assert gaps[2] == ["flush@0.000s", pytest.approx(1.0)]


def test_read_keeps_the_ingest_spans(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("ingest"):
            sum(range(1000))
        with jax.profiler.TraceAnnotation("flush"):
            sum(range(1000))
    jax.profiler.stop_trace()
    tr = tracing.read(tracing.xplane_path(str(tmp_path)))
    (s, e), = tr.spans["ingest"]
    (fs, _), = tr.spans["flush"]
    assert s <= e <= fs
