"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload wsj1.query --seed 7 --seconds 30 --trace 0
    JAX_PLATFORMS=cpu python bench/run.py --workload wsj1.query --seed 7 \\
        --seconds 2 --trace 1 --rehearse

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``: the collection and the engine) and a traffic
mix (``bench/traffic/<name>.json``).  In one process the run finds the TPU,
generates the collection from the configuration, ingests it through the
program's ``QueryService(Engine(...)).ingest_batch``, freezes it
(``collate_now``), ingests the traffic's ``delta_docs`` after the freeze,
runs every flush group of the window once to compile what it will use, then
drives the window (``lib/driver.py``) for ``--seconds``: queries open loop
at the traffic's rate and, where the traffic names ``ingest``, batches of
the collection's continuing stream ingested on schedule beside them.
Set-up time is process start to the window's start.

After the window, with the program's state freed, every answer is compared
with the plain reference (``lib/reference.py``) at the documents its query
was entitled to see (``Cell.check``); the numbers compared and their limits go to the last lines
of standard error and under ``checks`` in the result.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read by ``bench/metrics/<name>.py``), ``device``, with
``--trace 1`` a ``breakdown``, for an ingest traffic an ``ingest`` object
(documents, UTF-8 bytes, MB/min, ack latency and start lateness), and
``checks``.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--rehearse`` runs the same code on the
CPU with the configuration's and traffic's ``rehearse`` sizes; its line
carries no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench.lib import driver  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402
from bench.lib.cell import Cell, Run, verdict  # noqa: E402

TRACE_DIR = os.path.join(BENCH, ".trace")


class NoRun(Exception):
    """The run cannot measure (no chip, no program); exit non-zero."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_cell(name: str, rehearse: bool):
    """(BENCHMARK.json, the cell, its configuration, its traffic)."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise NoRun(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = load_json(BENCH, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    if rehearse:
        config.update(config.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
    return spec, cell, config, traffic


def device_check(chips: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"jax {jax.__version__}; platform {info['platform']}; device_kind "
        f"{info['kind']}; device count {info['count']}")
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise NoRun(f"no TPU: JAX found {info['platform']!r} "
                    f"({info['kind']}); use --rehearse for a CPU rehearsal")
    if len(devs) < chips:
        raise NoRun(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


def place_cache() -> None:
    """Give the program's compile cache a fixed directory inside the
    checkout, before JAX is imported: ``repro.jax_cache`` takes
    ``JAX_COMPILATION_CACHE_DIR`` where it is set."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def open_program(rehearse: bool) -> None:
    """Put the program on the path and, on the chip, its compile cache in
    the checkout (``repro.jax_cache``), caching every program."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.jax_cache import enable_compile_cache
    except ImportError as e:
        raise NoRun(f"the program is not in this checkout: {e}") from None
    if not rehearse:
        import jax
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def end_to_end(spec, cell, run: Run, setup_s: float):
    lat = driver.latencies(run.schedule, run.window) * 1e3
    values = {
        "query_p99_ms": float(np.percentile(lat, 99)),
        "query_p50_ms": float(np.percentile(lat, 50)),
        "setup_s": setup_s,
    }
    out = {}
    for m in spec["end_to_end"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(spec, cell, run: Run):
    out = {}
    for m in spec["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the rehearsal sizes; no metric")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        place_cache()
    spec, cell, config, traffic = load_cell(args.workload, args.rehearse)
    info = device_check(int(cell["chips"]), args.rehearse)
    open_program(args.rehearse)
    import jax
    peaks = None
    if not args.rehearse:
        from bench.lib.peaks import peaks as peak_table
        peaks = peak_table(info["kind"])

    sut = Cell(config, traffic, log)
    setup = {}
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = sut.window(args.seed, args.seconds, trace_dir, before_window=lambda:
                     setup.setdefault("s", time.perf_counter() - T_START))
    run.peaks = peaks
    stats = [d.memory_stats() or {}
             for d in jax.devices()[:int(cell["chips"])]]
    info["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0))
                                    for s in stats)
    sut.close()
    gc.collect()

    breakdown = None
    if trace_dir is not None:
        path = tracing.xplane_path(trace_dir)
        if path is not None:
            for line in tracing.describe(path):
                log(f"trace: {line}")
            run.trace = tracing.read(path)
            if run.trace.devices and run.trace.window is not None:
                info["busy_s"] = run.trace.busy_s()
                info["window_s"] = run.trace.window_s()
                breakdown = {"device_ops": run.trace.top_ops(),
                             "idle_gaps": run.trace.idle_gaps()}
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    readings = sut.check(run)
    ok, checks = verdict(readings, traffic["limits"])
    log(f"reference: {readings.compared} answers compared in "
        f"{time.perf_counter() - t:.3f} s; worst {readings.worst}")
    if args.trace:
        metrics = per_layer(spec, cell, run)
    else:
        metrics = end_to_end(spec, cell, run, setup["s"])
    result = {"correct": ok, "attempted": len(run.schedule.at_s),
              "failed": readings.unanswered, "metrics": metrics,
              "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["metrics"] = {}
        result["rehearsal"] = {"would_report": sorted(metrics)}
    live = sut.ingest_report(run)
    if live is not None:
        result["ingest"] = live
        log(f"ingest: {live}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoRun as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
