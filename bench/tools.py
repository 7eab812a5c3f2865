"""Measurement tools that share one build across windows; never run by the
benchmark's own runs.

    python bench/tools.py sweep --workload wsj1.query --rates 100,200,300 \\
        --seconds 10 --seed 1
    python bench/tools.py readings --workload wsj1.query --seeds 11,12,13 \\
        --seconds 10 --control 3
    python bench/tools.py readings --workload wsj1.query --seeds 21 \\
        --seconds 15 --control 0 --ingest 0:10,10:10,50:10,200:10

``sweep`` drives one window per rate, in the order given, on one build and
prints, per rate, the latency percentiles, the completed rate and how late
the flushes ran: it finds the highest rate a cell sustains.  It stops after
the first rate whose completed rate falls under 90% of the offered one.
``readings`` drives one window per seed at the cell's own rate and
prints the numbers ``correct`` compares for the program and, on the first
``--control`` seeds, for the control (the reference computed in bfloat16)
on the same queries: the readings the limits are set from.  ``both`` does
the sweep, then the readings at four fifths of the highest rate sustained.
``--ingest <docs_per_s>:<batch_docs>`` overlays a write stream on the
cell's traffic in memory (a comma list runs each overlay in turn, on the
same build, the stream going on from one window to the next); each window
then also reports the stream, the compiles and new launch keys of the
window, the delta rebuilds, and the device's peak memory.
Each result is one JSON line on standard output and, with ``--out``,
appended to that file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from bench.lib import driver  # noqa: E402
from bench.lib.cell import Cell, verdict  # noqa: E402
from bench.lib.schedule import with_ingest  # noqa: E402
from bench.run import (  # noqa: E402
    device_check, load_cell, log, open_program, place_cache)

COMPARED = ("unanswered", "conj_wrong", "rank_gap", "score_gap", "compared")


def emit(row: dict, out: str | None) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "a") as fh:
            fh.write(line + "\n")


def summary(run) -> dict:
    lat = driver.latencies(run.schedule, run.window) * 1e3
    w = run.window
    tail = lat[int(0.9 * len(lat)):]
    lag = [f.start - run.schedule.close_s[f.group] for f in w.flushes]
    return {
        "queries": len(lat), "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "last_decile_p50_ms": float(np.median(tail)),
        "done_qps": len(lat) / w.length_s, "window_s": w.length_s,
        "flush_lag_p50_ms": 1e3 * float(np.median(lag)),
        "flush_ms_mean": 1e3 * float(np.mean([f.end - f.start
                                                for f in w.flushes])),
        "flushes": len(w.flushes), "compiles": run.compiles,
        "device": run.served("device"), "host": run.served("host"),
    }


def live(sut: Cell, run, start: dict) -> dict:
    """What a window with a write stream did to the program: ``start`` is
    the delta's size when the window was built."""
    import jax
    if run.schedule.ingest_at_s is None:
        return {}
    b, a = run.before, run.after
    span = "repro.device.refresh"
    rebuilds = run.delta("delta_refreshes")
    refresh_s = (a["spans"].get(span, [0, 0.0])[1]
                 - b["spans"].get(span, [0, 0.0])[1])
    ms = [1e3 * (x.end - x.start) for x in run.window.ingests]
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "stream": sut.ingest_report(run), **start,
        "ingest_ms_p50": float(np.median(ms)) if ms else None,
        "ingest_ms_max": float(np.max(ms)) if ms else None,
        "rebuilds": rebuilds,
        "rebuilds_per_flush": rebuilds / max(1, len(run.window.flushes)),
        "refresh_ms_per_rebuild": 1e3 * refresh_s / rebuilds
        if rebuilds else None,
        "compactions": run.delta("delta_compactions"),
        "new_launch_keys": [list(k) for k in list(
            sut.eng.resident.launch_buckets)[b["launch_keys"]:]],
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def delta_size(sut: Cell) -> dict:
    return {"horizon_at_start": sut.horizon,
            "delta_blocks_at_start": sut.eng.resident.delta_blocks}


def sustained(row: dict, deadline_ms: float) -> bool:
    """The window kept up: it completed nine tenths of the offered rate
    (the window's length includes the last group's deadline), its last
    tenth waited no longer than three times the median, and flushes
    started within a deadline of when they were due."""
    return (row["done_qps"] >= 0.9 * row["rate_qps"]
            and row["last_decile_p50_ms"] <= 3 * row["p50_ms"]
            and row["flush_lag_p50_ms"] <= deadline_ms)


def sweep(sut: Cell, name: str, rates, seed: int, seconds: float,
          out: str | None) -> float | None:
    """One window per rate; the highest rate sustained, or None."""
    traffic, best = sut.traffic, None
    for rate in rates:
        sut.traffic = dict(traffic, rate_qps=rate)
        start = delta_size(sut)
        run = sut.window(seed, seconds)
        row = {"workload": name, "rate_qps": rate, **summary(run),
               **live(sut, run, start)}
        row["sustained"] = sustained(row, float(traffic["deadline_ms"]))
        emit(row, out)
        if row["sustained"]:
            best = rate
        if row["done_qps"] < 0.9 * rate:
            break
    sut.traffic = traffic
    return best


def readings(sut: Cell, name: str, seeds, seconds: float, control: int,
             out: str | None) -> None:
    for n, seed in enumerate(seeds):
        start = delta_size(sut)
        run = sut.window(seed, seconds)
        row = {"workload": name, "seed": seed,
               "rate_qps": float(sut.traffic["rate_qps"]), **summary(run),
               **live(sut, run, start)}
        t = time.perf_counter()
        got = sut.check(run)
        row["check_s"] = time.perf_counter() - t
        row["correct"], row["checks"] = verdict(got, sut.traffic["limits"])
        row["program"] = {k: getattr(got, k) for k in COMPARED}
        if n < control:
            ctl = sut.check(run, control=True)
            row["control"] = {k: getattr(ctl, k) for k in COMPARED}
        emit(row, out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "readings", "both"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on the first N seeds")
    ap.add_argument("--out", default=None)
    ap.add_argument("--ingest", default="",
                    help="docs_per_s:batch_docs[,...]: overlay a write "
                         "stream on the cell's traffic")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        place_cache()
    _spec, cell, config, traffic = load_cell(args.workload, args.rehearse)
    device_check(int(cell["chips"]), args.rehearse)
    open_program(args.rehearse)
    t = time.perf_counter()
    sut = Cell(config, traffic, log)
    log(f"build {time.perf_counter() - t:.3f} s")
    overlays = [tuple(x.split(":")) for x in args.ingest.split(",") if x]
    for docs_per_s, batch_docs in overlays or [(None, None)]:
        base = traffic if docs_per_s is None else with_ingest(
            traffic, float(docs_per_s), int(batch_docs))
        sut.traffic = base
        if args.what != "readings":
            best = sweep(sut, cell["name"], [float(r) for r in
                                             args.rates.split(",")],
                         args.seed, args.seconds, args.out)
            log(f"highest rate sustained: {best}")
            if args.what == "both":
                # the readings run at four fifths of the knee
                rate = (0.8 * best if best
                        else 0.5 * float(args.rates.split(",")[0]))
                sut.traffic = dict(base, rate_qps=round(rate))
        if args.what != "sweep":
            readings(sut, cell["name"],
                     [int(s) for s in args.seeds.split(",")],
                     args.seconds, args.control, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
