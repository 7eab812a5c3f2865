"""Where one cell's time goes, by the program's own spans and stages; never
run by the benchmark's own runs.

    python bench/stages.py --workload wsj1.query --seeds 11,12,13,14 \\
        --trace-seed 15 --seconds 20 --out stages.jsonl

One build, one untraced window per ``--seeds`` entry, then one traced
window of ``--trace-seed``.  Each untraced window prints the line
``tools.py readings`` prints (latency percentiles, ``correct``).  The
traced window prints the cell's per-layer metrics (``bench/metrics``) and
``stages``, read from the same trace by ``lib/program_trace.py``:

- ``idle_pct``: the device's idle share of the window, split by the
  innermost ``repro.*`` span the host was in (``none``: in no span, the
  batch not yet closed), in % of the window;
- ``spans``: per ``repro.*`` span in the window, its count, mean length
  and mean length less the device's busy time inside it, in ms;
- ``fused_pct``: the fused program's device time by ``jax.named_scope``
  stage, in % (``other``: operations outside the four stages);
- ``per_flush``: flushes, launches per flush and the host time a flush
  spends in each layer, beside ``host_ms_per_flush``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.lib import program_trace, spans  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402
from bench.lib.cell import Cell, verdict  # noqa: E402
from bench.run import (  # noqa: E402
    TRACE_DIR, device_check, load_cell, log, open_program, per_layer,
    place_cache)
from bench.tools import emit, summary  # noqa: E402


def stages(run, prog) -> dict:
    tr = run.trace
    win = tr.window_s()
    idle = program_trace.idle_by_stage(tr, prog)
    by_span = {}
    for name, v in sorted(prog.spans.items()):
        inside = [(s, e) for s, e, _ in v
                  if tr.window[0] <= s <= tr.window[1]]
        if not inside:
            continue
        host = program_trace.minus_device(tr, prog, name)
        by_span[name] = {
            "n": len(inside),
            "mean_ms": 1e3 * sum(e - s for s, e in inside) / len(inside),
            "host_ms": 1e3 * sum(host) / len(host)}
    fused = program_trace.stage_time(tr, prog)
    total = sum(fused.values())
    flushes = spans.flushes(run)
    launches = run.delta("batches_served")
    per_flush = {"flushes": flushes,
                 "launches_per_flush": launches / flushes if flushes else None}
    if flushes:
        for name in ("repro.service.flush", "repro.engine.execute",
                     "repro.engine.plan", "repro.device.refresh",
                     "repro.device.prep", "repro.device.launch",
                     "repro.device.wait", "repro.device.readback",
                     "repro.device.unpack"):
            d = spans.delta(run, name)
            per_flush[name] = {"self_ms": 1e3 * d[2] / flushes,
                               "total_ms": 1e3 * d[1] / flushes}
    return {
        "window_s": win,
        "idle_pct": {str(k or "none"): 100.0 * v / win
                     for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_in_flush_pct": 100.0 * sum(v for k, v in idle.items()
                                         if k is not None) / win,
        "spans": by_span,
        "fused_pct": {str(k or "other"): 100.0 * v / total
                      for k, v in fused.items()} if total else {},
        "per_flush": per_flush,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="",
                    help="untraced windows, comma-separated seeds")
    ap.add_argument("--trace-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        place_cache()
    spec, cell, config, traffic = load_cell(args.workload, args.rehearse)
    info = device_check(int(cell["chips"]), args.rehearse)
    open_program(args.rehearse)
    t = time.perf_counter()
    sut = Cell(config, traffic, log)
    log(f"build {time.perf_counter() - t:.3f} s")
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = sut.window(seed, args.seconds)
        ok, _ = verdict(sut.check(run), traffic["limits"])
        emit({"workload": cell["name"], "seed": seed, "traced": False,
              "correct": ok, **summary(run)}, args.out)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run = sut.window(args.trace_seed, args.seconds, TRACE_DIR)
    if not args.rehearse:
        from bench.lib.peaks import peaks
        run.peaks = peaks(info["kind"])
    path = tracing.xplane_path(TRACE_DIR)
    run.trace = tracing.read(path)
    row = {"workload": cell["name"], "seed": args.trace_seed, "traced": True,
           **summary(run)}
    if run.trace.devices:
        row["stages"] = stages(run, program_trace.read(path))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    row["correct"], _ = verdict(sut.check(run), traffic["limits"])
    row["metrics"] = {k: v["value"] for k, v in
                      per_layer(spec, cell, run).items()}
    emit(row, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
