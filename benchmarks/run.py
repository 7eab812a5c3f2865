"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only table14] [--skip-roofline]``
Prints ``name,us_per_call,derived`` CSV rows (paper-table quantities in the
derived column), then the §Roofline report from results/dryrun.  A bench
that raises prints an ERROR row, the others still run, and the exit code
is then 1.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()

    from benchmarks import tables
    from benchmarks.common import BENCH_DOCS, Emitter

    benches = [
        tables.table2_dvbyte_sizes,
        tables.table3_f_sweep,
        tables.table4_codec_speed,
        tables.table7_components,
        tables.table8_block_sweep,
        tables.table9_static,
        tables.table11_wordlevel,
        tables.table13_growth,
        tables.table14_collation,
        tables.fig4_ingest,
        tables.fig5_query_latency,
        tables.device_query_bench,
    ]
    emit = Emitter()
    failed = []
    print(f"# benchmarks over synthetic WSJ1-like corpus "
          f"(BENCH_SCALE={BENCH_DOCS} docs)")
    print("name,us_per_call,derived")
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        t0 = time.time()
        try:
            bench(emit)
        except Exception as e:  # noqa: BLE001
            failed.append(bench.__name__)
            print(f"{bench.__name__},nan,ERROR {type(e).__name__}: {e}",
                  flush=True)
        print(f"# {bench.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr, flush=True)

    if not args.skip_roofline:
        try:
            from benchmarks.roofline import report
            print("# --- roofline (from results/dryrun) ---")
            report()
        except Exception as e:  # noqa: BLE001
            print(f"# roofline report unavailable: {e}")
    if failed:
        print(f"# benches that errored: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
