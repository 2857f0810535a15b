# One function per paper table. Print ``name,value,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,table2] [--full]

Emits one CSV row per measurement: ``name,value,derived``.  Paper
benches run the calibrated simulator at the paper's configuration
(100 tiles ~ one image, as §V-C..G; fig14 full scale behind --full);
``roofline`` reads the dry-run sweep results.  The ``pr2`` bench
additionally writes machine-readable ``BENCH_PR2.json`` (chaining /
micro-batching perf trajectory) at the repo root.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (fig7..fig14,roofline)")
    ap.add_argument("--full", action="store_true",
                    help="full-scale fig14 (36,848 tiles; minutes)")
    ap.add_argument("--no-measure", action="store_true",
                    help="skip real variant timing in fig7")
    ap.add_argument("--pr2-json", default=None,
                    help="path for the pr2 bench JSON (default: BENCH_PR2.json)")
    ap.add_argument("--pr3-json", default=None,
                    help="path for the pr3 bench JSON (default: BENCH_PR3.json)")
    ap.add_argument("--pr4-json", default=None,
                    help="path for the pr4 bench JSON (default: BENCH_PR4.json)")
    ap.add_argument("--pr5-json", default=None,
                    help="path for the pr5 bench JSON (default: BENCH_PR5.json)")
    ap.add_argument("--pr6-json", default=None,
                    help="path for the pr6 bench JSON (default: BENCH_PR6.json)")
    ap.add_argument("--pr7-json", default=None,
                    help="path for the pr7 bench JSON (default: BENCH_PR7.json)")
    ap.add_argument("--pr9-json", default=None,
                    help="path for the pr9 bench JSON (default: BENCH_PR9.json)")
    ap.add_argument("--pr10-json", default=None,
                    help="path for the pr10 bench JSON (default: BENCH_PR10.json)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.paper_figs import ALL_BENCHES

    selected = (
        args.only.split(",")
        if args.only
        else list(ALL_BENCHES)
        + ["staging", "pr2", "pr3", "pr4", "pr5", "pr6", "pr7", "pr9",
           "pr10", "roofline"]
    )
    print("name,value,derived")
    errors = 0
    for name in selected:
        t0 = time.perf_counter()
        try:
            if name == "pr2":
                from benchmarks.pr2 import bench_pr2

                bench_rows = bench_pr2(args.pr2_json)
            elif name == "pr3":
                from benchmarks.transport import bench_pr3

                bench_rows = bench_pr3(args.pr3_json)
            elif name == "pr4":
                from benchmarks.dataplane import bench_pr4

                bench_rows = bench_pr4(args.pr4_json)
            elif name == "pr5":
                from benchmarks.network import bench_pr5

                bench_rows = bench_pr5(args.pr5_json)
            elif name == "pr6":
                from benchmarks.serving import bench_pr6

                bench_rows = bench_pr6(args.pr6_json)
            elif name == "pr7":
                from benchmarks.faults import bench_pr7

                bench_rows = bench_pr7(args.pr7_json)
            elif name == "pr9":
                from benchmarks.degradation import bench_pr9

                bench_rows = bench_pr9(args.pr9_json)
            elif name == "pr10":
                from benchmarks.eventsim import bench_pr10

                bench_rows = bench_pr10(args.pr10_json)
            elif name == "roofline":
                from benchmarks.roofline import OUT, rows

                if not OUT.exists():
                    print(f"roofline/skipped,0,run repro.launch.dryrun --sweep")
                    continue
                bench_rows = rows("16x16") + rows("2x16x16")
            elif name == "staging":
                from benchmarks.staging import bench_staging

                bench_rows = bench_staging()
            elif name == "fig14":
                bench_rows = ALL_BENCHES[name](full=args.full)
            elif name == "fig7":
                bench_rows = ALL_BENCHES[name](measure=not args.no_measure)
            else:
                bench_rows = ALL_BENCHES[name]()
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            errors += 1
            continue
        for row_name, value, derived in bench_rows:
            print(f"{row_name},{value:.6g},{derived}")
        print(f"{name}/bench_wall_s,{time.perf_counter() - t0:.1f},harness timing")
    if errors:
        sys.exit(f"{errors} bench(es) printed an ERROR row")


if __name__ == "__main__":
    main()
