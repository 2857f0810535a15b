#!/usr/bin/env python3
"""Readings from which a cell's limits (``limits/<cell>.json``) are set.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds <s> [--out <file>]

In one process, so the programs compile once: for every seed of
``--seeds``, a run of the cell's timed path at its own size and load
(``run.measure``, with a short window that still finishes as many
tiles or requests as a run compares), and its compared numbers; for
every seed of ``--control-seeds``, the control: the plain reference
in bfloat16 in the program's place, on as many of the seed's pool
tiles as a run compares, against the float32 reference.  The lower
reading of a number is its largest over the program's seeds, the upper
its smallest over the control's.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import compare, run, tiles as tile_pool

    program = []
    for seed in map(int, args.seeds.split(",")):
        detail: dict = {}
        result = run.measure(args.workload, seed, args.seconds, False,
                             detail=detail)
        program.append({"seed": seed, "correct": result["correct"],
                        **detail["numbers"]})
        print(json.dumps(program[-1]), flush=True)

    import jax.numpy as jnp

    _, _, config, mix = run.spec_of(args.workload)
    reference = __import__(f"bench.references.{config['reference']}",
                           fromlist=["run"])
    n = int(compare.limits(args.workload)["sample"])
    control = []
    for seed in map(int, args.control_seeds.split(",")):
        tiles, _ = tile_pool.start_pool(int(mix["pool"]),
                                        int(config["tile_side"]), seed)()
        per_tile = [compare.tile_numbers(reference.run(t, dtype=jnp.bfloat16),
                                         reference.run(t))
                    for t in tiles[:n]]
        control.append({"seed": seed, **compare.worst(per_tile)})
        print(json.dumps(control[-1]), flush=True)

    keys = ("plane_mismatch", "object_count_gap", "value_gap")
    summary = {
        "workload": args.workload,
        "lower": {k: max(p[k] for p in program) for k in keys},
        "upper": {k: min(c[k] for c in control) for k in keys},
        "program": program, "control": control,
    }
    print(json.dumps({k: summary[k] for k in ("workload", "lower", "upper")}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
