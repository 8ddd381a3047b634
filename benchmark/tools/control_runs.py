"""Run a cell's lower-precision control on the chip, at the cell's own
size, and print every number compared beside its limit.

    python3 benchmark/tools/control_runs.py --workload route_relaxed \
        --seeds 11,12,13 --seconds 1 [--relax_seeds 12]

The control is the program's own lower-precision path (bfloat16 planes
committed without the guard: ``plane_dtype="bf16", dtype_guard="off"``)
in the timed path's place; everything else of a run is as it is.  One
process holds the chip and makes every run, so the control's programs
compile once.  ``--relax_seeds N`` first reads the relaxation-vs-
Dijkstra gap of a route cell on N seeds in both precisions: the two
readings a limit is set from.  A control has to come out as NOT
correct; this tool exits 0 only if every control run did.  Not part of
a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONTROL = {"plane_dtype": "bf16", "dtype_guard": "off"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--relax_seeds", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, problem

    manifest = harness.load_manifest(REPO)
    cell = harness.load_cell(manifest, REPO, args.workload)
    harness.require_tpu(cell.chips)
    if args.relax_seeds:
        from parallel_eda_tpu.route.router import (
            enable_persistent_compile_cache)

        enable_persistent_compile_cache()
        driver = harness.load_module(cell.find(
            "drivers", cell.traffic["driver"], ".py"))
        f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
        for dtype in ("f32", "bf16"):
            gaps = [driver._relax_check(f, cell.traffic, 1000 + i, dtype)
                    for i in range(args.relax_seeds)]
            print(json.dumps({"relax_gap": dtype, "seeds": len(gaps),
                              "min": min(gaps), "max": max(gaps),
                              "all": gaps}), flush=True)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(REPO, args.workload, seed, args.seconds,
                                  trace=False, router_overrides=CONTROL)
        print(json.dumps({"control_seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"]}), flush=True)
        all_failed &= not result["correct"]
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
