"""The benchmark's command: one process, one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Sets up, warms up, measures for ``--seconds``, prints earlier lines
freely and the result object as the LAST line of stdout.  It never
selects a platform: it exits non-zero, without a result line, unless
JAX's own first device is a TPU and the host holds as many as the cell
asks for.  No option relaxes that; ``harness.run_cell`` is the rest of a
run, and the CPU tests call it directly at tiny sizes.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    # fail before touching the chip when the program is not next to us
    import parallel_eda_tpu  # noqa: F401

    from benchmark import harness

    manifest = harness.load_manifest(REPO)
    cell = harness.load_cell(manifest, REPO, args.workload)
    harness.require_tpu(cell.chips)
    result = harness.run_cell(REPO, args.workload, args.seed,
                              args.seconds, bool(args.trace),
                              t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
