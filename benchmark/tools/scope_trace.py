"""Run one cell with tracing on and print the scope table of its
traced slice: device self time per ``route.dev.*`` scope, the share
left unscoped, and the idle gaps by the program's own host spans.

    python3 benchmark/tools/scope_trace.py --workload route_relaxed \
        [--seed 1] [--seconds 50] [--out chiprun_out/scopes.json]

The run is the cell's own (``harness.run_cell`` with ``trace`` on); the
one difference is a ``Tracing`` whose ``finish`` hands the trace to
``scope_reduce`` before the harness reduces and drops it.  The compile
cache is keyed WITH metadata here: by default JAX leaves names out of
the key, and a cache filled before the scopes existed would serve
programs that carry none.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


from benchmark import harness, scope_reduce, trace_reduce  # noqa: E402


class ScopeTracing(harness.Tracing):
    """``harness.Tracing`` that also reduces the slice by scope, before
    the harness drops the trace.  The last run's table is ``found``."""

    found: dict = {}

    def finish(self) -> None:
        if self._thread is not None:
            self._thread.join()
            files = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))
            if files and self._error is None:
                t0 = time.perf_counter()
                planes = scope_reduce.planes_from_xplane(files[-1])
                planes.append(trace_reduce.host_spans_plane(
                    planes, self._spans, self._window_t0))
                red = scope_reduce.reduce(planes)
                red["reduce_s"] = time.perf_counter() - t0
                red["trace_bytes"] = os.path.getsize(files[-1])
                ScopeTracing.found = red
        super().finish()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: the manifest's)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    manifest = harness.load_manifest(REPO)
    cell = harness.load_cell(manifest, REPO, args.workload)
    harness.require_tpu(cell.chips)
    harness.Tracing = ScopeTracing
    seconds = (manifest["run_seconds"] if args.seconds is None
               else args.seconds)
    result = harness.run_cell(REPO, args.workload, args.seed, seconds,
                              trace=True)
    found = ScopeTracing.found
    if not found:
        print("scope_trace: the run wrote no trace", file=sys.stderr)
        return 1
    print(scope_reduce.table(found), flush=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    relax = next((r[2] for r in found["scopes"]
                  if r[0] == scope_reduce.SCOPE_PREFIX + "relax"), None)
    busy_us = metrics.get("kernel.busy_us_per_sweep")
    if relax is not None and busy_us is not None:
        # the route's busy time over its sweeps, times relax's share
        found["relax_us_per_sweep"] = busy_us * relax / 100.0
        print(f"relaxation: {found['relax_us_per_sweep']:.1f} us a sweep "
              f"of {busy_us:.1f} us busy a sweep", flush=True)
    doc = {"workload": args.workload, "seed": args.seed,
           "correct": result["correct"], "device": result["device"],
           "metrics": metrics, "scopes": found}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    print(json.dumps(doc), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
