"""Run one route cell of the benchmark, untraced by the profiler, and
print its routes BY WINDOW: the table of ``route/report.py``
``format_window_table`` (what each window is, did and cost, and whether
its result was kept) for the first timed route, and for every timed
route how its wall splits over the named intervals.

    python3 tools/window_report.py --workload route_scale --seed 1 \
        [--seconds 50] [--tracer 0|1] [--out chiprun_out/windows.json]

The run is the cell's own driver (``benchmark/drivers/route_loop.py``)
under ``benchmark/harness.py``'s ``Env``, so its ``route_s`` is an
untraced benchmark run's.  ``RouteResult.wall`` splits the ``route``
stage with no tracer; what ``flow.run_route`` does around the stage
(``flow.route.setup``, ``flow.route.sta``) is read from the spans of an
``obs.Tracer``, which ``--tracer 1`` installs: the same command with 0
and with 1 on one seed is what tracing costs when it is on.  Like
``benchmark/run.py`` it selects no platform; the line it prints names
the one JAX found.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

T_START = time.perf_counter()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from parallel_eda_tpu.route.report import WALL_KEYS  # noqa: E402

# flow.run_route's stages, by the span each is (obs.trace.stage)
FLOW_SPANS = {"flow.route.setup": "setup_s", "route": "stage_s",
              "flow.route.sta": "sta_s"}
# a timed route's line: the benchmark's clock, the wall's intervals and
# their sum, what is left, and under a tracer the flow's stages
ROUTE_KEYS = ("route_s", *WALL_KEYS, "wall_s", "outside_wall_s",
              *FLOW_SPANS.values())


def report(root: str, workload: str, seed: int, seconds: float,
           tracer: bool, work_dir: str = None, t_start: float = None):
    """Run the cell's driver once; returns (text, record)."""
    from parallel_eda_tpu.obs import Tracer, set_tracer
    from parallel_eda_tpu.route.report import format_window_table
    from parallel_eda_tpu.route.router import (
        enable_persistent_compile_cache)

    manifest = harness.load_manifest(root)
    cell = harness.load_cell(manifest, root, workload)
    device = harness.device_info()
    enable_persistent_compile_cache()
    driver = harness.load_module(cell.find(
        "drivers", cell.traffic["driver"], ".py"))
    work_dir = harness.fresh_dir(work_dir or harness.WORK_DIR, workload)
    env = harness.Env(
        seed=int(seed), seconds=float(seconds),
        tracing=harness.Tracing(False, work_dir),
        t_start=time.perf_counter() if t_start is None else t_start,
        work_dir=work_dir)
    tr = Tracer() if tracer else None
    set_tracer(tr)
    try:
        out = driver.run(cell, env)
    finally:
        set_tracer(None)
    routes, times = out.ctx["routes"], out.ctx["route_times"]

    per_route = []
    for r, dt in zip(routes, times):
        row = dict(route_s=dt, **r.wall)
        row["wall_s"] = sum(r.wall.values())
        row["outside_wall_s"] = dt - row["wall_s"]
        per_route.append(row)
    if tr is not None:
        # the timed routes are the LAST of each span (the warm-up route
        # comes first and is not in ``routes``)
        for name, key in FLOW_SPANS.items():
            durs = [e["dur"] / 1e6 for e in tr.events if e["name"] == name]
            for row, d in zip(per_route, durs[-len(per_route):]):
                row[key] = d
    by_window = list(zip(*[[s.route_time_s for s in r.stats]
                           for r in routes]))
    reg = out.ctx.get("registry", {})
    record = {
        "workload": workload, "seed": int(seed), "tracer": int(tracer),
        "device": device, "correct": all(c.ok for c in out.checks),
        "setup_s": out.setup_s, "route_s": statistics.median(times),
        "routes": per_route,
        "windows": [{k: v for k, v in vars(s).items() if v == v}
                    for s in routes[0].stats],
        "window_seconds_max_over_min": [
            max(w) / min(w) for w in by_window if min(w) > 0],
        "counters": {k: v for k, v in reg.items() if k.startswith(
            ("route.window.", "route.endgame.", "route.dispatch.compiles",
             "route.pipeline.blocking_syncs"))},
    }
    first = routes[0]
    lines = [
        f"{workload} seed {seed} on {device['platform']} "
        f"({device['kind']}), tracer {'ON' if tracer else 'off'}: "
        f"{len(routes)} timed routes, route_s {record['route_s']:.4f} "
        f"(each {' '.join(f'{t:.4f}' for t in times)}), setup_s "
        f"{out.setup_s:.1f}, correct {record['correct']}",
        f"first timed route (id {first.route_id}): iterations "
        f"{first.iterations}, sweeps {first.total_relax_steps} "
        f"({first.total_relax_steps_discarded} discarded), net routes "
        f"{first.total_net_routes}, waves {first.total_waves}, "
        f"wirelength {first.wirelength}",
        format_window_table(first)]
    for i, row in enumerate(per_route):
        lines.append(f"route {i + 1}: " + "  ".join(
            f"{k} {row[k]:.4f}" for k in ROUTE_KEYS if k in row))
    lines.append("same window across the routes, longest over shortest: "
                 + " ".join(f"{x:.4f}" for x in
                            record["window_seconds_max_over_min"]))
    return "\n".join(lines), record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    text, record = report(REPO, args.workload, args.seed, args.seconds,
                          bool(args.tracer), t_start=T_START)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(record, fh, indent=1, default=str)
    print(json.dumps(record, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
