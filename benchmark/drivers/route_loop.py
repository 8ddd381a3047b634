"""Driver ``route_loop``: route one fixed placed problem again and
again for the window.

Set-up builds the configuration's problem at the mix's channel width,
brings the program's compile cache up and routes once untimed (the
only way to touch every dispatch variant; the route is deterministic,
so the window then compiles nothing).  The window times whole routes
through ``flow.run_route(timing_driven=True)`` with the result on the
host; the route in flight at the deadline is finished and counted.
Everything that judges a route runs after the window, outside every
clock: ``reference.py``'s legality and sink delays on every route, the
serial router (``native/serial_route.cc``) on the same placed problem
for the wirelength ratio, and one relaxation fixpoint of a seeded cost
field against Dijkstra.
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from benchmark import harness, problem, reference

def _relax_check(f, traffic: dict, seed: int, plane_dtype: str) -> float:
    """Widest relative gap, over a seeded sample of nets, between one
    ``planes_relax`` fixpoint of a seeded congestion field and float64
    Dijkstra on the same field.  The field: two seeded wire seeds per
    net, congestion uniform in [0.5, 2) x 1e-10 s scaled by (1 - crit),
    infinite outside the net's bounding box, crit uniform in [0, 0.9)."""
    import jax.numpy as jnp

    from parallel_eda_tpu.route.planes import build_planes, planes_relax

    rr, term = f.rr, f.term
    g = reference.GraphArrays.of(rr)
    N = g.num_nodes
    rng = np.random.default_rng(seed)
    B = min(int(traffic["relax_sample_nets"]), term.num_nets)
    nets = rng.choice(term.num_nets, size=B, replace=False)
    wire = (g.node_type == reference.CHANX) | (g.node_type == reference.CHANY)
    inside = ((rr.xhigh[None] >= term.bb_xmin[nets, None])
              & (rr.xlow[None] <= term.bb_xmax[nets, None])
              & (rr.yhigh[None] >= term.bb_ymin[nets, None])
              & (rr.ylow[None] <= term.bb_ymax[nets, None]))
    crit = rng.uniform(0.0, 0.9, (B, 1)).astype(np.float32)
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    cong = np.where(inside, (1 - crit) * cong, np.inf).astype(np.float32)
    seeds = [rng.choice(np.flatnonzero(wire & inside[b]), 2, replace=False)
             for b in range(B)]

    pg = build_planes(rr)
    noc = np.asarray(pg.node_of_cell)
    con = np.asarray(pg.cell_of_node)
    d0 = np.full((B, N), np.inf, np.float32)
    for b in range(B):
        d0[b, seeds[b]] = 0.0
    dist_flat, _, _, stats = planes_relax(
        pg, jnp.asarray(d0[:, noc]), jnp.asarray(cong[:, noc]),
        jnp.asarray(crit)[:, :, None, None],
        jnp.zeros((B, pg.ncells), jnp.float32),
        int(traffic["relax_sweep_ceiling"]), plane_dtype=plane_dtype)
    dist_flat = np.asarray(dist_flat)
    if int(np.asarray(stats)[0]) >= int(traffic["relax_sweep_ceiling"]):
        return float("inf")         # no fixpoint under the ceiling
    got = np.full((B, N), np.inf)
    got[:, wire] = dist_flat[:, con[wire]]
    worst = 0.0
    for b in range(B):
        ref = reference.dijkstra_wire_dist(
            g, seeds[b], cong[b].astype(np.float64), float(crit[b, 0]))
        worst = max(worst, reference.relax_gap(ref, got[b]))
    return worst


def run(cell: harness.Cell, env: harness.Env) -> harness.Outcome:
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.route.serial_native import NativeSerialRouter

    cfg, traffic, tr = cell.config, cell.traffic, env.tracing
    limits = traffic["limits"]
    reg = harness.fresh_metrics()
    f = problem.build_placed(cell, int(traffic["chan_width"]))
    opts = problem.router_opts(cfg, env.router_overrides)
    timing = bool(cfg["router"]["timing_driven"])
    print_id = problem.fingerprint(f)

    def route_once():
        # a fresh analyzer per route: each must start from the same
        # criticalities, not from the previous route's result
        f.analyzer = None
        t0 = time.perf_counter()
        F.run_route(f, opts, timing_driven=timing, verify=False)
        dt = time.perf_counter() - t0
        return dt, f.route, f.crit_path_delay

    warm_s, warm, _ = route_once()
    compiles0 = reg.counter("route.dispatch.compiles").value
    setup_s = time.perf_counter() - env.t_start
    harness.say(phase="setup", setup_s=setup_s, warm_route_s=warm_s,
            grid=[f.grid.nx, f.grid.ny], nets=int(f.term.num_nets),
            rr_nodes=int(f.rr.num_nodes), dispatch_compiles=compiles0,
            warm_wirelength=int(warm.wirelength))

    # ---- the window
    times, routes, cpds, gauges = [], [], [], []
    t_w0 = time.perf_counter()
    tr.begin_slice(float(traffic["trace_offset_s"]),
                   float(traffic["trace_seconds"]))
    while time.perf_counter() - t_w0 < env.seconds:
        with tr.span("bench.route"):
            dt, route, cpd = route_once()
        times.append(dt)
        routes.append(route)
        cpds.append(cpd)
        gauges.append(reg.values("route.pipeline."))
    window_s = time.perf_counter() - t_w0
    compiles1 = reg.counter("route.dispatch.compiles").value
    peak_bytes = harness.memory_peak_bytes()
    tr.finish()

    # ---- judged outside every clock
    g = reference.GraphArrays.of(f.rr)
    term = f.term
    judged = [reference.judge(g, term.source, term.sinks, term.num_sinks,
                              r.paths, r.sink_delay) for r in routes]
    problems = [p for j in judged for p in j["problems"]]
    for p in problems[:10]:
        print(f"illegal: {p}", flush=True)
    bad = sum(1 for r, j in zip(routes, judged)
              if j["problems"] or not r.success)
    wls = [int(r.wirelength) for r in routes]
    t0 = time.perf_counter()
    native = NativeSerialRouter(f.rr).route(f.term)
    native_wl = int(native.wirelength)
    harness.say(phase="native", seconds=time.perf_counter() - t0,
                success=bool(native.success), wirelength=native_wl,
                iterations=int(native.iterations))
    relax = _relax_check(f, traffic, env.seed, opts.plane_dtype)
    checks = [
        harness.exactly("problem_sha256", print_id,
                        traffic["problem_sha256"]),
        harness.exactly("routes_not_legal", bad, 0),
        harness.exactly("wirelength_recount_diff", max(
            abs(j["wirelength"] - w) for j, w in zip(judged, wls)), 0),
        harness.exactly("occupancy_drift", max(
            int(np.abs(j["occ"] - np.asarray(r.occ, np.int64)).sum())
            for j, r in zip(judged, routes)), 0),
        harness.exactly("wirelength_spread", max(wls) - min(wls), 0),
        harness.exactly("native_route_legal", bool(native.success), True),
        harness.at_most("wirelength_x", max(wls) / native_wl,
                        limits["wirelength_x"]),
        harness.at_most("sink_delay_gap", max(
            j["delay_gap"] for j in judged), limits["sink_delay_gap"]),
        harness.at_most("relax_gap", relax, limits["relax_gap"]),
        harness.exactly("crit_path_finite_positive", all(
            math.isfinite(c) and c > 0 for c in cpds), True),
        harness.exactly("compiles_in_window", compiles1 - compiles0, 0),
    ]
    last = routes[-1]
    harness.say(phase="window", window_s=window_s, routes=len(routes),
            route_s_each=times, iterations=int(last.iterations),
            windows=len(last.stats), sweeps=int(last.total_relax_steps),
            wirelength=wls[-1], native_wirelength=native_wl)
    return harness.Outcome(
        attempted=len(routes), failed=bad, setup_s=setup_s,
        end_to_end={"route_s": statistics.median(times)},
        checks=checks,
        ctx={"routes": routes, "route_times": times,
             "wirelength_x": wls[-1] / native_wl,
             "crit_path_ns": cpds[-1] * 1e9,
             "pipeline_gauges": gauges, "memory_peak_bytes": peak_bytes,
             "batch_size": int(opts.batch_size),
             "plane_shape": [int(traffic["chan_width"]),
                             int(f.grid.nx), int(f.grid.ny)],
             "registry": reg.values("route.")})
