"""Benchmark: batched TPU PathFinder routing throughput vs the serial CPU
baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric is nets-routed-per-second over a complete negotiated-congestion
route (the reference's primary throughput counter — nets routed per
iteration over route time, iter_stats.txt schema,
partitioning_multi_sink_delta_stepping_route.cxx:5925-5931).

vs_baseline is the speedup of the batched device router over the
independent heap-based serial CPU PathFinder (route.serial_ref — the
stand-in for serial VPR, whose TBB/boost/METIS deps don't exist in this
image; same rr-graph, same cost model, same convergence criterion,
per-sink A* with the same admissible lookahead).  Both run the full
negotiation to legality on the identical problem; each side's throughput
is its total net-route invocations over its wall time.
"""

import argparse
import json
import os
import re
import sys
import time

# silence the TSL "could not determine host CPU features" WARNING that
# XLA's CPU client prints on first use: it pollutes a driver's captured
# stderr tail.  Must be set before jax (and through it TSL)
# initializes; setdefault so an operator's explicit level wins.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import numpy as np


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# XLA/LLVM noise the log level does NOT silence: the host-machine-
# features (SIGILL risk) warning wall — hundreds of +/-feature tokens
# plus its banner lines — which drowns the useful bench log out of a
# captured stderr tail.
_STDERR_NOISE = re.compile(
    rb"host machine features|SIGILL|cpu_feature_guard|"
    rb"This TensorFlow binary is optimized|"
    rb"absl::InitializeLog|"
    rb"(?:[+-][A-Za-z0-9_.\-]+,){8,}")


def install_stderr_filter():
    """Interpose a line filter on fd 2 so known XLA noise never reaches
    the real stderr (and therefore never lands in a driver's captured
    tail).  fd-level on purpose: the warning wall is printed by native
    code (TSL/LLVM), not through sys.stderr, and subprocesses (the
    backend probe) inherit the filtered fd too.  Returns the saved
    real-stderr fd.  BENCH_NO_STDERR_FILTER=1 disables it."""
    import atexit
    import threading

    if os.environ.get("BENCH_NO_STDERR_FILTER"):
        return None
    r_fd, w_fd = os.pipe()
    real = os.dup(2)
    os.dup2(w_fd, 2)
    os.close(w_fd)

    def pump():
        buf = b""
        while True:
            try:
                chunk = os.read(r_fd, 65536)
            except OSError:
                break
            if not chunk:
                break
            buf += chunk
            lines = buf.split(b"\n")
            buf = lines.pop()
            for ln in lines:
                if not _STDERR_NOISE.search(ln):
                    os.write(real, ln + b"\n")
        if buf and not _STDERR_NOISE.search(buf):
            os.write(real, buf)
        os.close(r_fd)

    t = threading.Thread(target=pump, daemon=True,
                         name="bench-stderr-filter")
    t.start()

    def restore():
        try:
            sys.stderr.flush()
        except Exception:
            pass
        # rebinding fd 2 to the real stderr drops the pipe's last
        # writer: the pump drains what's left and exits
        os.dup2(real, 2)
        t.join(timeout=5.0)

    atexit.register(restore)
    return real


def _config_key(args) -> str:
    """Canonical id of this exact bench config: the run corpus
    scenario id (runs/<key>.jsonl)."""
    if args.place_only:
        return (f"place_l{args.luts}_w{args.chan_width}"
                f"_m{args.moves_per_step}")
    if args.sweep_only:
        return (f"sweep_{args.program}_c{args.sweep_crop}_b{args.batch}"
                f"_g{args.sweep_max_grid}")
    # _d suffix only for non-default divs: the default-config key
    # must stay stable or the corpus trajectory would fork
    from parallel_eda_tpu.route import RouterOpts as _RO
    div = (f"_d{args.budget_div}"
           if args.budget_div != _RO().sweep_budget_div else "")
    # same stability rule for the plane dtype: suffix only when it
    # leaves the default, so the f32 config of record keeps its
    # scenario id
    pd = (f"_p{args.plane_dtype}"
          if getattr(args, "plane_dtype", "f32") != "f32" else "")
    return (f"scale{int(bool(args.scale))}_l{args.luts}"
            f"_w{args.chan_width}_{args.program}_b{args.batch}"
            f"{div}{pd}")


def _runstore():
    from parallel_eda_tpu.obs import runstore
    return runstore


def emit(args, line: dict, gauges=None, series=None,
         congestion=None, qor=None) -> None:
    """Print the bench line.  Every emitted row is stamped with
    provenance (schema_version, ts, git rev, backend, device kind,
    scenario — so a captured row is self-describing and flow_doctor can
    refuse cross-backend diffs) and, unless --no_corpus, appended to
    the runs/<scenario>.jsonl corpus."""
    import jax

    rs = _runstore()
    line = dict(line)
    detail = line.get("detail") or {}
    backend = detail.get("platform") or "unknown"
    scenario = _config_key(args)
    line.update({
        "schema_version": rs.SCHEMA_VERSION,
        "ts": rs.now_iso(),
        "git_rev": rs.git_rev(os.path.dirname(os.path.abspath(__file__))),
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "scenario": scenario,
    })
    print(json.dumps(line))
    if getattr(args, "no_corpus", False):
        return
    # corpus append must never kill the bench line it rides on
    try:
        rec = rs.make_record(
            scenario, {k: v for k, v in sorted(vars(args).items())},
            line.get("metric", "unknown"), line.get("value", -1.0),
            line.get("unit", "none"), backend, line["device_kind"],
            qor=qor, gauges=gauges, series=series,
            congestion=congestion, detail=detail or None,
            ts=line["ts"], rev=line["git_rev"],
            # absent means f32 (pre-dtype-era rows stay valid), so
            # only non-default dtypes are stamped
            plane_dtype=(args.plane_dtype
                         if getattr(args, "plane_dtype", "f32") != "f32"
                         else None))
        path = rs.append_run(getattr(args, "runs_dir", "runs"), rec)
        log(f"corpus: appended {scenario} row to {path}")
    except Exception as e:
        log(f"corpus append failed (non-fatal): {type(e).__name__}: {e}")


def build(num_luts: int, chan_width: int, seed: int = 11,
          place: bool = False):
    from parallel_eda_tpu.flow import synth_flow

    flow = synth_flow(num_luts=num_luts, num_inputs=12, num_outputs=12,
                      chan_width=chan_width, seed=seed)
    if place:
        # anneal before routing (the flow's normal shape).  The 60-LUT
        # smoke config has always routed from the initial placement and
        # keeps doing so for cross-round comparability, but at >=600
        # LUTs an unannealed placement is effectively unroutable at any
        # sane W (measured: diffuse ~9% wire overuse after 50 serial
        # iterations at 600 LUTs/W=20), so the at-scale config MUST
        # place first.  The native C++ annealer keeps this host-side
        # and deterministic — no extra device programs to compile.
        from parallel_eda_tpu.flow import run_place_native

        flow = run_place_native(flow)
        log(f"placed {flow.pnl.num_blocks} blocks in "
            f"{flow.times['place']:.1f}s (native SA)")
    return flow


def sweep_microbench(args) -> None:
    """Measure the planes relaxation's per-sweep device cost directly:
    one program, two syncs, reports ms/sweep and derived cell-rate at
    several grid sizes."""
    import jax
    import jax.numpy as jnp

    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route.planes import (build_planes, planes_relax,
                                               xla_bytes_per_cell)
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import DeviceGrid

    if args.program == "ell":
        raise SystemExit("--sweep_only measures the planes relaxation; "
                         "--program must be planes")
    if args.sweep_crop:
        from parallel_eda_tpu.route.planes import planes_relax_cropped

    rows = []
    # analytic roofline constants (the MFU-style statement for a
    # non-matmul kernel): one XLA sweep reads+writes the 6 state
    # canvases ~15x (4 scans x (in+out) + turn stencils), ~4 B each;
    # achieved cell rate / HBM-bound rate = bandwidth utilization.
    # The peak comes from the one table (obs/devprof): None on a CPU —
    # a CPU run reports no roofline share — and an error for a device
    # kind the table does not know
    from parallel_eda_tpu.obs.devprof import peak_hbm_bytes_per_s
    from parallel_eda_tpu.serve.batcher import unpacked_lane_occupancy
    peak_bw = peak_hbm_bytes_per_s(jax.devices()[0])

    nsweeps = 16
    bytes_per_cell_sweep = float(xla_bytes_per_cell())
    hbm_bound_rate = (peak_bw / bytes_per_cell_sweep
                      if peak_bw else None)
    for nx, W in ((16, 12), (32, 14), (64, 16), (96, 20)):
        if nx > args.sweep_max_grid:
            continue
        arch = minimal_arch(chan_width=W)
        rr = build_rr_graph(arch, DeviceGrid(nx, nx, arch.io_capacity))
        pg = build_planes(rr)
        B = args.batch
        d0 = jnp.full((B, pg.ncells), jnp.inf, jnp.float32)
        d0 = d0.at[:, :: pg.ncells // 7].set(0.0)
        cc = jnp.ones((B, pg.ncells), jnp.float32) * 1e-9
        crit = jnp.zeros((B, 1, 1, 1), jnp.float32)
        w0 = jnp.zeros((B, pg.ncells), jnp.float32)
        if args.sweep_crop:
            # per-net bb-cropped relaxation at a fixed tile: measures
            # the crop's REAL per-sweep cost on this backend, slice +
            # scatter overhead included
            t = min(args.sweep_crop, nx - 1)
            rng = np.random.default_rng(3)
            ox = jnp.asarray(rng.integers(0, nx - t, B), jnp.int32)
            oy = jnp.asarray(rng.integers(0, nx - t, B), jnp.int32)
            fn = jax.jit(lambda d: planes_relax_cropped(
                pg, d, cc, crit, w0, nsweeps, ox, oy, t, t)[0])
        else:
            fn = jax.jit(lambda d: planes_relax(pg, d, cc, crit, w0,
                                                nsweeps)[0])
        fn(d0).block_until_ready()             # compile + warm
        t0 = time.time()
        reps = 3
        for _ in range(reps):
            out = fn(d0)
        out.block_until_ready()
        dt = (time.time() - t0) / (reps * nsweeps)
        if args.sweep_crop:
            # swept work is the tile, not the grid
            t = min(args.sweep_crop, nx - 1)
            cells = B * W * 2 * t * (t + 1)
        else:
            cells = B * pg.ncells
        util = (cells / dt / hbm_bound_rate
                if hbm_bound_rate else None)
        # kernel-layout rider (mirrors Router._plan_block_nets /
        # route.kernel.* gauges)
        if args.sweep_crop:
            t = min(args.sweep_crop, nx - 1)
            shx, shy = (W, t, t + 1), (W, t + 1, t)
        else:
            shx, shy = pg.shape_x, pg.shape_y
        kernel = {"variant": "xla",
                  "lane_occupancy": round(
                      unpacked_lane_occupancy(shx, shy), 4)}
        rows.append({"grid": f"{nx}x{nx}", "W": W, "cells": pg.ncells,
                     "ms_per_sweep": round(dt * 1e3, 3),
                     "cell_rate_G": round(cells / dt / 1e9, 3),
                     "hbm_bound_cell_rate_G": (
                         round(hbm_bound_rate / 1e9, 2)
                         if hbm_bound_rate else None),
                     "bw_utilization": (round(util, 4)
                                        if util is not None else None),
                     "kernel": kernel})
        share = (f"{100 * util:.1f}% of the HBM roofline of the XLA "
                 f"lowering" if util is not None
                 else "no roofline share off the chip")
        log(f"sweep {nx}x{nx} W={W} B={B}: {dt * 1e3:.2f} ms/sweep, "
            f"{cells / dt / 1e9:.2f} Gcell/s ({share})")
    emit(args, {
        "metric": "planes_ms_per_sweep",
        "value": rows[-1]["ms_per_sweep"] if rows else -1.0,
        "unit": "ms",
        "vs_baseline": 0.0,
        "detail": {"platform": jax.devices()[0].platform,
                   "batch": args.batch, "program": args.program,
                   "sweep_crop": args.sweep_crop,
                   "rows": rows}})


def place_microbench(args) -> None:
    """SA moves/sec/chip (BASELINE.json metric #1, place.c:246 try_swap
    semantics): full anneal of the device segment-fused placer vs the
    native C++ serial annealer on the identical initial placement."""
    import jax

    from parallel_eda_tpu.place.sa import Placer, PlacerOpts
    from parallel_eda_tpu.place.serial_sa import serial_sa_place

    flow = build(num_luts=args.luts, chan_width=args.chan_width)
    pnl, grid = flow.pnl, flow.grid
    NB = pnl.num_blocks
    log(f"placement problem: {NB} blocks, grid "
        f"{grid.nx}x{grid.ny}")

    opts = PlacerOpts(moves_per_step=args.moves_per_step, seed=3)
    placer = Placer(pnl, grid, opts)
    from parallel_eda_tpu.obs import (compile_seconds, get_metrics,
                                      reset_compile_seconds)
    c0 = compile_seconds()
    # warmup anneal: compiles every sa_segment shape (cold compiles
    # must not land in the metric of record)
    t0 = time.time()
    placer.place(flow.pos)
    log(f"device warmup anneal: {time.time() - t0:.1f}s")
    compile_warmup_s = compile_seconds() - c0
    get_metrics().reset()        # the measured anneal's snapshots only
    reset_compile_seconds()      # steady-state compile attribution
    t0 = time.time()
    pos_d, stats = placer.place(flow.pos)
    ddt = time.time() - t0
    compile_measured_s = compile_seconds()
    dev_mps = stats.total_moves / max(ddt, 1e-9)
    log(f"device anneal: {ddt:.1f}s, {stats.total_moves} moves, "
        f"{dev_mps / 1e6:.3f} M moves/s, final bb cost "
        f"{stats.final_cost:.1f} (initial {stats.initial_cost:.1f})")

    # baseline failure must not kill the line (same contract as the
    # route bench's serial guards)
    sres = None
    serial_error = None
    try:
        sres = serial_sa_place(pnl, grid, flow.pos, seed=3)
        ser_mps = sres.moves_per_sec
        log(f"native serial anneal: {sres.wall_s:.1f}s, {sres.proposed} "
            f"moves, {ser_mps / 1e6:.3f} M moves/s, final bb cost "
            f"{sres.final_cost:.1f}")
    except Exception as e:
        serial_error = f"{type(e).__name__}: {e}"
        ser_mps = 0.0
        log(f"native serial anneal failed: {serial_error}")

    emit(args, {
        "metric": "sa_moves_per_sec",
        "value": round(dev_mps, 1),
        "unit": "moves/s",
        "vs_baseline": round(dev_mps / max(ser_mps, 1e-9), 4),
        "detail": {
            "platform": jax.devices()[0].platform,
            "num_blocks": NB,
            "moves_per_step": args.moves_per_step,
            "device_wall_s": round(ddt, 2),
            "device_moves": int(stats.total_moves),
            "device_final_bb_cost": round(stats.final_cost, 2),
            "serial_wall_s": round(sres.wall_s, 2) if sres else None,
            "serial_moves": int(sres.proposed) if sres else None,
            "serial_moves_per_sec": round(ser_mps, 1),
            "serial_final_bb_cost": (round(sres.final_cost, 2)
                                     if sres else None),
            "serial_error": serial_error,
            "baseline": "native/serial_sa.cc (place.c try_place "
                        "semantics, -O3, single core)",
            # obs rider: temperature count + SA acceptance from the
            # metrics registry, compile-vs-execute attribution of the
            # measured anneal (jax.monitoring listener)
            "obs": {
                "temps": len(stats.temps),
                "acceptance_rate_mean": (
                    round(get_metrics()
                          .histogram("place.acceptance_rate").mean, 4)
                    if get_metrics()
                    .histogram("place.acceptance_rate").count else None),
                "compile_s_warmup": round(compile_warmup_s, 3),
                "compile_s_measured": round(compile_measured_s, 3),
                "execute_s_measured": round(
                    max(0.0, ddt - compile_measured_s), 3),
            }}})


def main():
    install_stderr_filter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--luts", type=int, default=60)
    ap.add_argument("--chan_width", type=int, default=12)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--program", default="planes",
                    choices=["planes", "ell"],
                    help="device search program")
    ap.add_argument("--scale", action="store_true",
                    help="the at-scale crossover config: "
                         "a >=1200-LUT circuit, full negotiation on both "
                         "routers, vs_baseline = serial wall / device "
                         "wall (wall-clock speedup, not nets/s ratio)")
    ap.add_argument("--sweep_only", action="store_true",
                    help="microbench the planes relaxation per-sweep "
                         "device cost and exit")
    ap.add_argument("--sweep_max_grid", type=int, default=96)
    ap.add_argument("--sweep_crop", type=int, default=0,
                    help="with --sweep_only: measure the bb-CROPPED "
                         "relaxation at this tile size (per-net random "
                         "origins) instead of full canvases")
    ap.add_argument("--serial_timeout", type=float, default=0.0,
                    help="cap serial baseline wall seconds (0 = none); "
                         "a timed-out serial run reports its elapsed "
                         "time as a LOWER BOUND, vs_baseline marked >=")
    ap.add_argument("--skip_serial", action="store_true",
                    help="report device throughput only (vs_baseline 0)")
    ap.add_argument("--py_serial", action="store_true",
                    help="force the pure-Python serial baseline "
                         "(default: the bit-identical native C++ one)")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend (tests and smoke runs "
                         "only: a CPU row is never a device number).  "
                         "Without it the bench needs a TPU and exits "
                         "non-zero, printing no row, when JAX finds "
                         "none")
    ap.add_argument("--place_only", action="store_true",
                    help="measure SA moves/sec/chip (device segment-"
                         "fused annealer vs native serial_sa.cc) and "
                         "exit")
    ap.add_argument("--moves_per_step", type=int, default=256,
                    help="with --place_only: batched proposals per "
                         "device SA step (M)")
    ap.add_argument("--budget_div", type=int, default=None,
                    help="RouterOpts.sweep_budget_div override "
                         "(default: the library default; 1 forces the "
                         "full first-try budgets off-setting)")
    ap.add_argument("--sync", action="store_true",
                    help="disable the async host-device pipeline "
                         "(RouterOpts.pipeline=False): drain every "
                         "dispatch before further host work.  Bit-"
                         "identical results; used by the parity suite "
                         "and for isolating pipeline regressions")
    ap.add_argument("--compile_cache_dir", default=None,
                    help="persistent XLA compile-cache directory "
                         "(default <checkout>/.jax_cache; "
                         "JAX_COMPILATION_CACHE_DIR, when set, wins): a "
                         "second run deserializes the programs instead "
                         "of recompiling them")
    ap.add_argument("--runs_dir",
                    default=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "runs"),
                    help="run-corpus directory: every bench run appends "
                         "one runs/<scenario>.jsonl record "
                         "(obs/runstore.py schema; default %(default)s)")
    ap.add_argument("--no_corpus", action="store_true",
                    help="skip the corpus append (one-off experiments "
                         "that must not pollute the trajectory)")
    ap.add_argument("--trace_out", default=None,
                    help="export a Chrome trace-event JSON of the "
                         "measured route to this path (obs tracer)")
    ap.add_argument("--plane_dtype", default="f32",
                    choices=("f32", "bf16"),
                    help="distance/backtrack plane storage dtype "
                         "(bf16 halves the modeled plane traffic and "
                         "is the dtype the route commits: a different "
                         "result, not a faster f32 one)")
    args = ap.parse_args()
    serial_error = None
    if args.budget_div is None:
        # resolve to the library default up front: the scenario key and
        # the JSON detail must reflect the value that actually runs
        from parallel_eda_tpu.route import RouterOpts as _RO
        args.budget_div = _RO().sweep_budget_div
    if args.scale and args.luts == 60:
        args.luts = 1200
        args.chan_width = 20

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    log(f"platform {platform}")
    if platform != "tpu" and not args.cpu:
        # no fallback: a row measured elsewhere must never stand in
        # for a device number
        log("no TPU found and --cpu not given; refusing to run")
        sys.exit(3)
    from parallel_eda_tpu.route import enable_persistent_compile_cache
    enable_persistent_compile_cache(args.compile_cache_dir)
    # observability riders on every emitted row: the jax.monitoring
    # compile listener lets the bench split compile from execute time
    # without wrapping any jit call site, and the metrics registry
    # carries the per-iteration trajectories
    from parallel_eda_tpu.obs import (enable_compile_capture,
                                      get_devprof, get_metrics)
    enable_compile_capture()
    get_metrics().enabled = True
    if args.trace_out:
        from parallel_eda_tpu.obs import Tracer, set_tracer
        set_tracer(Tracer())
    # device-truth profiler: notes every dispatch variant (warmup
    # included — its own seen-set is fresh even on a warm jit cache);
    # the AOT capture runs after the measured route
    get_devprof().enabled = True

    if args.sweep_only:
        sweep_microbench(args)
        return
    if args.place_only:
        place_microbench(args)
        return
    flow = build(num_luts=args.luts, chan_width=args.chan_width,
                 place=args.scale)
    rr, term = flow.rr, flow.term
    R = term.sinks.shape[0]
    log(f"circuit: {R} nets, rr graph {rr.num_nodes} nodes, "
        f"W={rr.chan_width}")

    from parallel_eda_tpu.route import Router, RouterOpts

    # warmup: one full route populates the compile cache for every
    # program variant the negotiation loop can hit; the SAME Router is
    # reused so the device-resident terminal tables are uploaded once
    router = Router(rr, RouterOpts(
        batch_size=args.batch, program=args.program,
        sweep_budget_div=args.budget_div, pipeline=not args.sync,
        plane_dtype=args.plane_dtype))
    from parallel_eda_tpu.obs import (compile_seconds, get_metrics,
                                      reset_compile_seconds)
    c0 = compile_seconds()
    t0 = time.time()
    res = router.route(term)
    warmup_s = time.time() - t0
    log(f"device warmup route: {warmup_s:.1f}s "
        f"(success={res.success}, iters={res.iterations})")
    compile_warmup_s = compile_seconds() - c0

    get_metrics().reset()        # the measured route's ledger only
    reset_compile_seconds()      # steady-state compile split: the
    t0 = time.time()             # measured run's compile time alone
    res = router.route(term)
    dt = time.time() - t0
    compile_measured_s = compile_seconds()
    log(f"compile split: {compile_warmup_s:.1f}s during warmup, "
        f"{compile_measured_s:.1f}s during the measured route")
    nets_per_sec = res.total_net_routes / dt
    log(f"device route: {dt:.1f}s, {res.total_net_routes} net routes, "
        f"{nets_per_sec:.1f} nets/s, wirelength {res.wirelength}")
    # pipeline ledger of the MEASURED route only: the post-warmup
    # metrics reset cleared the warmup's pipeline gauges and dispatch
    # counters; the variant cache itself is process-wide on purpose, so
    # a fully warmed run reports cache_hits and zero compiles
    pv = get_metrics().values("route.pipeline.")
    dv = get_metrics().values("route.dispatch.")
    log(f"pipeline[{'sync' if args.sync else 'async'}]: "
        f"plan {pv.get('route.pipeline.host_plan_ms_total', 0)}ms "
        f"exec {pv.get('route.pipeline.device_exec_ms_total', 0)}ms "
        f"stall {pv.get('route.pipeline.stall_ms_total', 0)}ms "
        f"overlap {pv.get('route.pipeline.overlap_frac', 0)} "
        f"(host-work {pv.get('route.pipeline.host_overlap_frac', 0)}), "
        f"{pv.get('route.pipeline.blocking_syncs', 0)} blocking syncs, "
        f"{dv.get('route.dispatch.compiles', 0)} compiles / "
        f"{dv.get('route.dispatch.cache_hits', 0)} variant cache hits, "
        f"{pv.get('route.pipeline.upload_skips', 0)} upload skips")

    # device-truth cost capture: AOT-relower every dispatch variant the
    # run noted and read XLA's cost/memory analysis — AFTER dt is
    # recorded, so the half-compile per variant never lands in the
    # measured wall time
    get_devprof().capture_all()
    devcost = get_devprof().summary()
    if "unavailable" in devcost:
        log(f"devcost: unavailable ({devcost['unavailable']})")
    else:
        log(f"devcost[{devcost.get('variants')} variants]: dominant "
            f"{devcost.get('flops', 0):.3g} flops / "
            f"{devcost.get('bytes_accessed', 0):.3g} B accessed, "
            f"peak temp {devcost.get('temp_bytes', 0)} B; measured/"
            f"modeled bytes {devcost.get('bytes_delta')} "
            f"(band 1e±{devcost.get('delta_band_log10')})")

    # serial CPU baseline: identical problem, full negotiation
    if args.skip_serial:
        speedup = 0.0
        serial_nets_per_sec = 0.0
        sres = None
        sdt = 0.0
        native = None
        ndt = 0.0
    else:
        from parallel_eda_tpu.route.serial_ref import SerialRouter

        # the stretch bar: the native C++ serial router (bit-identical
        # algorithm, serial-VPR speed class).  Cheap, so always run it;
        # reported in detail.native_* with vs_native
        native = None
        ndt = 0.0
        if not args.py_serial:
            try:
                from parallel_eda_tpu.route.serial_native import (
                    NativeSerialRouter, native_available)
                if native_available():
                    t0 = time.time()
                    native = NativeSerialRouter(rr).route(
                        term, deadline_s=args.serial_timeout or None)
                    ndt = time.time() - t0
                    log(f"native serial route: {ndt:.3f}s, "
                        f"success={native.success}, "
                        f"wirelength {native.wirelength}")
            except Exception as e:
                log(f"native serial baseline failed: {e}")

        t0 = time.time()
        try:
            sres = SerialRouter(rr).route(
                term, deadline_s=args.serial_timeout or None)
        except Exception as e:   # baseline failure must not kill the line
            log(f"serial baseline failed: {e}")
            serial_error = f"{type(e).__name__}: {e}"
            sres = None
        sdt = time.time() - t0
        if sres is not None:
            s_routes = sum(s["rerouted"] for s in sres.stats)
            serial_nets_per_sec = s_routes / max(sdt, 1e-9)
            log(f"serial route: {sdt:.1f}s, success={sres.success}"
                f"{' (TIMED OUT: lower bound)' if sres.timed_out else ''}"
                f", {serial_nets_per_sec:.1f} nets/s, "
                f"wirelength {sres.wirelength}")
            speedup = nets_per_sec / max(serial_nets_per_sec, 1e-9)
            if sres.wirelength:
                # QoR gap of record (device batch-negotiated vs serial
                # exact incremental): tracked so wirelength regressions
                # show up in the metrics dump, not just the bench line
                get_metrics().gauge("route.wirelength_vs_serial").set(
                    round(res.wirelength / sres.wirelength, 4))
        else:
            serial_nets_per_sec = 0.0
            speedup = 0.0

    wall_semantics = args.scale or bool(sres and sres.timed_out)
    if wall_semantics:
        # at-scale semantics (and the only meaningful one for a
        # timed-out serial run): vs_baseline is the WALL-CLOCK speedup
        # of the complete negotiated route (serial wall / device wall)
        # on the identical problem — the BASELINE.md claim shape.  A
        # timed-out serial run makes it a lower bound.
        sdt_eff = sdt if (not args.skip_serial and sres is not None) \
            else 0.0
        speedup = sdt_eff / max(dt, 1e-9)

    mv = get_metrics().values("route.")
    # corpus riders: the full route.* gauge snapshot, the per-iteration
    # overuse/pres_fac trajectories, and the per-window congestion
    # heatmap rasterized from the router's top_overused ids (extent is
    # the grid plus the IO ring)
    reg = get_metrics()
    corpus_series = {
        "overused_nodes": [int(s.overused_nodes) for s in res.stats],
        "overuse_total": [int(s.overuse_total) for s in res.stats],
        "pres_fac": reg.series("route.pres_fac", phase="route"),
    }
    corpus_congestion = _runstore().congestion_blob(
        res.congestion, rr.xlow, rr.ylow, rr.xhigh, rr.yhigh,
        rr.grid.nx + 2, rr.grid.ny + 2)
    corpus_qor = {"wirelength": int(res.wirelength),
                  "routed": bool(res.success),
                  "iterations": int(res.iterations)}
    if args.trace_out:
        from parallel_eda_tpu.obs import get_tracer
        tr = get_tracer()
        if tr is not None:
            tr.export(args.trace_out)
            log(f"trace exported to {args.trace_out}")
    emit(args, {
        "metric": "nets_routed_per_sec",
        "value": round(float(nets_per_sec), 2),
        "unit": "nets/s",
        "vs_baseline": round(float(speedup), 3),
        "detail": {
            "platform": platform,
            "scale_config": bool(args.scale),
            "budget_div": int(args.budget_div),
            "luts": int(args.luts),
            "rr_nodes": int(rr.num_nodes),
            "routed": bool(res.success),
            "iterations": int(res.iterations),
            "host_syncs": len(res.stats),
            "total_net_routes": int(res.total_net_routes),
            "total_relax_steps": int(res.total_relax_steps),
            "route_time_s": round(dt, 3),
            "wirelength": int(res.wirelength),
            "serial_route_time_s": (round(sdt, 3)
                                    if not args.skip_serial and sres
                                    else None),
            "serial_nets_per_sec": round(float(serial_nets_per_sec), 2),
            "serial_success": bool(sres.success) if sres else None,
            "serial_timed_out": bool(sres.timed_out) if sres else None,
            "serial_wirelength": int(sres.wirelength) if sres else None,
            "serial_error": serial_error,
            "vs_baseline_semantics": (
                "wall_clock_speedup" if wall_semantics
                else "nets_per_sec"),
            "baseline": "serial_ref heap PathFinder (serial-VPR "
                        "stand-in; native C++ stretch bar in native_*)",
            # the stretch bar: bit-identical C++ serial router
            "native_route_time_s": round(ndt, 4) if native else None,
            "native_success": bool(native.success) if native else None,
            "native_wirelength": (int(native.wirelength) if native
                                  else None),
            "vs_native_wall": (round(ndt / max(dt, 1e-9), 5)
                               if native else None),
            # work-efficiency ledger: per-lever accounting of the
            # measured route's relaxation sweeps (useful + wasted ==
            # total by construction) plus the batch-plan shape; the
            # same numbers land in the metrics dump for
            # tools/ledger_report.py
            "ledger": {
                "relax_steps_useful": int(res.total_relax_steps_useful),
                "relax_steps_wasted": int(res.total_relax_steps_wasted),
                "relax_steps_cropped": int(res.total_relax_steps_cropped),
                "bucket_occupancy": mv.get("route.bucket_occupancy"),
                "compaction_ratio": mv.get("route.compaction_ratio"),
                "relax_wasted_frac": mv.get("route.relax_wasted_frac"),
                "wirelength_vs_serial": mv.get(
                    "route.wirelength_vs_serial"),
            },
            # kernel-layout ledger (route.kernel.* gauges, set by the
            # router's block planner for the dominant window shape):
            # the model-side lane occupancy / HBM traffic of the
            # one-net-per-step XLA layout
            "kernel": {
                "lane_occupancy": mv.get("route.kernel.lane_occupancy"),
                "bytes_per_sweep": mv.get(
                    "route.kernel.bytes_per_sweep"),
            },
            # async-pipeline ledger (route.pipeline.* gauges +
            # route.dispatch.* counters, measured route only — the
            # post-warmup reset() cleared the warmup's accumulation):
            # overlap_frac is the pipeline FILL factor (device-busy
            # share of the negotiation timeline); host_overlap_frac is
            # the stricter host-work-overlapped share.  warmup_s is the
            # cold-path wall time — a second process run over a warm
            # compile cache shows it dropping to deserialization cost
            "pipeline": {
                "sync": bool(args.sync),
                "warmup_s": round(warmup_s, 3),
                "plan_ms": pv.get("route.pipeline.host_plan_ms_total"),
                "exec_ms": pv.get(
                    "route.pipeline.device_exec_ms_total"),
                "stall_ms": pv.get("route.pipeline.stall_ms_total"),
                "serial_ms": pv.get(
                    "route.pipeline.host_serial_ms_total"),
                "overlap_frac": pv.get("route.pipeline.overlap_frac"),
                "host_overlap_frac": pv.get(
                    "route.pipeline.host_overlap_frac"),
                "blocking_syncs": pv.get(
                    "route.pipeline.blocking_syncs"),
                "upload_skips": pv.get("route.pipeline.upload_skips"),
                "crit_upload_skips": pv.get(
                    "route.pipeline.crit_upload_skips"),
                "compiles": dv.get("route.dispatch.compiles"),
                "cache_hits": dv.get("route.dispatch.cache_hits"),
            },
            # obs rider (obs.metrics / obs.trace): per-iteration
            # overuse trajectory + compile-vs-execute attribution of
            # the measured route (warmup absorbs the cold compiles;
            # any residual measured-run compile means a new program
            # shape was hit mid-negotiation)
            # device-truth cost rider (route.devcost.*, obs/devprof):
            # XLA's measured FLOPs/bytes for the dominant dispatch
            # variant and the measured-vs-modeled bytes delta against
            # the planner's bytes_per_sweep (or unavailable + reason on
            # backends without cost analysis)
            "devcost": devcost,
            "obs": {
                "route_iterations": int(res.iterations),
                "overuse_trajectory": [int(s.overused_nodes)
                                       for s in res.stats],
                "compile_s_warmup": round(compile_warmup_s, 3),
                "compile_s_measured": round(compile_measured_s, 3),
                "execute_s_measured": round(
                    max(0.0, dt - compile_measured_s), 3),
            },
        },
    }, gauges=mv, series=corpus_series, congestion=corpus_congestion,
        qor=corpus_qor)


if __name__ == "__main__":
    main()
