"""Scale study for the planes router.

Three artifacts, printed as markdown for BENCHMARKS.md:
  1. per-sweep relaxation cost vs rr-graph size (the planes kernel's
     scaling curve — each sweep is a fixed set of scans/shifts over
     [B, W, X, Y] grids, so cost should scale ~linearly in cell count
     once past fixed overheads);
  2. an end-to-end route of a large synthetic circuit (>= 1e4..1e5 rr
     nodes depending on --big), with iteration stats and legality from
     the independent checker;
  3. the memory model: bytes for every resident structure as a function
     of (R nets, S max fanout, N nodes, Ncells, W, grid).

Runs on the CPU backend by default (the scaling SHAPE only — a CPU
time is never a device number); pass --tpu to use the chip, which
fails unless JAX reports a TPU.  Every row is labelled with the
platform JAX reports, never with the flag.
"""

import argparse
import os
import sys
import time

# keep the TSL host-CPU-features WARNING out of the captured stderr
# (same guard as bench.py; must precede jax/TSL init)
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def log(m):
    print(m, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tpu", action="store_true")
    ap.add_argument("--big", type=int, default=1200,
                    help="LUTs for the end-to-end route")
    ap.add_argument("--curve_only", action="store_true")
    ap.add_argument("--memory_only", action="store_true",
                    help="print only the memory model (small fixture, "
                         "Titan-proxy extrapolation); no routing")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--runs_dir",
                    default=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "runs"),
                    help="run-corpus directory (obs/runstore.py); the "
                         "end-to-end route appends one record")
    ap.add_argument("--no_corpus", action="store_true",
                    help="skip the corpus append")
    ap.add_argument("--mesh", type=int, default=1,
                    help="shard the planes relaxation over N devices "
                         "(route/planes_shard.py); on CPU this forces "
                         "N virtual host devices via XLA_FLAGS, so it "
                         "must run in a fresh process.  Routes a "
                         "single-device reference of the same placed "
                         "circuit and checks bit-identical QoR")
    ap.add_argument("--multichip_out", default="",
                    help="with --mesh > 1: also write the mesh probe "
                         "doc (n_devices/ok/mesh/... — the shape "
                         "observatory's legacy importer parses) to "
                         "this file; default: no file")
    args = ap.parse_args()
    if args.curve_only and args.memory_only:
        ap.error("--curve_only and --memory_only are mutually exclusive")
    if args.mesh > 1 and (args.curve_only or args.memory_only):
        ap.error("--mesh needs the end-to-end route section")

    # the host-platform device trick: N virtual CPU devices, decided
    # BEFORE jax initialises its backends (XLA reads the flag once)
    if args.mesh > 1 and not args.tpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    backend = jax.devices()[0].platform
    if args.tpu and backend != "tpu":
        raise SystemExit(f"scale_bench: --tpu asked but JAX reports "
                         f"platform {backend!r}")
    import jax.numpy as jnp
    import numpy as np

    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route import planes as P
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import DeviceGrid

    # ---- 1. per-sweep cost vs N ----
    sizes = (() if args.memory_only else
             ((8, 10), (16, 12), (32, 14), (48, 16), (64, 16),
              (96, 20)))
    if sizes:
        print("## Planes relaxation: per-sweep cost vs rr-graph size\n")
        print("| grid | W | rr nodes | cells | sweep cost (B=64) |")
        print("|---|---|---|---|---|")
    B = 64
    for g, W in sizes:
        arch = minimal_arch(chan_width=W)
        rr = build_rr_graph(arch, DeviceGrid(g, g, arch.io_capacity))
        pg = P.build_planes(rr)
        nc = pg.ncells
        rng = np.random.default_rng(0)
        cc = jnp.asarray(rng.uniform(1e-10, 2e-10,
                                     (B, nc)).astype(np.float32))
        d0 = jnp.full((B, nc), jnp.inf).at[:, nc // 2].set(0.0)
        crit = jnp.zeros((B, 1, 1, 1))
        w0 = jnp.zeros((B, nc))
        f = jax.jit(lambda d0, cc, c, w:
                    P.planes_relax(pg, d0, cc, c, w, 8))
        out = f(d0, cc, crit, w0)
        out[0].block_until_ready()      # compile + warm
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(d0, cc, crit, w0)
            out[0].block_until_ready()
        per_sweep = (time.perf_counter() - t0) / reps / 8
        print(f"| {g}x{g} | {W} | {rr.num_nodes} | {nc} | "
              f"{per_sweep*1e3:.2f} ms |")
        log(f"curve {g}x{g} done")
    if args.curve_only:
        return

    # ---- 2. end-to-end large route ----
    from parallel_eda_tpu.flow import run_place, run_route, synth_flow
    from parallel_eda_tpu.obs import (compile_seconds,
                                      enable_compile_capture, get_devprof,
                                      get_metrics)
    from parallel_eda_tpu.place import PlacerOpts
    from parallel_eda_tpu.route import RouterOpts

    enable_compile_capture()

    if args.memory_only:
        f = synth_flow(num_luts=120, num_inputs=16, num_outputs=16,
                       chan_width=16, seed=5)
        R, S = f.term.sinks.shape
    else:
        print("\n## End-to-end large route\n")
        t0 = time.time()
        f = synth_flow(num_luts=args.big, num_inputs=32, num_outputs=32,
                       chan_width=16, seed=5)
        log(f"prepared: {f.rr.num_nodes} rr nodes, "
            f"{f.term.num_nets} nets, grid {f.rr.grid.nx}x{f.rr.grid.ny} "
            f"({time.time()-t0:.0f}s)")
        t0 = time.time()
        f = run_place(f, PlacerOpts(moves_per_step=256), timing_driven=False)
        t_place = time.time() - t0
        log(f"placed in {t_place:.0f}s")
        # --mesh: route a single-device reference of the SAME placed
        # circuit first, under a throwaway metrics registry so the
        # measured (mesh) route's gauge snapshot stays pure.  The mesh
        # relaxation is bit-identical by construction (planes_shard) —
        # this check makes the MULTICHIP row load-bearing.
        ref = None
        if args.mesh > 1:
            from parallel_eda_tpu.obs import (MetricsRegistry,
                                              set_metrics)
            log(f"mesh({args.mesh}): routing single-device reference")
            t0 = time.time()
            old_reg = set_metrics(MetricsRegistry())
            try:
                ref = run_route(f, RouterOpts(batch_size=args.batch),
                                timing_driven=False).route
            finally:
                set_metrics(old_reg)
            log(f"reference routed in {time.time()-t0:.0f}s "
                f"(wl {ref.wirelength})")

        mesh_kw = ({"mesh_shards": args.mesh} if args.mesh > 1 else {})
        get_devprof().enabled = True
        c0 = compile_seconds()
        t0 = time.time()
        f = run_route(f, RouterOpts(batch_size=args.batch, **mesh_kw),
                      timing_driven=False)
        t_route = time.time() - t0
        c_route = compile_seconds() - c0
        res = f.route
        R, S = f.term.sinks.shape
        print(f"- circuit: {args.big} LUTs, {R} nets (Smax {S}), "
              f"grid {f.rr.grid.nx}x{f.rr.grid.ny} W={f.rr.chan_width}, "
              f"**{f.rr.num_nodes} rr nodes**")
        print(f"- route: success={res.success} in {res.iterations} "
              f"iterations, wirelength {res.wirelength}, "
              f"{t_route:.0f}s wall ({backend} backend), "
              f"{res.total_net_routes} net-routes "
              f"({res.total_net_routes/t_route:.1f} nets/s)")
        print(f"- work ledger: {res.total_relax_steps} relax sweeps = "
              f"{res.total_relax_steps_useful} useful + "
              f"{res.total_relax_steps_wasted} wasted "
              f"({res.total_relax_steps_cropped} in cropped tiles)")
        kv = get_metrics().values("route.kernel.")
        if kv.get("route.kernel.lane_occupancy") is not None:
            print(f"- kernel layout: lane occupancy "
                  f"{kv.get('route.kernel.lane_occupancy')}, "
                  f"~{kv.get('route.kernel.bytes_per_sweep')} modeled "
                  f"HBM bytes/sweep (dominant window shape)")
        pv = get_metrics().values("route.pipeline.")
        dvv = get_metrics().values("route.dispatch.")
        if pv.get("route.pipeline.overlap_frac") is not None:
            print(f"- pipeline: overlap "
                  f"{pv['route.pipeline.overlap_frac']} (host-work "
                  f"{pv.get('route.pipeline.host_overlap_frac')}), "
                  f"plan {pv.get('route.pipeline.host_plan_ms_total')} / "
                  f"exec {pv.get('route.pipeline.device_exec_ms_total')} / "
                  f"stall {pv.get('route.pipeline.stall_ms_total')} ms, "
                  f"{pv.get('route.pipeline.blocking_syncs')} blocking "
                  f"syncs, {dvv.get('route.dispatch.compiles', 0)} "
                  f"dispatch compiles / "
                  f"{dvv.get('route.dispatch.cache_hits', 0)} variant "
                  f"cache hits")
        mesh_info = None
        if args.mesh > 1:
            mv = get_metrics().values("route.mesh.")
            bitid = (res.success and ref.success
                     and int(res.wirelength) == int(ref.wirelength)
                     and np.array_equal(np.asarray(res.paths),
                                        np.asarray(ref.paths))
                     and np.array_equal(np.asarray(res.occ),
                                        np.asarray(ref.occ)))
            mesh_info = {
                "n_shards": int(args.mesh),
                "impl": ("pallas_halo" if backend == "tpu"
                         else "ppermute"),
                "bit_identical": bool(bitid),
                "wirelength_ref": int(ref.wirelength),
                "halo_bytes": int(mv.get("route.mesh.halo_bytes")
                                  or 0),
                "halo_exchanges":
                    int(mv.get("route.mesh.halo_exchanges") or 0),
                "overlap_frac":
                    float(mv.get("route.mesh.overlap_frac") or 0.0),
                "mesh_demotions":
                    int(mv.get("route.mesh.mesh_demotions") or 0),
            }
            print(f"- mesh: {args.mesh} shards ({mesh_info['impl']}), "
                  f"QoR vs single-device reference "
                  f"{'BIT-IDENTICAL' if bitid else 'DIVERGED'} "
                  f"(wl {res.wirelength} vs {ref.wirelength}), "
                  f"{mesh_info['halo_exchanges']} halo exchanges / "
                  f"{mesh_info['halo_bytes']} halo bytes, overlap "
                  f"{mesh_info['overlap_frac']}, "
                  f"{mesh_info['mesh_demotions']} demotions")
            if not bitid:
                log("mesh: QoR DIVERGED from the single-device "
                    "reference — this is a bug (planes_shard parity)")
        get_devprof().capture_all()
        dc = get_devprof().summary()
        if "unavailable" in dc:
            print(f"- devcost: unavailable ({dc['unavailable']})")
        else:
            print(f"- devcost: {dc.get('measured_variants')}/"
                  f"{dc.get('variants')} variants measured, dominant "
                  f"{dc.get('flops', 0):.3g} flops / "
                  f"{dc.get('bytes_accessed', 0):.3g} B accessed, "
                  f"peak temp {dc.get('temp_bytes', 0)} B, "
                  f"measured/modeled bytes {dc.get('bytes_delta')} "
                  f"(band 1e±{dc.get('delta_band_log10')})")
        # corpus append (obs/runstore.py): the scale route joins the
        # same trajectory store the 60-LUT bench feeds, under its own
        # scenario id.  Never fatal to the study output.
        if not args.no_corpus:
            try:
                from parallel_eda_tpu.obs import runstore as _rs
                dev0 = jax.devices()[0]
                scen = f"scale_bench_l{args.big}_b{args.batch}"
                if args.mesh > 1:
                    scen += f"_m{args.mesh}"
                rec = _rs.make_record(
                    scen,
                    {"big": args.big, "batch": args.batch,
                     "tpu": bool(args.tpu), "mesh": args.mesh},
                    "nets_routed_per_sec",
                    round(res.total_net_routes / max(t_route, 1e-9), 2),
                    "nets/s", backend, dev0.device_kind,
                    qor={"wirelength": int(res.wirelength),
                         "routed": bool(res.success),
                         "iterations": int(res.iterations)},
                    gauges=get_metrics().values("route."),
                    series={"overused_nodes":
                            [int(s.overused_nodes) for s in res.stats],
                            "overuse_total":
                            [int(s.overuse_total) for s in res.stats]},
                    congestion=_rs.congestion_blob(
                        res.congestion, f.rr.xlow, f.rr.ylow,
                        f.rr.xhigh, f.rr.yhigh,
                        f.rr.grid.nx + 2, f.rr.grid.ny + 2),
                    detail={
                        "platform": backend,
                        "luts": int(args.big),
                        "rr_nodes": int(f.rr.num_nodes),
                        "route_time_s": round(t_route, 3),
                        "total_net_routes": int(res.total_net_routes),
                        "total_relax_steps": int(res.total_relax_steps),
                        "wirelength": int(res.wirelength),
                        "ledger": {
                            "relax_steps_useful":
                                int(res.total_relax_steps_useful),
                            "relax_steps_wasted":
                                int(res.total_relax_steps_wasted)},
                        "pipeline": {
                            "exec_ms": pv.get(
                                "route.pipeline.device_exec_ms_total"),
                            "stall_ms": pv.get(
                                "route.pipeline.stall_ms_total")},
                        "obs": {"compile_s_measured": round(c_route, 3)},
                        **({"mesh": mesh_info} if mesh_info else {}),
                    },
                    n_shards=(args.mesh if args.mesh > 1 else None),
                    repo_dir=os.path.dirname(os.path.abspath(__file__)))
                p = _rs.append_run(args.runs_dir, rec)
                log(f"corpus: appended {scen} row to {p}")
            except Exception as e:
                log(f"corpus append failed (non-fatal): "
                    f"{type(e).__name__}: {e}")
        # --mesh --multichip_out: also write the mesh probe doc (the
        # shape observatory's legacy importer parses; the mesh_* keys
        # are the load-bearing measurement)
        if mesh_info is not None and args.multichip_out:
            mc_path = args.multichip_out
            import json as _json
            tail = (f"scale_bench --mesh {args.mesh}: "
                    f"{'ok' if mesh_info['bit_identical'] else 'DIVERGED'}"
                    f" — mesh ({args.mesh},), {res.iterations} iters, "
                    f"wirelength {res.wirelength} "
                    f"(reference {mesh_info['wirelength_ref']})\n")
            doc = {"n_devices": int(args.mesh),
                   "rc": 0 if mesh_info["bit_identical"] else 1,
                   "ok": bool(mesh_info["bit_identical"]),
                   "skipped": False,
                   "tail": tail,
                   "mesh": mesh_info,
                   "backend": backend,
                   "luts": int(args.big),
                   "rr_nodes": int(f.rr.num_nodes),
                   "route_time_s": round(t_route, 3)}
            with open(mc_path, "w") as mcf:
                _json.dump(doc, mcf, indent=2)
                mcf.write("\n")
            log(f"mesh: wrote probe doc {mc_path}")
        print(f"- legality: verified by the independent checker (run_route)")
        print(f"- obs: {res.iterations} route iterations, overuse "
              f"trajectory {[s.overused_nodes for s in res.stats]}, "
              f"compile {c_route:.1f}s / execute "
              f"{max(0.0, t_route - c_route):.1f}s of the route wall "
              f"(jax.monitoring split; cold run = mostly compile)")
        print("- iteration stats (window syncs):")
        print("  | iter | overused | overuse total | dirty nets |")
        print("  |---|---|---|---|")
        for s in res.stats:
            print(f"  | {s.iteration} | {s.overused_nodes} | "
                  f"{s.overuse_total} | {s.rerouted_nets} |")

    # ---- 3. memory model ----
    from parallel_eda_tpu.route.planes import (build_planes,
                                               build_planes_terminals)
    import numpy as _np
    from parallel_eda_tpu.route.router import path_budget
    pg = build_planes(f.rr)
    pt = build_planes_terminals(f.rr, f.term.source, f.term.sinks,
                                _np.asarray(pg.cell_of_node), pg.ncells)
    N = f.rr.num_nodes
    nc = pg.ncells
    Bt = args.batch
    U, P, C = pt.uid_pcrank.shape
    U -= 1                               # drop the pad row
    K = pt.sink_cands
    span0 = int(((f.term.bb_xmax - f.term.bb_xmin)
                 + (f.term.bb_ymax - f.term.bb_ymin)).max())
    L_bb = path_budget(span0, 4 * (f.rr.grid.nx + f.rr.grid.ny) + 64)

    # the path store and the sink index are kept a fanout class
    # (rr/terminals.py fanout_ladder): their size follows the classes'
    # sink slots, sum of R_c * S_c, not R * Smax
    slots = sum(c.width * len(c.nets) for c in f.term.fanout_classes)

    def model(slots_, nc_, N_, U_, C_, L_):
        return [
            ("planes dist/pred/w (per batch)", "3*B*Ncells*4",
             3 * Bt * nc_ * 4),
            ("congestion cc (per batch)", "B*Ncells*4", Bt * nc_ * 4),
            ("occ/acc/history", "N*8", N_ * 8),
            ("paths (a store a fanout class, bb-adaptive L)",
             "sum(R_c*S_c)*L_bb*4", slots_ * L_ * 4),
            ("sink uid index", "sum(R_c*S_c)*4", slots_ * 4),
            ("unique-sink tables (cells x pins)", "U*(P*C*8+(P+C)*4)",
             U_ * (P * C_ * 8 + (P + C_) * 4)),
            ("planes masks/delays (static)", "~12*Ncells*4", 12 * nc_ * 4),
        ]

    print("\n## Memory model (resident device state)\n")
    print("The two round-3 Titan blockers are closed: sink tables are "
          "factorized by unique sink node ([U, P, C] + int32 index, was "
          "[R, S, K]*12B) and the path store's L is the circuit's "
          "largest bb half-perimeter (regrown on demand), not the "
          "device's.\n")
    print("| structure | formula | this circuit |")
    print("|---|---|---|")
    total = 0
    for name, formula, b in model(slots, nc, N, U, C, L_bb):
        total += b
        print(f"| {name} | {formula} | {b/1e6:.1f} MB |")
    print(f"| **total** | | **{total/1e6:.1f} MB** |")

    # Titan proxy: 1e6 rr nodes, 1e5 nets (bitcoin_miner-class,
    # BASELINE.md ladder step 5): 300x300 grid, W=80, avg fanout ~4
    # (S here is the width of the dominant fanout class, not the global
    # max: the tables are kept a class, so the population routes at
    # S~8 and a handful of wide nets add R_c * S_c of their own; L_bb ~
    # a few hundred for bb-local nets)
    gx = 300
    W_t = 80
    nc_t = 2 * W_t * gx * (gx + 1)
    N_t = int(1.0e6)
    R_t = int(1.0e5)
    S_t = 8
    U_t = int(1.2e5)
    # per-sink candidate and distinct-cell counts scale with channel
    # width (wire->IPIN fan-in ~ Fc_in * W per adjacent channel), the
    # pin count does not: extrapolate from the measured fixture K, C
    K_t = max(K, int(round(K * W_t / f.rr.chan_width)))
    C_t = max(C, int(round(C * W_t / f.rr.chan_width)))
    L_t = 512
    print(f"\nTitan proxy (1e6 rr nodes, 1e5 nets, 300x300 W=80, "
          f"fanout-class S=8, L_bb=512, C={C_t} cells x P={P} pins a "
          f"sink extrapolated from the fixture's C={C} at "
          f"W={f.rr.chan_width}):\n")
    print("| structure | bytes |")
    print("|---|---|")
    tot = 0
    for name, formula, b in model(R_t * S_t, nc_t, N_t, U_t, C_t, L_t):
        tot += b
        print(f"| {name} | {b/1e9:.2f} GB |")
    print(f"| **total** | **{tot/1e9:.2f} GB** |")
    L_dev = 4 * (gx + gx) + 64
    print(f"\nTotal {tot/1e9:.2f} GB fits a single v5p chip's 95 GB HBM "
          f"(the [B, Ncells] search state shrinks linearly with batch); "
          f"the dense pre-factorization model paid R*S*K*12 = "
          f"{R_t*S_t*K_t*12/1e9:.1f} GB for sink tables alone plus a "
          f"device-half-perimeter L of {L_dev} "
          f"({R_t*S_t*L_dev*4/1e9:.1f} GB paths).")
    # bb-cropped windows (planes_relax_cropped): the per-batch search
    # state is the TILE, not the grid — for bb-local nets (tile ~64x64
    # on the 300x300 proxy) the 4 per-batch terms above shrink by the
    # tile-area ratio; only the wide-net window still allocates
    # grid-sized canvases
    tile = 64
    nc_tile = 2 * W_t * tile * (tile + 1)
    crop_state = 4 * Bt * nc_tile * 4
    full_state = 4 * Bt * nc_t * 4
    print(f"\nWith bb-cropped windows (tile {tile}x{tile}), the "
          f"per-batch planes state is {crop_state/1e9:.2f} GB instead "
          f"of {full_state/1e9:.2f} GB ({nc_tile/nc_t:.1%} of the "
          f"canvas) — HBM stops being the batch-size ceiling for the "
          f"bb-local net population.")


if __name__ == "__main__":
    main()
