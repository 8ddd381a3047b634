#!/usr/bin/env python3
"""Per-variant micro-benchmark + roofline ledger for the planes
relaxation kernels.

One row per kernel variant at the bench canvas size:

  xla            planes_relax (the XLA lowering; every sweep streams
                 ~15 canvas-sized reads+writes through HBM)
  pallas_g1      planes_relax_pallas, block_nets=1 / lane_mult=1 — the
                 legacy one-net-per-grid-step layout (VMEM-resident
                 sweeps, but one small canvas per step)
  pallas_packed  planes_relax_pallas, auto-planned block of G nets per
                 grid step, canvases lane-folded
  *_crop<t>      the same three at crop-ladder rung t (bb-cropped
                 tiles; the packed planner re-sizes G per rung)

Each row reports the measured wall time (best of --reps), the executed
sweep count the kernel's convergence counters saw, the MODELED HBM
bytes/sweep of that variant, the achieved bandwidth those two imply,
the roofline fraction against the device's peak HBM bandwidth, and the
modeled vector-lane occupancy of the layout (PackedLayout /
unpacked_lane_occupancy — the same models the router's block planner
publishes as route.kernel.* gauges).

The whole ledger dumps as JSON (--out); `--check <ledger.json>`
validates a previously written ledger (structure + the packed variants'
occupancy floor) and exits nonzero on violation, so the suite can gate
on it (pytest -m kernelbench).

Off-TPU the Pallas kernels run in interpret mode: their wall times (and
thus achieved GB/s) measure the interpreter, not the chip — the ledger
marks interpret=true and the occupancy/bytes columns stay meaningful
because they are layout models, not measurements.

Two further dimensions (PR-11):

- ``--plane_dtype {f32,bf16,both}`` benches every variant per plane
  storage dtype; each row carries a ``plane_dtype`` column and its byte
  model uses the dtype-aware formulas
  (planes_pallas.packed_bytes_per_cell / xla_bytes_per_cell) — the
  check enforces the bf16 packed full-canvas model at <= 0.6x f32.
- the ``dispatch`` section measures the fixed per-dispatch cost (wall
  of a minimal 1-sweep cropped dispatch, best-of-reps): the overhead
  the router's fused ragged window program pays once per WINDOW instead
  of once per populated crop rung.  The fused-vs-per-rung wall
  comparison at full routing fidelity lives in bench.py
  (--fused_dispatch); this column is the kernel-level decomposition.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# runnable from anywhere (python tools/kernel_bench.py): the repo root
# is the parent of tools/
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# packed-variant acceptance floor: the fold exists to fill the vector
# lanes, so a packed row below half occupancy means the planner or the
# layout regressed
PACKED_OCC_FLOOR = 0.5

ROW_FIELDS = ("variant", "tile", "block_nets", "lane_occupancy",
              "bytes_per_sweep", "wall_ms", "sweeps_executed",
              "achieved_gbps", "roofline_fraction", "plane_dtype")

# acceptance bar for the reduced-precision byte model: the bf16 packed
# full-canvas variant must move at most this fraction of the f32 bytes
# per sweep (2*(5*2+4)=28 vs 2*(5*4+4)=48 cells-bytes -> 0.583)
BF16_PACKED_BYTES_RATIO_MAX = 0.6


def log(msg: str) -> None:
    print(f"kernel_bench: {msg}", file=sys.stderr, flush=True)


def _instance(nx: int, ny: int, W: int, B: int):
    """Bench problem at the 60-LUT canvas scale: minimal arch, uniform
    congestion, a few zero-delay seeds per net (the relaxation's cost
    structure, not its routing quality, is what's measured)."""
    import jax.numpy as jnp

    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route.planes import build_planes
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import DeviceGrid

    arch = minimal_arch(chan_width=W)
    rr = build_rr_graph(arch, DeviceGrid(nx, ny, arch.io_capacity))
    pg = build_planes(rr)
    d0 = jnp.full((B, pg.ncells), jnp.inf, jnp.float32)
    d0 = d0.at[:, :: pg.ncells // 7].set(0.0)
    cc = jnp.ones((B, pg.ncells), jnp.float32) * 1e-9
    crit = jnp.zeros((B, 1, 1, 1), jnp.float32)
    w0 = jnp.zeros((B, pg.ncells), jnp.float32)
    return pg, d0, cc, crit, w0


def _time_best(fn, d0, reps: int):
    """Best-of-reps wall time of fn(d0); returns (seconds, stats)."""
    import numpy as np

    out = fn(d0)
    stats = np.asarray(out[1])          # compile + warm, sync
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        out = fn(d0)
        out[0].block_until_ready()
        best = min(best, time.time() - t0)
    return best, np.asarray(out[1])


def _row(variant, tile, block_nets, occupancy, bytes_per_sweep,
         wall_s, sweeps, peak_bw, plane_dtype="f32"):
    achieved = bytes_per_sweep * sweeps / max(wall_s, 1e-12)
    return {
        "variant": variant,
        "tile": tile,                    # None = full canvas
        "block_nets": int(block_nets),
        "lane_occupancy": round(float(occupancy), 4),
        "bytes_per_sweep": int(bytes_per_sweep),
        "wall_ms": round(wall_s * 1e3, 3),
        "sweeps_executed": int(sweeps),
        "achieved_gbps": round(achieved / 1e9, 3),
        # None off the chip: a CPU run reports no roofline share
        "roofline_fraction": (round(achieved / peak_bw, 4)
                              if peak_bw else None),
        "plane_dtype": plane_dtype,
    }


def run_bench(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_eda_tpu.route.planes import (plane_itemsize,
                                               planes_relax,
                                               planes_relax_cropped)
    from parallel_eda_tpu.route.planes_pallas import (
        auto_block_nets, packed_bytes_per_cell, packed_layout,
        planes_relax_cropped_pallas, planes_relax_pallas,
        unpacked_lane_occupancy, xla_bytes_per_cell)

    from parallel_eda_tpu.obs.devprof import peak_hbm_bytes_per_s
    from parallel_eda_tpu.route.planes_pallas import pallas_interpret

    dev = jax.devices()[0]
    peak_bw = peak_hbm_bytes_per_s(dev)
    interpret = pallas_interpret(None)
    B, nsw, reps = args.batch, args.nsweeps, args.reps
    dtypes = (("f32", "bf16") if args.plane_dtype == "both"
              else (args.plane_dtype,))
    pg, d0, cc, crit, w0 = _instance(args.nx, args.ny, args.chan_width,
                                     B)
    log(f"device {dev.platform} (peak HBM "
        f"{f'{peak_bw / 1e9:.0f} GB/s' if peak_bw else 'not known'}, "
        f"pallas interpret={interpret}); canvas {args.nx}x{args.ny} "
        f"W={args.chan_width} B={B}, {pg.ncells} cells/net, "
        f"dtypes {'/'.join(dtypes)}")

    rows = []
    dispatch = {}

    def bench_shape(tile, dt):
        """All three variants at one shape (full canvas or a rung) for
        one plane storage dtype."""
        isz = plane_itemsize(dt)
        if tile is None:
            shx, shy = pg.shape_x, pg.shape_y
            sfx = ""
        else:
            t = tile
            shx, shy = ((args.chan_width, t, t + 1),
                        (args.chan_width, t + 1, t))
            sfx = f"_crop{t}"
            rng = np.random.default_rng(3)
            ox = jnp.asarray(rng.integers(0, args.nx - t, B), jnp.int32)
            oy = jnp.asarray(rng.integers(0, args.ny - t, B), jnp.int32)
        lay = packed_layout(shx, shy)
        # the planner is dtype-aware: halving the itemsize roughly
        # doubles the nets one VMEM budget holds
        g_auto = (args.block if args.block else
                  auto_block_nets(shx, shy, B, itemsize=isz))

        def make_fn(variant, g, lm):
            if tile is None:
                if variant == "xla":
                    return jax.jit(lambda d: planes_relax(
                        pg, d, cc, crit, w0, nsw,
                        plane_dtype=dt)[-2:])
                return jax.jit(lambda d: planes_relax_pallas(
                    pg, d, cc, crit, w0, nsw, block_nets=g,
                    lane_mult=lm, plane_dtype=dt)[-2:])
            if variant == "xla":
                return jax.jit(lambda d: planes_relax_cropped(
                    pg, d, cc, crit, w0, nsw, ox, oy, tile,
                    tile, plane_dtype=dt)[-2:])
            return jax.jit(lambda d: planes_relax_cropped_pallas(
                pg, d, cc, crit, w0, nsw, ox, oy, tile, tile,
                block_nets=g, lane_mult=lm, plane_dtype=dt)[-2:])

        # models: the XLA lowering streams ~15 canvas traversals per
        # sweep through HBM (storage sets at the plane dtype, scan
        # temporaries f32); the Pallas kernels load+store the state
        # canvases ONCE for the whole loop (amortized over the executed
        # sweeps), padded columns included — both formulas live in
        # planes_pallas so the router's planner and this bench agree
        for variant, g, lm in (("xla", 1, 1), ("pallas_g1", 1, 1),
                               ("pallas_packed", g_auto, None)):
            if lm is None:
                lm = lay.lane_mult
            fn = make_fn(variant, g, lm)
            wall, stats = _time_best(fn, d0, reps)
            sweeps = max(1, int(stats[0]))
            if variant == "xla":
                occ = unpacked_lane_occupancy(shx, shy)
                bps = xla_bytes_per_cell(isz) * lay.cells * B
            else:
                vlay = packed_layout(shx, shy, lm)
                occ = vlay.lane_occupancy(g)
                bps = (packed_bytes_per_cell(isz) * vlay.padded_cells
                       * B / sweeps)
            r = _row(variant + sfx, tile, g, occ, bps, wall, sweeps,
                     peak_bw, plane_dtype=dt)
            rows.append(r)
            log(f"[{dt:<4}] {r['variant']:<22} G={g:<3} "
                f"occ={occ:.3f} {r['wall_ms']:8.2f} ms  "
                f"{r['achieved_gbps']:8.2f} GB/s "
                + (f"({r['roofline_fraction']:.1%} of roofline)"
                   if peak_bw else "(no roofline share off the chip)"))

    def bench_dispatch(dt):
        """Fixed per-dispatch cost: best-of-reps wall of a MINIMAL
        cropped dispatch (1 sweep, smallest rung).  One sweep of real
        work rides along, so this is an upper bound on the launch +
        retrace-free call overhead the fused window program saves per
        eliminated rung dispatch."""
        ts = [t for t in args.crops if t < min(args.nx, args.ny)]
        t = min(ts) if ts else max(2, min(args.nx, args.ny) - 2)
        rng = np.random.default_rng(3)
        ox = jnp.asarray(rng.integers(0, args.nx - t, B), jnp.int32)
        oy = jnp.asarray(rng.integers(0, args.ny - t, B), jnp.int32)
        fn = jax.jit(lambda d: planes_relax_cropped(
            pg, d, cc, crit, w0, 1, ox, oy, t, t,
            plane_dtype=dt)[-2:])
        wall, _ = _time_best(fn, d0, reps)
        dispatch[dt] = {"tile": t, "wall_ms": round(wall * 1e3, 3)}
        log(f"[{dt:<4}] dispatch overhead (1-sweep crop{t} xla): "
            f"{wall * 1e3:.3f} ms upper bound")

    for dt in dtypes:
        bench_shape(None, dt)
        for t in args.crops:
            if t >= min(args.nx, args.ny):
                log(f"skipping crop rung {t}: tile exceeds the "
                    f"{args.nx}x{args.ny} canvas")
                continue
            bench_shape(t, dt)
        bench_dispatch(dt)

    return {
        "config": {"nx": args.nx, "ny": args.ny,
                   "chan_width": args.chan_width, "batch": B,
                   "nsweeps": nsw, "reps": reps,
                   "crops": list(args.crops),
                   "block": args.block or None,
                   "plane_dtype": args.plane_dtype},
        "device": {"platform": dev.platform,
                   "kind": dev.device_kind,
                   "peak_hbm_gbps": (round(peak_bw / 1e9, 1)
                                     if peak_bw else None)},
        "interpret": interpret,
        "dispatch_overhead": dispatch,
        "rows": rows,
    }


def check_ledger(doc) -> list:
    """Structural + invariant validation of a ledger; returns problems
    (empty list = OK)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"top level is {type(doc).__name__}, expected object"]
    for key in ("config", "device", "rows"):
        if key not in doc:
            errs.append(f"missing top-level '{key}'")
    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        return errs + ["'rows' missing/empty"]
    variants = set()
    # packed full-canvas bytes model per dtype, for the bf16/f32 ratio
    packed_bps = {}
    for i, r in enumerate(rows):
        if not isinstance(r, dict):
            errs.append(f"row {i}: not an object")
            continue
        for f in ROW_FIELDS:
            if f not in r:
                errs.append(f"row {i}: missing '{f}'")
        variants.add(str(r.get("variant", "")))
        pd = r.get("plane_dtype")
        if pd not in ("f32", "bf16"):
            errs.append(f"row {i}: bad plane_dtype {pd!r}")
        elif str(r.get("variant", "")) == "pallas_packed":
            # un-amortize (x executed sweeps): the ratio must compare
            # the per-cell storage model, not each dtype's convergence
            packed_bps[pd] = (r.get("bytes_per_sweep", 0)
                              * max(1, r.get("sweeps_executed", 1)))
        occ = r.get("lane_occupancy")
        if not isinstance(occ, (int, float)) or not 0 < occ <= 1:
            errs.append(f"row {i}: bad lane_occupancy {occ!r}")
            continue
        if str(r.get("variant", "")).startswith("pallas_packed") \
                and occ < PACKED_OCC_FLOOR:
            errs.append(
                f"row {i} ({r['variant']}): packed occupancy {occ} "
                f"below the {PACKED_OCC_FLOOR} floor")
        if not r.get("bytes_per_sweep", 0) > 0:
            errs.append(f"row {i}: bytes_per_sweep must be positive")
        rf = r.get("roofline_fraction")
        if rf is not None and (not isinstance(rf, (int, float))
                               or rf < 0):
            errs.append(f"row {i}: bad roofline_fraction {rf!r}")
        g = r.get("block_nets", 0)
        if not (isinstance(g, int) and g >= 1):
            errs.append(f"row {i}: bad block_nets {g!r}")
    for need in ("xla", "pallas_g1", "pallas_packed"):
        if need not in variants:
            errs.append(f"no '{need}' full-canvas row")
    # the reduced-precision acceptance bar: when both dtypes were
    # benched, the bf16 packed full-canvas variant must MODEL at most
    # BF16_PACKED_BYTES_RATIO_MAX of the f32 bytes per sweep (the
    # whole point of halving the storage width)
    if "f32" in packed_bps and "bf16" in packed_bps \
            and packed_bps["f32"] > 0:
        ratio = packed_bps["bf16"] / packed_bps["f32"]
        if ratio > BF16_PACKED_BYTES_RATIO_MAX:
            errs.append(
                f"bf16 packed dispatch bytes are {ratio:.3f}x f32 — "
                f"above the {BF16_PACKED_BYTES_RATIO_MAX} acceptance "
                f"bar")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nx", type=int, default=12)
    ap.add_argument("--ny", type=int, default=12)
    ap.add_argument("--chan_width", type=int, default=12)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--nsweeps", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--crops", default="6,8",
                    help="comma-separated crop-ladder rungs to bench "
                         "('' = full canvas only)")
    ap.add_argument("--plane_dtype", default="both",
                    choices=("f32", "bf16", "both"),
                    help="plane storage dtype(s) to bench (default "
                         "both; each row carries its dtype and the "
                         "byte model follows the itemsize)")
    ap.add_argument("--block", type=int, default=0,
                    help="force the packed variants' block size "
                         "(default 0 = auto_block_nets per shape)")
    ap.add_argument("--out", default="",
                    help="write the JSON ledger here (default stdout)")
    ap.add_argument("--quick", action="store_true",
                    help="smoke config: B=8, 4 sweeps, 1 rep, rung 6 "
                         "(the pytest -m kernelbench gate)")
    ap.add_argument("--check", metavar="LEDGER",
                    help="validate a previously written ledger JSON "
                         "and exit (nonzero on violation); no bench "
                         "runs")
    args = ap.parse_args(argv)

    if args.check:
        try:
            with open(args.check) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"MALFORMED: {e}", file=sys.stderr)
            return 2
        errs = check_ledger(doc)
        if errs:
            print("INVALID kernel ledger:", file=sys.stderr)
            for e in errs[:20]:
                print(f"  {e}", file=sys.stderr)
            return 1
        print(f"OK: {len(doc['rows'])} variant rows")
        return 0

    if args.quick:
        args.batch, args.nsweeps, args.reps = 8, 4, 1
        args.crops = "6"
    args.crops = [int(t) for t in str(args.crops).split(",") if t]

    doc = run_bench(args)
    text = json.dumps(doc, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        log(f"ledger written to {args.out}")
    else:
        print(text)
    errs = check_ledger(doc)
    if errs:
        print("ledger FAILED its own validation:", file=sys.stderr)
        for e in errs[:20]:
            print(f"  {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
