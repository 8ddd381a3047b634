"""Compare the CROPPED relaxation alone, on a cell's own graph, with
float64 Dijkstra and with the full-canvas relaxation.

    python3 benchmark/tools/crop_check.py --workload route_scale \
        --seeds 1,2,3 [--nets 16] [--tile 16x16]

For each seed: ``--nets`` nets of the cell's placed problem whose
bounding boxes fit the tile with the crop's margin (the router's rule:
span + 2 x ``max_span`` in both axes) get ``route_loop``'s seeded cost
field (two wire seeds a net, congestion uniform in [0.5, 2) x 1e-10 s
scaled by 1 - crit, infinite outside the box).  One fixpoint by
``planes_relax_cropped`` at the tile, with the origins the window
program takes, is compared with ``reference.dijkstra_wire_dist`` in
float64 (``relax_gap``, against the cell's own limit) and with
``planes_relax`` on the whole canvas: distance bits, predecessors and
entry weights.  The tile defaults to the largest rung of the grid's
crop ladder.  Exits 0 only if every seed's gap is inside the limit and
the cropped result is the full canvas's bit for bit: the same cells
reached, distances, predecessors and entry weights equal.  Not part of
a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness, problem, reference  # noqa: E402


def fitting_nets(term, tile, margin: int) -> np.ndarray:
    """Nets whose box plus the crop margin fits the tile."""
    w = term.bb_xmax - term.bb_xmin + 1 + 2 * margin
    h = term.bb_ymax - term.bb_ymin + 1 + 2 * margin
    return np.flatnonzero((w <= tile[0]) & (h <= tile[1]))


def check(rr, term, tile, seed: int, n_nets: int, ceiling: int,
          plane_dtype: str = "f32") -> dict:
    """One seeded comparison; the numbers of the module docstring."""
    import jax.numpy as jnp

    from parallel_eda_tpu.route.planes import (
        build_planes, planes_relax, planes_relax_cropped)

    pg = build_planes(rr)
    g = reference.GraphArrays.of(rr)
    N = g.num_nodes
    rng = np.random.default_rng(seed)
    fit = fitting_nets(term, tile, pg.max_span)
    B = min(n_nets, len(fit))
    if B == 0:
        raise SystemExit(f"crop_check: no net fits a {tile} tile")
    nets = rng.choice(fit, size=B, replace=False)
    wire = (g.node_type == reference.CHANX) | (
        g.node_type == reference.CHANY)
    inside = ((rr.xhigh[None] >= term.bb_xmin[nets, None])
              & (rr.xlow[None] <= term.bb_xmax[nets, None])
              & (rr.yhigh[None] >= term.bb_ymin[nets, None])
              & (rr.ylow[None] <= term.bb_ymax[nets, None]))
    crit = rng.uniform(0.0, 0.9, (B, 1)).astype(np.float32)
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    cong = np.where(inside, (1 - crit) * cong, np.inf).astype(np.float32)
    seeds = [rng.choice(np.flatnonzero(wire & inside[b]), 2, replace=False)
             for b in range(B)]

    noc = np.asarray(pg.node_of_cell)
    con = np.asarray(pg.cell_of_node)
    d0 = np.full((B, N), np.inf, np.float32)
    for b in range(B):
        d0[b, seeds[b]] = 0.0
    args = (pg, jnp.asarray(d0[:, noc]), jnp.asarray(cong[:, noc]),
            jnp.asarray(crit)[:, :, None, None],
            jnp.zeros((B, pg.ncells), jnp.float32), ceiling)
    # the window program's origins (planes._window_body)
    NX, NY = pg.shape_x[1], pg.shape_y[2]
    ox = np.clip(term.bb_xmin[nets] - pg.max_span, 0, NX - tile[0])
    oy = np.clip(term.bb_ymin[nets] - pg.max_span, 0, NY - tile[1])
    full = planes_relax(*args, plane_dtype=plane_dtype)
    crop = planes_relax_cropped(
        *args, jnp.asarray(ox, jnp.int32), jnp.asarray(oy, jnp.int32),
        tile[0], tile[1], plane_dtype=plane_dtype)
    df, pf, wf, sf = (np.asarray(a) for a in full)
    dc, pc, wc, sc = (np.asarray(a) for a in crop)

    gap = 0.0
    if int(sc[0]) >= ceiling:
        gap = float("inf")          # no fixpoint under the ceiling
    got = np.full((B, N), np.inf)
    got[:, wire] = dc[:, con[wire]]
    for b in range(B):
        ref = reference.dijkstra_wire_dist(
            g, seeds[b], cong[b].astype(np.float64), float(crit[b, 0]))
        gap = max(gap, reference.relax_gap(ref, got[b]))
    fin = np.isfinite(df)
    same_reach = bool(np.array_equal(fin, np.isfinite(dc)))
    both = fin & np.isfinite(dc)
    differ = both & (df != dc)
    return {
        "seed": seed, "nets": int(B), "tile": list(tile),
        "fitting_nets": int(len(fit)),
        "sweeps_cropped": int(sc[0]), "sweeps_full": int(sf[0]),
        "relax_gap_vs_dijkstra_f64": gap,
        "same_cells_reached": same_reach,
        "cells_reached": int(both.sum()),
        "dist_bits_equal": bool(same_reach and not differ.any()),
        "dist_cells_differing": int(differ.sum()),
        "dist_max_rel_diff": float(np.max(
            np.abs(df[differ] - dc[differ]) / df[differ], initial=0.0)),
        "pred_equal": bool(np.array_equal(pf, pc)),
        "wenter_equal": bool(np.array_equal(wf, wc)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--nets", type=int, default=16)
    ap.add_argument("--tile", default="",
                    help="WxH (default: the ladder's largest rung)")
    args = ap.parse_args(argv)

    from parallel_eda_tpu.route.router import (
        _crop_ladder, enable_persistent_compile_cache)

    manifest = harness.load_manifest(REPO)
    cell = harness.load_cell(manifest, REPO, args.workload)
    device = harness.require_tpu(cell.chips)
    enable_persistent_compile_cache()
    f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    if args.tile:
        tile = tuple(int(v) for v in args.tile.split("x"))
    else:
        ladder = _crop_ladder(f.grid.nx, f.grid.ny)
        if not ladder:
            raise SystemExit("crop_check: the grid's ladder has no rung")
        tile = ladder[-1]
    limit = float(cell.traffic["limits"]["relax_gap"])
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        row = check(f.rr, f.term, tile, seed, args.nets,
                    int(cell.traffic["relax_sweep_ceiling"]))
        row.update(workload=args.workload, device=device, limit=limit)
        print(json.dumps(row), flush=True)
        ok &= (row["relax_gap_vs_dijkstra_f64"] <= limit
               and row["same_cells_reached"] and row["dist_bits_equal"]
               and row["pred_equal"] and row["wenter_equal"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
