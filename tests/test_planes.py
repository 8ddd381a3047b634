"""Planes-kernel tests: the structured scan/shift relaxation
(route/planes.py) must be exactly equivalent to the gather-based ELL
relaxation (route/search.py _relax) — the two independent implementations
of the same cost model are each other's oracle — and the planes router
must produce legal, deterministic routings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import minimal_arch
from parallel_eda_tpu.arch.model import SegmentInf
from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.route.device_graph import to_device
from parallel_eda_tpu.route.planes import build_planes, planes_relax
from parallel_eda_tpu.route.search import _relax
from parallel_eda_tpu.rr.graph import CHANX, CHANY, build_rr_graph
from parallel_eda_tpu.rr.grid import DeviceGrid


def _mixed_len_arch():
    arch = minimal_arch(chan_width=12)
    arch.segments = [
        SegmentInf(name="l1", length=1, frequency=0.4, wire_switch=0,
                   opin_switch=1),
        SegmentInf(name="l2", length=2, frequency=0.3, Rmetal=80.0,
                   Cmetal=15e-15, wire_switch=1, opin_switch=1),
        SegmentInf(name="l4", length=4, frequency=0.3, Rmetal=60.0,
                   Cmetal=12e-15, wire_switch=0, opin_switch=0),
    ]
    return arch


@pytest.mark.slow
@pytest.mark.parametrize("arch,nx,ny,seed", [
    (minimal_arch(chan_width=6), 4, 4, 0),
    (_mixed_len_arch(), 7, 7, 7),
    (_mixed_len_arch(), 5, 9, 11),
])
def test_planes_relax_matches_ell(arch, nx, ny, seed):
    """Wire-node distances from the planes relaxation equal the ELL
    pull-relaxation on random seeds/congestion/criticality/bounding
    boxes, including mixed-length staggered segments and rectangular
    grids."""
    grid = DeviceGrid(nx, ny, arch.io_capacity)
    rr = build_rr_graph(arch, grid)
    dev = to_device(rr)
    pg = build_planes(rr)
    N = rr.num_nodes
    B = 4
    rng = np.random.default_rng(seed)
    wires = np.where((rr.node_type == CHANX) | (rr.node_type == CHANY))[0]
    seed_m = np.zeros((B, N), bool)
    for b in range(B):
        seed_m[b, rng.choice(wires, 2, replace=False)] = True
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    crit = rng.uniform(0.0, 0.9, (B, 1)).astype(np.float32)
    crit[0] = 0.0
    inside = np.ones((B, N), bool)
    inside[1] = ((rr.xhigh >= 1) & (rr.xlow <= max(2, nx // 2))
                 & (rr.yhigh >= 1) & (rr.ylow <= ny))
    cong_m = np.where(inside, (1 - crit) * cong, np.inf).astype(np.float32)

    dist, _, _, _ = _relax(
        dev, jnp.asarray(cong_m), jnp.asarray(crit), jnp.asarray(inside),
        jnp.asarray(seed_m), jnp.zeros((B, N), jnp.float32), 500)
    dist = np.asarray(dist)

    noc = np.asarray(pg.node_of_cell)
    d0 = np.where(seed_m[:, noc], 0.0, np.inf).astype(np.float32)
    dist_flat, pred, wenter, _ = planes_relax(
        pg, jnp.asarray(d0), jnp.asarray(cong_m[:, noc]),
        jnp.asarray(crit)[:, :, None, None],
        jnp.zeros((B, pg.ncells), jnp.float32), 64)
    dist_flat = np.asarray(dist_flat)
    con = np.asarray(pg.cell_of_node)
    distp = np.full((B, N), np.inf, np.float32)
    wmask = con < pg.ncells
    distp[:, wmask] = dist_flat[:, con[wmask]]

    a, b = dist[:, wires], distp[:, wires]
    both_inf = np.isinf(a) & np.isinf(b)
    assert (np.isclose(a, b, rtol=1e-4, atol=1e-13) | both_inf).all()

    # pred chains must terminate at a seed and strictly descend
    pred = np.asarray(pred)
    for bi in range(B):
        fin = np.where(np.isfinite(dist_flat[bi]))[0]
        for c in fin[:: max(1, len(fin) // 17)]:
            cur, steps = int(c), 0
            while int(pred[bi][cur]) != cur and steps < 10000:
                nxt = int(pred[bi][cur])
                assert dist_flat[bi][nxt] <= dist_flat[bi][cur] + 1e-12
                cur, steps = nxt, steps + 1
            assert int(pred[bi][cur]) == cur
            assert d0[bi][cur] == 0.0, "walk must end at a seed"


def test_planes_route_legal_and_deterministic():
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                   chan_width=12, seed=3)
    r1 = Router(f.rr, RouterOpts(batch_size=64)).route(f.term)
    assert r1.success
    check_route(f.rr, f.term, r1.paths, occ=r1.occ)
    r2 = Router(f.rr, RouterOpts(batch_size=64)).route(f.term)
    assert np.array_equal(r1.paths, r2.paths)
    assert np.array_equal(r1.occ, r2.occ)


@pytest.mark.slow
def test_planes_vs_ell_quality():
    """The two programs implement the same cost model; their negotiated
    wirelengths must land in the same quality class (not bit-equal: the
    search orders differ, so tie-breaks and trajectories differ)."""
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                   chan_width=12, seed=3)
    rp = Router(f.rr, RouterOpts(batch_size=64, sink_group=1)).route(f.term)
    re = Router(f.rr, RouterOpts(batch_size=64, sink_group=1,
                                 program="ell")).route(f.term)
    assert rp.success and re.success
    check_route(f.rr, f.term, rp.paths, occ=rp.occ)
    assert rp.wirelength <= re.wirelength * 1.15 + 5


@pytest.mark.slow
def test_planes_incremental_sink_schedule():
    """sink_group=1 (exact VPR incremental) must also route legally via
    the planes program, with wirelength no worse than the default
    doubling schedule."""
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                   chan_width=12, seed=3)
    rd = Router(f.rr, RouterOpts(batch_size=64)).route(f.term)
    r1 = Router(f.rr, RouterOpts(batch_size=64, sink_group=1)).route(f.term)
    assert rd.success and r1.success
    check_route(f.rr, f.term, r1.paths, occ=r1.occ)
    assert r1.wirelength <= rd.wirelength * 1.05 + 5


@pytest.mark.parametrize("unidir,seed", [(False, 3), (True, 5)])
def test_planes_cropped_matches_full(unidir, seed):
    """planes_relax_cropped == planes_relax EXACTLY (dist, pred, wenter)
    when every finite-cc cell and every seed of each net lies inside its
    crop tile — the per-net bb crop contract (route.h:70-165 semantics;
    exactness argument in planes.py geom_cropped)."""
    import jax

    from parallel_eda_tpu.arch.builtin import unidir_arch
    from parallel_eda_tpu.route.planes import planes_relax_cropped

    if unidir:
        arch = unidir_arch(chan_width=8)
        arch.segments = [
            SegmentInf(name="l1", length=1, frequency=0.5, wire_switch=0,
                       opin_switch=1, directionality="unidir"),
            SegmentInf(name="l2", length=2, frequency=0.5, Rmetal=80.0,
                       Cmetal=15e-15, wire_switch=1, opin_switch=1,
                       directionality="unidir"),
        ]
    else:
        arch = _mixed_len_arch()
    # grid comfortably larger than the 3x3-bb tiles so the crop is a
    # REAL sub-tile (the test asserts that below), not the whole grid
    grid = DeviceGrid(14, 12, arch.io_capacity)
    rr = build_rr_graph(arch, grid)
    pg = build_planes(rr)
    N = rr.num_nodes
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1
    B = 4
    rng = np.random.default_rng(seed)

    # per-net bb (grid coords) + inside mask = bb-INTERSECTING wires
    bbs = []
    for b in range(B):
        x0 = int(rng.integers(1, NX - 2))
        y0 = int(rng.integers(1, NY - 2))
        bbs.append((x0, min(NX, x0 + 3), y0, min(NY, y0 + 3)))
    inside = np.zeros((B, N), bool)
    for b, (x0, x1, y0, y1) in enumerate(bbs):
        inside[b] = ((rr.xhigh >= x0) & (rr.xlow <= x1)
                     & (rr.yhigh >= y0) & (rr.ylow <= y1)
                     & ((rr.node_type == CHANX) | (rr.node_type == CHANY)))
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    crit = rng.uniform(0.0, 0.9, (B, 1)).astype(np.float32)
    cong_m = np.where(inside, (1 - crit) * cong, np.inf).astype(np.float32)

    noc = np.asarray(pg.node_of_cell)
    cc_cells = cong_m[:, noc]                       # [B, ncells]

    # seeds: 2 random finite-cc cells per net
    d0 = np.full((B, pg.ncells), np.inf, np.float32)
    for b in range(B):
        fin = np.where(np.isfinite(cc_cells[b]))[0]
        d0[b, rng.choice(fin, 2, replace=False)] = 0.0

    # crop tiles from the finite-cc cells (per net, in plane-index
    # space), bucketed to one static (cnx, cny) for the batch
    finx = np.isfinite(cc_cells[:, :ncx]).reshape(B, W, NX, NYp1)
    finy = np.isfinite(cc_cells[:, ncx:]).reshape(B, W, NXp1, NY)
    ox = np.zeros(B, np.int32)
    oy = np.zeros(B, np.int32)
    need_x = need_y = 1
    for b in range(B):
        ax = np.where(finx[b].any(axis=(0, 2)))[0]
        ay = np.where(finx[b].any(axis=(0, 1)))[0]
        bx = np.where(finy[b].any(axis=(0, 2)))[0]
        by = np.where(finy[b].any(axis=(0, 1)))[0]
        o_x = min(ax.min(initial=NX), bx.min(initial=NX))
        o_y = min(ay.min(initial=NYp1), by.min(initial=NY))
        ox[b], oy[b] = o_x, o_y
        need_x = max(need_x, ax.max(initial=0) - o_x + 1,
                     bx.max(initial=0) - o_x)
        need_y = max(need_y, ay.max(initial=0) - o_y,
                     by.max(initial=0) - o_y + 1)
    cnx = min(NX, int(need_x) + 1)
    cny = min(NY, int(need_y) + 1)
    assert cnx < NX and cny < NY, "crop degenerated to the full grid"
    ox = np.minimum(ox, NX - cnx).astype(np.int32)
    oy = np.minimum(oy, NY - cny).astype(np.int32)

    crit_c = jnp.asarray(crit)[:, :, None, None]
    w0 = jnp.zeros((B, pg.ncells), jnp.float32)
    full = planes_relax(pg, jnp.asarray(d0), jnp.asarray(cc_cells),
                        crit_c, w0, 64)
    crop = planes_relax_cropped(
        pg, jnp.asarray(d0), jnp.asarray(cc_cells), crit_c, w0, 64,
        jnp.asarray(ox), jnp.asarray(oy), cnx, cny)
    # The crop changes the associative-scan TREE SHAPE (row length cnx
    # vs NX), so multi-hop prefix sums can differ by an ulp — bit
    # equality is not the contract (each program is individually
    # deterministic; sharded==single stays bit-exact per program).
    # Contract: identical reachability, values to fp32 roundoff, and
    # identical pred/wenter except at ulp-tied cells.
    df, dc = np.asarray(full[0]), np.asarray(crop[0])
    assert np.array_equal(np.isfinite(df), np.isfinite(dc))
    fin = np.isfinite(df)
    np.testing.assert_allclose(dc[fin], df[fin], rtol=1e-5, atol=0)
    pf, pc = np.asarray(full[1]), np.asarray(crop[1])
    wf, wc = np.asarray(full[2]), np.asarray(crop[2])
    mism = (pf != pc) | (wf != wc)
    assert mism.mean() < 1e-3, mism.mean()
    # every structural mismatch sits on an ulp-tied distance
    assert np.allclose(df[mism], dc[mism], rtol=1e-5), "non-tie pred diff"


@pytest.mark.slow
def test_crop_engaged_route_legal_deterministic():
    """Flow-level crop gate: on a placed circuit whose bbs are small
    relative to the grid, the window driver must actually ENGAGE the
    cropped kernel (cost model), and the route must stay legal,
    deterministic, and converge like the uncropped program."""
    from parallel_eda_tpu.flow import run_place_native

    f = synth_flow(num_luts=300, chan_width=14, seed=5, bb_factor=1)
    f = run_place_native(f)
    r1 = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    r1b = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    assert r1.success
    check_route(f.rr, f.term, r1.paths, r1.occ)
    # the runtime counter proves engagement (jit-cache independent)
    assert r1.total_relax_steps_cropped > 0, "cropped kernel never engaged"
    assert np.array_equal(np.asarray(r1.paths), np.asarray(r1b.paths))

    r2 = Router(f.rr, RouterOpts(batch_size=32, crop="off")).route(f.term)
    assert r2.success
    check_route(f.rr, f.term, r2.paths, r2.occ)
    assert r2.total_relax_steps_cropped == 0
    # same-quality class (crop changes negotiation order, not validity)
    assert abs(r1.wirelength - r2.wirelength) / r2.wirelength < 0.05


@pytest.mark.slow
def test_crop_timing_driven_crit_path_parity():
    """Timing-driven (fused device STA) negotiation with the crop
    engaged: legal, deterministic, and the crit path must match the
    uncropped program within the QoR bar (measured exact on this
    fixture)."""
    from parallel_eda_tpu.flow import run_place_native
    from parallel_eda_tpu.timing import TimingAnalyzer, build_timing_graph

    f = synth_flow(num_luts=120, chan_width=12, seed=4, bb_factor=1)
    f = run_place_native(f)

    def run(crop):
        ta = TimingAnalyzer(build_timing_graph(f.nl, f.pnl, f.term))
        r = Router(f.rr, RouterOpts(batch_size=16, crop=crop)).route(
            f.term, analyzer=ta)
        return r, ta.crit_path_delay

    r1, cpd1 = run("6x6")
    assert r1.success and r1.total_relax_steps_cropped > 0
    check_route(f.rr, f.term, r1.paths, r1.occ)
    r2, cpd2 = run("6x6")
    assert np.array_equal(np.asarray(r1.paths), np.asarray(r2.paths))
    assert cpd1 == cpd2
    r3, cpd3 = run("off")
    assert r3.success
    assert cpd1 <= cpd3 * 1.01 + 1e-12          # the <=1% BASELINE bar


# ---- the traceback walk alone (planes.traceback_walk) ----

_WALK_B, _WALK_G, _WALK_KW, _WALK_NC, _WALK_N = 3, 4, 12, 64, 200


def _walk_reference(pred, wenter, noc_p1, pick_cell, done0, Kw):
    """The full-budget walk, one (net, pick) at a time in NumPy: every
    one of the Kw steps is taken, whatever the walks' lengths."""
    B, G = pick_cell.shape
    ncells = pred.shape[1]
    cells = np.full((B, G, Kw), ncells, np.int32)
    nodes = np.full((B, G, Kw), noc_p1[ncells], np.int32)
    wst = np.zeros((B, G, Kw), np.float32)
    cur = pick_cell.copy()
    done = done0.copy()
    longest = 0
    for b in range(B):
        for g in range(G):
            for pos in range(Kw):
                if done[b, g]:
                    break
                c = cur[b, g]
                cells[b, g, pos] = c
                nodes[b, g, pos] = noc_p1[c]
                wst[b, g, pos] = wenter[b, c]
                longest = max(longest, pos + 1)
                if pred[b, c] == c:
                    done[b, g] = True
                else:
                    cur[b, g] = pred[b, c]
    return cur, done, cells, nodes, wst, longest


def _walk_case(kind):
    """Seeded pred fields [B, ncells]: cell c points at c - 1 inside
    runs of random length, a run's first cell at itself (a root)."""
    B, G, Kw, NC = _WALK_B, _WALK_G, _WALK_KW, _WALK_NC
    rng = np.random.default_rng(sum(map(ord, kind)))
    pred = np.empty((B, NC), np.int32)
    for b in range(B):
        c = 0
        while c < NC:
            e = min(NC, c + int(rng.integers(1, Kw)))
            pred[b, c] = c
            pred[b, c + 1:e] = np.arange(c, e - 1)
            c = e
    pick = rng.integers(0, NC, (B, G)).astype(np.int32)
    done0 = rng.random((B, G)) < 0.25
    done0[0, 0] = False
    if kind == "ends_at_budget":
        # one chain of exactly Kw cells: the last step finds the root
        pred[1, 20], pred[1, 20 + Kw] = 20, 20 + Kw
        pred[1, 21:20 + Kw] = np.arange(20, 19 + Kw)
        pick[1, 2], done0[1, 2] = 19 + Kw, False
    elif kind == "overruns_budget":
        pred[2, 10], pred[2, 40] = 10, 40
        pred[2, 11:40] = np.arange(10, 39)
        pick[2, 1], done0[2, 1] = 10 + Kw + 5, False
    elif kind == "none_starts":
        done0[:] = True
    elif kind == "cycle_of_two":
        pred[0, 30], pred[0, 31] = 31, 30
        pick[0, 3], done0[0, 3] = 30, False
    wenter = rng.random((B, NC), dtype=np.float32)
    noc_p1 = np.append(rng.integers(0, _WALK_N, NC),
                       _WALK_N).astype(np.int32)
    return pred, wenter, noc_p1, pick, done0


@pytest.mark.parametrize("kind,steps,all_done", [
    ("mixed_lengths", None, True),
    ("ends_at_budget", _WALK_KW, True),
    ("overruns_budget", _WALK_KW, False),
    ("none_starts", 0, True),
    ("cycle_of_two", _WALK_KW, False),
])
def test_traceback_walk_equals_full_budget_walk(kind, steps, all_done):
    """The early-ending walk returns what the full-budget walk returns,
    in every output, and reports the longest walk (capped at Kw) as its
    step count: nothing that a step would have written is dropped."""
    from parallel_eda_tpu.route.planes import traceback_walk

    pred, wenter, noc_p1, pick, done0 = _walk_case(kind)
    want = _walk_reference(pred, wenter, noc_p1, pick, done0, _WALK_KW)
    got = traceback_walk(jnp.asarray(pred), jnp.asarray(wenter),
                         jnp.asarray(noc_p1), jnp.asarray(pick),
                         jnp.asarray(done0), _WALK_KW)
    for name, g, w in zip(("cur", "done", "cells", "nodes", "wst"),
                          got, want):
        assert np.array_equal(np.asarray(g), w), name
    assert int(got[5]) == want[5]
    if steps is not None:
        assert int(got[5]) == steps
    else:
        assert 1 < int(got[5]) < _WALK_KW
    assert bool(want[1].all()) == all_done
    # a walk is `ok` only where it stands on a root when the loop ends
    cur = want[0]
    on_root = np.take_along_axis(pred, cur, axis=1) == cur
    assert on_root[~done0].all() == all_done


# ---- the standalone resident batch step (__graft_entry__.entry) ----

def test_resident_batch_step_equals_a_one_group_window():
    """`route_batch_resident_planes` (the driver's entry() program) is
    `_step_core` alone: one forced group of a one-iteration window
    program must leave the same paths, delays, reached flags, boxes and
    occupancy, and count the same sweeps."""
    import jax

    import __graft_entry__ as graft
    from parallel_eda_tpu.route.planes import (SCAL_S_EXEC,
                                               route_window_planes)

    fn, args = graft.entry()
    res = jax.jit(fn)(*(jnp.array(a) for a in args))

    p = graft.planes_step_problem()
    occ, acc, paths, sink_delay, all_reached, bb = p["state"]
    win = route_window_planes(
        p["pg"], p["dev"], occ, acc, paths, sink_delay, all_reached, bb,
        *p["nets"], p["sel"][None], p["valid"][None], p["full_bb"],
        jnp.float32(0.5), jnp.float32(1.0), jnp.float32(1.0),
        jnp.float32(0.0), jnp.int32(0), jnp.int32(1),
        1, p["nsweeps"], p["max_len"], p["num_waves"], p["group"],
        True, topk=64)
    w_occ, _, w_paths, w_delay, w_reached, w_bb = win[:6]
    for name, got, want in (("paths", res[0], w_paths),
                            ("sink_delay", res[1], w_delay),
                            ("all_reached", res[2], w_reached),
                            ("bb", res[3], w_bb), ("occ", res[4], w_occ)):
        assert np.array_equal(np.asarray(got), np.asarray(want)), name
    assert np.asarray(res[2]).any()
    assert int(res[5]) == int(win[-1][SCAL_S_EXEC]) > 0


# ---- the cost fields alone (planes.entry_fields, planes.node_cost_field)
# against the per-(net, cell) gather forms they replaced ----

def _field_graph(kind):
    """A small rr graph with its PlanesGraph: length-1 two-way wires
    (``bidirectional``; ``k4n4`` with the benchmark's cluster, ``directs``
    with two dedicated OPIN -> IPIN connections into one pin), and the
    published length-4 single-driver ones (``directional_l4``)."""
    import warnings

    from parallel_eda_tpu.arch.builtin import k6_n10_40nm_arch
    from parallel_eda_tpu.arch.model import DirectSpec

    if kind == "directional_l4":
        arch, n = k6_n10_40nm_arch(chan_width=16), 5
    elif kind == "k4n4":
        arch, n = minimal_arch(K=4, N=4, I=10, io_capacity=2,
                               chan_width=12), 5
    elif kind == "directs":
        arch, n = minimal_arch(chan_width=10), 4
        arch.directs = [
            DirectSpec(from_type="clb", from_pin=6, to_type="clb",
                       to_pin=0, dx=0, dy=1),
            DirectSpec(from_type="clb", from_pin=7, to_type="clb",
                       to_pin=0, dx=1, dy=0)]
    else:
        arch, n = minimal_arch(chan_width=8), 4
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        rr = build_rr_graph(arch, DeviceGrid(n, n, arch.io_capacity))
    pg = build_planes(rr)
    assert pg.directional == (kind == "directional_l4")
    return rr, pg


def _entry_case(pg, N, B, Ko=12, O=3):
    """Seeded wave inputs over ``pg``'s canvas that hold every edge of
    the entry fields; returns (args, the planted (net, cell) spots)."""
    ncells = pg.ncells
    rng = np.random.default_rng(B + ncells)
    wire = np.where(np.asarray(pg.node_of_cell) < N)[0]
    ecell = np.stack([rng.choice(wire, Ko, replace=False)
                      for _ in range(B)]).astype(np.int32)
    edelay = rng.uniform(1e-11, 9e-11, (B, Ko)).astype(np.float32)
    eoidx = rng.integers(0, O, (B, Ko)).astype(np.int32)
    # padded entries at the ncells sentinel (their delays are garbage
    # on purpose: nothing may read them)
    ecell[:, Ko - 3:] = ncells
    ecell[5, :] = ncells                      # a net with no entry
    seed_cells = rng.random((B, ncells)) < 0.03
    seed_cells[np.arange(B)[:, None], np.minimum(ecell, ncells - 1)] = False
    cc_flat = rng.uniform(1e-11, 1e-10, (B, ncells)).astype(np.float32)
    opin_du = rng.uniform(0.0, 1e-10, (B, O)).astype(np.float32)
    crit_w = rng.uniform(0.1, 0.99, B).astype(np.float32)
    valid = np.ones(B, bool)
    # net 0: entries 0, 1 and 2 of ONE cell at equal cost (no delay
    # term at crit 0, one OPIN), each with its own delay
    crit_w[0] = 0.0
    ecell[0, 1] = ecell[0, 2] = ecell[0, 0]
    eoidx[0, :3] = 1
    # net 1: invalid, all-INF costs
    valid[1] = False
    # net 2: entry 0 lands on a tree cell and cannot beat it
    seed_cells[2, ecell[2, 0]] = True
    # net 3: entry 0's cell lies outside the box (INF congestion);
    # entries 1 and 2 share a cell and the LATER one is cheaper
    cc_flat[3, ecell[3, 0]] = np.inf
    ecell[3, 2] = ecell[3, 1]
    eoidx[3, 1:3] = 0
    edelay[3, 1], edelay[3, 2] = 8e-11, 2e-11
    # net 4: its only OPIN already used (entry cost without the OPIN's)
    opin_du[4, :] = 0.0
    spots = {"tie": (0, ecell[0, 0]), "seeded": (2, ecell[2, 0]),
             "outside": (3, ecell[3, 0]), "later_wins": (3, ecell[3, 1])}
    args = (seed_cells, opin_du, cc_flat, crit_w, valid, ecell, eoidx,
            edelay)
    return tuple(jnp.asarray(a) for a in args), spots


@pytest.mark.parametrize("B", [16, 64])
@pytest.mark.parametrize("kind", ["bidirectional", "directional_l4"])
def test_entry_fields_equal_the_per_cell_gather(kind, B):
    """`entry_fields` writes by the entries what the gather form read
    by the cells: d0, entry_flag, wk and wenter0 bit for bit, on every
    edge the block has."""
    from cost_field_refs import entry_fields_gather
    from parallel_eda_tpu.route.planes import entry_fields

    rr, pg = _field_graph(kind)
    args, spots = _entry_case(pg, rr.num_nodes, B)
    want = [np.asarray(a) for a in entry_fields_gather(*args)]
    got = [np.asarray(a) for a in entry_fields(*args)]
    for name, g, w in zip(("d0", "entry_flag", "wk", "wenter0"),
                          got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    d0, flag, wk, wenter0 = got
    edelay = np.asarray(args[7])
    Ko = edelay.shape[1]
    # the edges are live, not vacuous
    b, c = spots["tie"]
    assert flag[b, c] and wk[b, c] == 0 and wenter0[b, c] == edelay[0, 0]
    assert edelay[0, 0] != edelay[0, 1]
    # (an invalid net's INF costs still 'win' their INF cells in wk)
    assert not flag[1].any() and not wenter0[1].any()
    assert np.isinf(d0[1][~np.asarray(args[0])[1]]).all()
    b, c = spots["seeded"]
    assert d0[b, c] == 0.0 and not flag[b, c] and wenter0[b, c] == 0.0
    b, c = spots["outside"]
    assert np.isinf(d0[b, c]) and wk[b, c] == 0 and wenter0[b, c] == 0.0
    b, c = spots["later_wins"]
    assert wk[b, c] == 2 and wenter0[b, c] == edelay[3, 2]
    assert not flag[5].any() and (wk[5] == Ko).all()
    # a weight on every flagged cell and nowhere else
    assert np.array_equal(wenter0 != 0.0, flag)
    assert flag.sum() > B * (Ko - 3) // 2


@pytest.mark.parametrize("B", [16, 64])
@pytest.mark.parametrize("kind", ["bidirectional", "directional_l4"])
def test_node_cost_field_equals_the_per_cell_gather(kind, B):
    """One index vector applied to all nets alike gives the values
    B * ncells independent element reads gave."""
    from cost_field_refs import node_cost_field_gather
    from parallel_eda_tpu.route.planes import node_cost_field

    rr, pg = _field_graph(kind)
    N = rr.num_nodes
    rng = np.random.default_rng(B)
    congj = rng.uniform(1e-11, 1e-10, (B, N)).astype(np.float32)
    congj[rng.random((B, N)) < 0.2] = np.inf          # outside the box
    congj_p1 = jnp.asarray(np.concatenate(
        [congj, np.full((B, 1), np.inf, np.float32)], axis=1))
    want = np.asarray(node_cost_field_gather(congj_p1, pg.node_of_cell))
    got = np.asarray(node_cost_field(congj_p1, pg.node_of_cell))
    assert got.dtype == want.dtype and got.shape == (B, pg.ncells)
    assert np.array_equal(got, want)
    # canvas cells no wire covers read the INF column
    noc = np.asarray(pg.node_of_cell)
    assert np.isinf(got[:, noc == N]).all()
    assert np.isfinite(got).any() and np.isinf(got[:, noc < N]).any()


def _placed(kind):
    """A placed 60-LUT circuit on the published length-4 single-driver
    wires (``directional_l4``) or on the benchmark's K=4 N=4 cluster
    with two-way length-1 wires; ``directional_l4_19x19`` is 30 LUTs
    (35 nets) on those wires on a 19 x 19 grid, the smallest size
    class whose crop ladder has a 16 x 16 rung."""
    import warnings

    from parallel_eda_tpu.arch.builtin import k6_n10_40nm_arch
    from parallel_eda_tpu.flow import prepare, run_place_native
    from parallel_eda_tpu.netlist.generate import generate_circuit

    luts, gen_seed, n = 60, 3, 0
    if kind == "directional_l4":
        arch, W = k6_n10_40nm_arch(chan_width=32), 32
    elif kind == "directional_l4_19x19":
        arch, W = k6_n10_40nm_arch(chan_width=24), 24
        luts, gen_seed, n = 30, 1, 19
    else:
        arch, W = minimal_arch(K=4, N=4, I=10, io_capacity=2,
                               chan_width=12), 12
    nl = generate_circuit(num_luts=luts, num_inputs=8, num_outputs=8,
                          K=arch.K, seed=gen_seed)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        f = run_place_native(prepare(nl, arch, W, seed=5, nx=n, ny=n),
                             seed=7)
    assert f.rr.unidir == kind.startswith("directional_l4")
    return f


def _assert_route_unmoved_by(f, monkeypatch, refs):
    """Route ``f``, then again with planes' builders ``refs`` {name:
    reference} patched in: every reference was traced, and the two
    routes agree node for node, in iterations, sweeps, waves and walk
    steps, and in the dirty set and colours the host read of every
    window."""
    from parallel_eda_tpu.route import planes

    programs = (planes.route_window_planes,
                planes.route_batch_resident_planes)

    def route():
        # the builders are traced into jitted programs: drop what they
        # hold, before and after
        for prog in programs:
            prog.clear_cache()
        try:
            return Router(f.rr, RouterOpts(batch_size=16)).route(f.term)
        finally:
            for prog in programs:
                prog.clear_cache()

    # what the host reads of each window's summary: (rrm, colors)
    seen = {"res": [], "ref": []}
    unpack = planes.unpack_window_status

    def recording(side):
        def wrapped(status):
            out = unpack(status)
            seen[side].append((out[0].copy(), out[1].copy()))
            return out
        return wrapped

    monkeypatch.setattr(planes, "unpack_window_status", recording("res"))
    res = route()
    monkeypatch.setattr(planes, "unpack_window_status", recording("ref"))
    calls = set()

    def counted(name, fn):
        def wrapped(*a, **kw):
            calls.add(name)
            return fn(*a, **kw)
        return wrapped

    for name, fn in refs.items():
        monkeypatch.setattr(planes, name, counted(name, fn))
    ref = route()
    monkeypatch.undo()
    assert calls == set(refs)
    assert res.success and res.total_waves > res.iterations > 1
    assert (res.success, res.iterations, res.wirelength,
            res.total_relax_steps, res.total_relax_steps_useful,
            res.total_waves, res.total_walk_steps) == (
        ref.success, ref.iterations, ref.wirelength,
        ref.total_relax_steps, ref.total_relax_steps_useful,
        ref.total_waves, ref.total_walk_steps)
    assert len(seen["res"]) == len(seen["ref"]) == len(res.stats)
    for (rrm, colors), (rrm_ref, colors_ref) in zip(seen["res"],
                                                    seen["ref"]):
        assert np.array_equal(rrm, rrm_ref)
        assert np.array_equal(colors, colors_ref)
    assert np.array_equal(np.asarray(res.paths), np.asarray(ref.paths))
    assert np.array_equal(np.asarray(res.sink_delay),
                          np.asarray(ref.sink_delay))
    assert np.array_equal(np.asarray(res.occ), np.asarray(ref.occ))
    return res


def _mis_counters():
    """(window programs dispatched, colourings the host read) so far in
    this process."""
    from parallel_eda_tpu.obs import get_metrics

    v = get_metrics().values("route.mis_colors.")
    return (v.get("route.mis_colors.calls_total", 0),
            v.get("route.mis_colors.read_total", 0))


def _mis_forms():
    """(skipped, short, full): the programs by what their conflict
    colouring ran, so far in this process."""
    from parallel_eda_tpu.obs import get_metrics

    v = get_metrics().values("route.mis_colors.")
    return tuple(v.get(f"route.mis_colors.{form}_total", 0)
                 for form in ("skipped", "short", "full"))


def test_directional_route_equals_the_route_under_gathered_fields(
        monkeypatch):
    """A whole route on the published length-4 single-driver wires is
    the route with both cost fields built by the per-(net, cell)
    gathers, node for node and in iterations, sweeps and waves."""
    from cost_field_refs import entry_fields_gather, node_cost_field_gather

    _assert_route_unmoved_by(
        _placed("directional_l4"), monkeypatch,
        {"entry_fields": entry_fields_gather,
         "node_cost_field": node_cost_field_gather})


def test_directional_cropped_route_equals_the_full_canvas_route(
        monkeypatch):
    """On a grid with a 16 x 16 crop rung (19 x 19) a route on the
    length-4 single-driver wires dispatches a cropped rung and the full
    canvas in every window, and is the route with the full-canvas
    relaxation in the cropped one's place -- the SAME dispatch, node
    for node and in iterations, sweeps, waves and walk steps.  (Against
    ``crop="off"`` the dispatch itself differs: one subset of all the
    nets where ``auto`` hands each rung its own, so the net groups and
    with them the negotiation are others; that route is held to be
    legal and to count no cropped sweep.)

    The reference route also colours EVERY rung, by the full form
    (ISSUE 46): the route that colours the window's last rung alone, by
    the short list where few nodes are over, is that route too, and its
    three form counters say what it skipped."""
    import jax.numpy as jnp

    from parallel_eda_tpu.obs import get_metrics
    from parallel_eda_tpu.route import planes, router

    def full_canvas(pg, d0, cc, crit_c, wenter0, nsweeps, ox, oy, cnx,
                    cny, plane_dtype="f32", cut=None):
        assert (cnx, cny) == (16, 16)
        return planes.planes_relax(pg, d0, cc, crit_c, wenter0, nsweeps,
                                   None, plane_dtype)

    def full_form_every_rung(dev, occ, paths, all_reached, topk, n_colors,
                             read, fan=None):
        return planes._mis_colors_full(
            dev, occ, paths, all_reached, topk, n_colors, fan) + (
            jnp.int32(planes.MIS_FULL),)

    f = _placed("directional_l4_19x19")
    assert (f.grid.nx, f.grid.ny) == (19, 19)
    calls0, reads0 = _mis_counters()
    forms0 = _mis_forms()
    variants0 = set(router._DISPATCH_VARIANTS)
    traces = []
    window_colours = planes.window_colours

    def traced(*a, **kw):
        traces.append(1)
        return window_colours(*a, **kw)

    monkeypatch.setattr(planes, "window_colours", traced)
    # a short list so short that the early windows, with tens of nodes
    # over, colour by the table: both forms in one route
    monkeypatch.setattr(planes, "MIS_SHORT_K", 4)
    res = _assert_route_unmoved_by(
        f, monkeypatch, {"planes_relax_cropped": full_canvas,
                         "window_colours": full_form_every_rung})
    check_route(f.rr, f.term, res.paths, res.occ)
    # a program ran once a rung and the host read one a window (the
    # helper routed twice): the cropped rung's colours nobody reads
    calls, reads = _mis_counters()
    assert calls - calls0 > reads - reads0 == 2 * len(res.stats)
    # the first route skipped them, coloured some windows' ends by the
    # short list and some by the table; the reference ran the full form
    # on every rung.  The three counters sum to the programs
    skipped, short, full = (a - b for a, b in zip(_mis_forms(), forms0))
    one = (calls - calls0) // 2
    assert skipped + short + full == calls - calls0 == 2 * one
    assert skipped == one - len(res.stats) > 0
    assert short > 0 and full > one
    # the flag is traced: a rung that is its window's last in one window
    # and not in another is ONE program, so the first route traced the
    # window program once a dispatch variant (and the variants' keys
    # hold no flag: they are the parent's)
    new = set(router._DISPATCH_VARIANTS) - variants0
    assert len(traces) == len(new) > 1
    # not by bypass: a cropped rung was dispatched, and so was the
    # full canvas
    assert 0 < res.total_relax_steps_cropped < res.total_relax_steps
    reg = get_metrics().values("route.crop.")
    assert reg["route.crop.net_dispatches_cropped_total"] > 0
    assert reg["route.crop.net_dispatches_full_total"] > 0

    full0 = reg["route.crop.net_dispatches_full_total"]
    off = Router(f.rr, RouterOpts(batch_size=16, crop="off")).route(f.term)
    assert off.success and off.total_relax_steps_cropped == 0
    check_route(f.rr, f.term, off.paths, off.occ)
    reg = get_metrics().values("route.crop.")
    assert reg["route.crop.net_dispatches_full_total"] > full0


# ---- the sink pick (planes.build_planes_terminals' factored sink
# tables, planes.sink_pin_costs, planes.sink_pick) against the flat
# (cell, pin, delay) candidate form it replaced ----

def _seeded_nets(rr, R, S, seed):
    """R nets of random SINK nodes in S slots -- slot 0 a cluster's
    (the sinks of most pins), the others clusters' and pads' alike:
    trailing slots padded, net 3 all pads."""
    from parallel_eda_tpu.rr.graph import SINK, SOURCE

    rng = np.random.default_rng(seed)
    all_sinks = np.where(rr.node_type == SINK)[0]
    pins = np.diff(rr.in_row_ptr)[all_sinks]
    sinks = rng.choice(all_sinks, (R, S)).astype(np.int64)
    sinks[:, 0] = rng.choice(all_sinks[pins == pins.max()], R)
    sinks[:, S - 2:] = -1
    sinks[3, :] = -1
    source = rng.choice(np.where(rr.node_type == SOURCE)[0], R)
    return source.astype(np.int64), sinks


@pytest.mark.parametrize("kind", ["k4n4", "directional_l4", "directs"])
def test_sink_tables_reproduce_the_flat_tables(kind):
    """Every flat candidate (cell, ipin, delay) of rank k sits at its
    (pin, cell) slot and no slot holds anything else; pads stay pads;
    the three gauges say the shapes."""
    from parallel_eda_tpu.obs import get_metrics
    from parallel_eda_tpu.route.planes import (RANK_PAD,
                                               build_planes_terminals)
    from sink_pick_refs import flat_sink_tables

    rr, pg = _field_graph(kind)
    N, ncells = rr.num_nodes, pg.ncells
    source, sinks = _seeded_nets(rr, 24, 6, seed=1)
    con = np.asarray(pg.cell_of_node)
    pt = build_planes_terminals(rr, source, sinks, con, ncells)
    uid, u_cell, u_ipin, u_del = flat_sink_tables(rr, sinks, con, ncells)
    assert np.array_equal(pt.sink_uid, uid)
    U1, K = u_cell.shape
    _, P, C = pt.uid_pcrank.shape
    assert pt.uid_ucell.shape == (U1, C) and pt.uid_upin.shape == (U1, P)
    assert pt.uid_pcdel.shape == pt.uid_pcrank.shape == (U1, P, C)
    g = get_metrics().values("route.sink_pick.")
    assert (g["route.sink_pick.cands_per_sink"],
            g["route.sink_pick.cells_per_sink"],
            g["route.sink_pick.pins_per_sink"]) == (K, C, P)
    assert pt.sink_cands == K and C < K      # pins share tracks here

    held = pt.uid_pcrank < RANK_PAD
    dropped = 0
    for u in range(U1):
        ps, cs = np.nonzero(held[u])
        ks = pt.uid_pcrank[u, ps, cs]
        real = np.nonzero((u_cell[u] < ncells) | (u_ipin[u] < N))[0]
        # ranks are distinct flat positions of real candidates ...
        assert len(np.unique(ks)) == len(ks)
        assert np.isin(ks, real).all()
        # ... each holding its candidate's cell, pin and delay
        assert np.array_equal(pt.uid_ucell[u, cs], u_cell[u, ks])
        assert np.array_equal(pt.uid_upin[u, ps], u_ipin[u, ks])
        assert np.array_equal(pt.uid_pcdel[u, ps, cs], u_del[u, ks])
        # a candidate without a slot shares (pin, pad cell) with an
        # earlier one: several OPINs that drive one pin directly
        for k in np.setdiff1d(real, ks):
            assert u_cell[u, k] == ncells
            twin = ks[(pt.uid_upin[u, ps] == u_ipin[u, k])
                      & (pt.uid_ucell[u, cs] == ncells)]
            assert len(twin) == 1 and twin[0] < k
            dropped += 1
        # cells ascending and distinct, then pads; pins, then pads
        # (only OPIN -> IPIN edges sit on the pad cell, in ONE slot)
        nc = int((pt.uid_ucell[u] < ncells).sum())
        assert (np.diff(pt.uid_ucell[u, :nc]) > 0).all()
        assert (pt.uid_ucell[u, nc:] == ncells).all()
        assert not held[u, :, nc + 1:].any()
        assert not held[u, :, nc:].any() or kind == "directs"
        npin = int((pt.uid_upin[u] < N).sum())
        assert (pt.uid_upin[u, npin:] == N).all()
        assert not held[u, npin:].any()
    assert not pt.uid_pcdel[~held].any()
    assert not held[U1 - 1].any()            # the pad row
    assert (dropped > 0) == (kind == "directs")


def _sink_case(rr, pg, B, seed):
    """Seeded wave inputs over ``pg``'s canvas for B nets, with the
    batch's factored and flat sink tables; exact ties planted on nets
    0 and 1.  Returns (dist, congj_p1, crit_w, cw, fact, flat, spots)."""
    from parallel_eda_tpu.route.planes import build_planes_terminals
    from sink_pick_refs import flat_sink_tables

    N, ncells = rr.num_nodes, pg.ncells
    S = 6
    source, sinks = _seeded_nets(rr, B, S, seed)
    con = np.asarray(pg.cell_of_node)
    pt = build_planes_terminals(rr, source, sinks, con, ncells)
    uid, u_cell, u_ipin, u_del = flat_sink_tables(rr, sinks, con, ncells)
    fact = tuple(t[uid] for t in (pt.uid_ucell, pt.uid_upin,
                                  pt.uid_pcdel, pt.uid_pcrank))
    flat = tuple(t[uid] for t in (u_cell, u_ipin, u_del))
    scell, sipin, sdel = flat

    rng = np.random.default_rng(seed + B)
    dist = rng.uniform(1e-10, 9e-9, (B, ncells)).astype(np.float32)
    dist[rng.random((B, ncells)) < 0.3] = np.inf      # unreached cells
    congj = rng.uniform(1e-11, 1e-10, (B, N)).astype(np.float32)
    congj[rng.random((B, N)) < 0.1] = np.inf          # outside the box
    crit_w = rng.uniform(0.1, 0.99, B).astype(np.float32)
    crit_w[4] = 0.0                                   # no timing term
    dist[2] = np.inf                                  # nothing reached

    def real(b, k):
        return scell[b, 0, k] < ncells

    # net 0, slot 0: two cells of ONE pin at one distance, the pin the
    # cheapest by far -- and the earlier candidate on the HIGHER cell,
    # so no order of cell slots picks it
    k1, k2 = next(
        (a, b) for a in range(scell.shape[2])
        for b in range(a + 1, scell.shape[2])
        if real(0, a) and real(0, b) and sipin[0, 0, a] == sipin[0, 0, b]
        and scell[0, 0, a] > scell[0, 0, b])
    assert sdel[0, 0, k1] == sdel[0, 0, k2]
    dist[0, scell[0, 0, [k1, k2]]] = 1e-12
    congj[0, sipin[0, 0, k1]] = 1e-13
    # net 1, slot 0: two PINS at one cost, each on a cell of its own,
    # the earlier pin's cell the higher; no timing term, so the pins'
    # delays cannot part them
    crit_w[1] = 0.0
    k3, k4 = next(
        (a, b) for a in range(scell.shape[2])
        for b in range(a + 1, scell.shape[2])
        if real(1, a) and real(1, b) and sipin[1, 0, a] != sipin[1, 0, b]
        and scell[1, 0, a] > scell[1, 0, b])
    dist[1, scell[1, 0, [k3, k4]]] = 1e-12
    congj[1, sipin[1, 0, [k3, k4]]] = 1e-13
    congj_p1 = np.concatenate(
        [congj, np.full((B, 1), np.inf, np.float32)], axis=1)
    spots = {"two_cells": (0, k1, k2), "two_pins": (1, k3, k4)}
    j = jnp.asarray
    return (j(dist), j(congj_p1), j(crit_w), j(1.0 - crit_w),
            tuple(j(t) for t in fact), tuple(j(t) for t in flat), spots)


@pytest.mark.parametrize("B", [16, 64])
@pytest.mark.parametrize("kind", ["bidirectional", "directional_l4"])
def test_sink_pick_equals_the_flat_pick(kind, B):
    """The pick over distinct cells x pins gives what the argmin over
    the flat candidates gave: sink_dist, ent_cell, ent_ipin and ent_wdel
    bit for bit, exact ties and pad sinks included."""
    from parallel_eda_tpu.route.planes import sink_pick, sink_pin_costs
    from sink_pick_refs import sink_pick_flat, sink_pin_costs_flat

    rr, pg = _field_graph(kind)
    N, ncells = rr.num_nodes, pg.ncells
    dist, congj_p1, crit_w, cw, fact, flat, spots = _sink_case(
        rr, pg, B, seed=7)
    ipin_congj = sink_pin_costs_flat(congj_p1, flat)
    want = [np.asarray(a) for a in sink_pick_flat(
        dist, ipin_congj, crit_w, cw, flat)]
    pin_congj = sink_pin_costs(congj_p1, fact)
    got = [np.asarray(a) for a in sink_pick(
        dist, pin_congj, crit_w, cw, fact)]
    for name, g, w in zip(("sink_dist", "ent_cell", "ent_ipin",
                           "ent_wdel"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    sink_dist, ent_cell, ent_ipin, ent_wdel = got
    scell, sipin, sdel = (np.asarray(t) for t in flat)
    # each pin's cost is the cost its candidates read
    upin, pcrank = np.asarray(fact[1]), np.asarray(fact[3])
    assert pin_congj.shape == upin.shape
    assert np.array_equal(
        np.asarray(pin_congj),
        np.take_along_axis(np.asarray(congj_p1),
                           upin.reshape(B, -1), axis=1).reshape(upin.shape))

    # the edges are live, not vacuous
    cand = (np.take_along_axis(
        np.concatenate([np.asarray(dist), np.full((B, 1), np.inf,
                                                  np.float32)], axis=1),
        scell.reshape(B, -1), axis=1).reshape(scell.shape)
        + np.asarray(crit_w)[:, None, None] * sdel
        + np.asarray(cw)[:, None, None] * np.asarray(ipin_congj))
    for spot in ("two_cells", "two_pins"):
        b, ka, kb = spots[spot]
        assert np.isfinite(sink_dist[b, 0])
        assert cand[b, 0, ka] == cand[b, 0, kb] == sink_dist[b, 0]
        assert (cand[b, 0] == sink_dist[b, 0]).sum() == 2
        # the earlier candidate won though it sits on the later cell
        assert ent_cell[b, 0] == scell[b, 0, ka] > scell[b, 0, kb]
        assert ent_ipin[b, 0] == sipin[b, 0, ka]
    b, ka, kb = spots["two_pins"]
    assert sipin[b, 0, ka] != sipin[b, 0, kb]
    # nothing reached: the first candidate stands, at INF
    assert np.isinf(sink_dist[2]).all()
    assert np.array_equal(ent_cell[2], scell[2, :, 0])
    # pad sinks read the pads
    pad = np.asarray(fact[0])[:, :, 0] == ncells
    assert pad[3].all() and pad[:, -2:].all() and not pad.all()
    assert np.isinf(sink_dist[pad]).all()
    assert (ent_cell[pad] == ncells).all() and (ent_ipin[pad] == N).all()
    assert not ent_wdel[pad].any()
    # most real sinks found a finite candidate, through a real pin
    found = np.isfinite(sink_dist)
    assert found.sum() > (~pad).sum() // 2
    assert (ent_cell[found] < ncells).all() and (ent_ipin[found] < N).all()
    assert (pcrank < np.iinfo(np.int32).max).any(axis=(2, 3))[~pad].all()


@pytest.mark.parametrize("kind", ["directional_l4", "bidirectional"])
def test_route_equals_the_route_under_the_flat_sink_pick(kind,
                                                         monkeypatch):
    """A whole route -- on the published length-4 single-driver wires
    and on two-way length-1 ones -- is the route with the flat
    candidate pick patched in, node for node and in iterations, sweeps,
    waves and walk steps."""
    from parallel_eda_tpu.route.planes import build_planes_terminals
    from sink_pick_refs import flat_forms

    f = _placed(kind)
    pg = build_planes(f.rr)
    pt = build_planes_terminals(f.rr, f.term.source, f.term.sinks,
                                np.asarray(pg.cell_of_node), pg.ncells)
    assert pt.sink_cands > pt.uid_ucell.shape[1] > 1
    pin_costs_flat, pick_flat = flat_forms(pt.sink_cands, f.rr.num_nodes)
    _assert_route_unmoved_by(
        f, monkeypatch,
        {"sink_pin_costs": pin_costs_flat, "sink_pick": pick_flat,
         # the flat pick is the DENSE rung's reference: the live rungs
         # read the factored tables themselves
         "live_pick_rungs": lambda B, S: ()})


# ---- the LIVE sink pick (planes.sink_pick_live / sink_pick_wave: the
# wave's unrouted sinks listed densely, M x C distances read) against
# the dense sink_pick ----

LIVE_B, LIVE_S = 16, 6          # _sink_case's batch: rungs (16, 24, 48)
# slots the cases are about, live first whatever the count: the two
# planted ties (equal costs parted by rank), a sink of the net nothing
# reached (every hop INF), an ordinary one
LIVE_SPOTS = [(0, 0), (1, 0), (2, 0), (7, 1)]
INVALID_NETS = (5, 6)


def _live_case(kind="directional_l4"):
    rr, pg = _field_graph(kind)
    dist, congj_p1, crit_w, cw, fact, _, _ = _sink_case(
        rr, pg, LIVE_B, seed=7)
    from parallel_eda_tpu.route.planes import sink_pin_costs
    return (dist, sink_pin_costs(congj_p1, fact), crit_w, cw, fact), \
        pg.ncells


def _live_mask(fact, ncells, count):
    """``count`` live slots, the LIVE_SPOTS first, then seeded: real
    sinks (not pad slots) of valid nets alone, as the wave's
    ``remaining`` holds them; None for every slot of the batch."""
    if count is None:
        return np.ones((LIVE_B, LIVE_S), bool)
    ok = np.asarray(fact[0])[:, :, 0] != ncells
    ok[list(INVALID_NETS)] = False
    assert not ok[3].any() and not ok[:, -2:].any()     # the pad slots
    spots = [b * LIVE_S + s for b, s in LIVE_SPOTS]
    assert ok.reshape(-1)[spots].all()
    rest = np.setdiff1d(np.flatnonzero(ok), spots)
    order = spots + list(np.random.default_rng(count).permutation(rest))
    assert count <= len(order)
    m = np.zeros(LIVE_B * LIVE_S, bool)
    m[order[:count]] = True
    return m.reshape(LIVE_B, LIVE_S)


def _assert_live_equals_dense(got, want, mask):
    names = ("sink_dist", "ent_cell", "ent_ipin", "ent_wdel")
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g[mask], w[mask]), name
    assert np.isinf(np.asarray(got[0])[~mask]).all()


def test_the_live_rungs_of_a_batch():
    from parallel_eda_tpu.route.planes import live_pick_rungs

    assert live_pick_rungs(LIVE_B, LIVE_S) == (16, 24, 48)
    assert live_pick_rungs(64, 8) == (64, 128, 256)
    assert live_pick_rungs(64, 13) == (104, 208, 416)
    assert live_pick_rungs(16, 204) == (408, 816, 1632)
    # a batch too small for a list narrower than itself has none
    assert live_pick_rungs(2, 4) == ()
    assert live_pick_rungs(4, 3) == (8,)


def test_the_forms_tool_times_nothing_off_the_chip(capsys):
    """tools/sink_pick_forms.py: a time is a device number only from a
    TPU, so off one the tool exits 2 before it times a form (the suite
    runs on the CPU); --allow-cpu is a rehearsal that says so."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / \
        "sink_pick_forms.py"
    spec = importlib.util.spec_from_file_location("sink_pick_forms", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--shapes", "route_tight", "--reps", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "not a TPU" in err


# live counts of a rung of width M, by name
RUNG_COUNTS = {"0": lambda M: 0, "1": lambda M: 1, "4": lambda M: 4,
               "M-1": lambda M: M - 1, "M": lambda M: M}


@pytest.mark.parametrize("count", list(RUNG_COUNTS))
@pytest.mark.parametrize("rung", [0, 1, 2])
def test_the_live_pick_equals_the_dense_pick_on_a_rung(rung, count):
    """sink_pick_live at one list width: bit for bit sink_pick on the
    live slots (ties by rank, a sink without a finite hop), INF on the
    others -- pad sinks and invalid nets' slots among them -- from no
    live slot to a full list."""
    from parallel_eda_tpu.route.planes import (live_pick_rungs, sink_pick,
                                               sink_pick_live)

    args, ncells = _live_case()
    M = live_pick_rungs(LIVE_B, LIVE_S)[rung]
    mask = _live_mask(args[4], ncells, RUNG_COUNTS[count](M))
    # compiled, both, as the window program holds them (op by op the
    # CPU rounds a hop's two products apart, compiled it fuses them)
    want = jax.jit(sink_pick)(*args)
    got = jax.jit(sink_pick_live, static_argnames="M")(
        *args, jnp.asarray(mask), M=M)
    _assert_live_equals_dense(got, want, mask)
    if mask[2, 0]:
        assert np.isinf(np.asarray(got[0])[2, 0])       # live, no hop
        assert np.isfinite(np.asarray(got[0])[mask]).any()


# (live count, sink rows the wave must have read): each rung's last
# count and the first past it; None = every slot of the batch live
WAVE_CASES = [(0, 16), (1, 16), (16, 16), (17, 24), (24, 24), (25, 48),
              (48, 48), (49, LIVE_B * LIVE_S), (52, LIVE_B * LIVE_S),
              (None, LIVE_B * LIVE_S)]


@pytest.mark.parametrize("count, rows", WAVE_CASES)
@pytest.mark.parametrize("kind", ["directional_l4", "bidirectional",
                                  "directs"])
def test_the_wave_pick_takes_the_narrowest_rung_that_holds(kind, count,
                                                           rows):
    """sink_pick_wave over the rung boundaries, on one-way and two-way
    wires and on a graph whose sinks hold a DIRECT hop (OPIN -> IPIN,
    on the pad cell) beside their fabric hops."""
    from parallel_eda_tpu.route.planes import (RANK_PAD, live_pick_rungs,
                                               sink_pick, sink_pick_wave)

    args, ncells = _live_case(kind)
    mask = _live_mask(args[4], ncells, count)
    if kind == "directs" and (count is None or count >= 16):
        direct = ((np.asarray(args[4][3]) < RANK_PAD)
                  & (np.asarray(args[4][0]) == ncells)[:, :, None, :]
                  ).any(axis=(2, 3))
        assert (direct & mask).any()
    want = jax.jit(sink_pick)(*args)
    wave = jax.jit(sink_pick_wave, static_argnames="rungs")
    *got, read = wave(*args, jnp.asarray(mask),
                      rungs=live_pick_rungs(LIVE_B, LIVE_S))
    assert int(read) == rows
    if rows == LIVE_B * LIVE_S:
        # the dense rung IS sink_pick, every slot
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
    else:
        _assert_live_equals_dense(got, want, mask)
    # no ladder (a mesh): the dense pick, statically
    *dense, read = wave(*args, jnp.asarray(mask), rungs=())
    assert int(read) == LIVE_B * LIVE_S
    for g, w in zip(dense, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# ---- the conflict colouring (planes._mis_colors: a slot's column of
# the conflict matrix by one read of a node-indexed table) against the
# searchsorted form it replaced ----

# case: (overused nodes, topk (None: the graph's node count), every
# overuse equal, the planted net the case is about)
MIS_CASES = {
    "no_overused_node": (0, 64, False, None),
    "fewer_overused_than_topk": (24, 64, False, None),
    "more_overused_than_topk": (120, 32, False, "dump_only"),
    "topk_is_the_node_count": (24, None, False, None),
    "ties_across_the_topk_boundary": (96, 32, True, None),
    "an_unreached_net_with_a_clean_path": (24, 64, False,
                                           "unreached_clean"),
    "a_net_of_sentinel_slots": (24, 64, False, "all_sentinel"),
}


def _mis_case(kind, case):
    """(dev, occ, paths, all_reached, topk): 24 seeded nets of 3 sinks
    x 10 path slots (tails padded with the sentinel N) on ``kind``'s
    graph, the case's overused nodes all ON paths; net 0 carries the
    case's plant."""
    n_over, topk, ties, plant = MIS_CASES[case]
    rr, _ = _field_graph(kind)
    dev = to_device(rr)
    N = rr.num_nodes
    R, S, L = 24, 3, 10
    rng = np.random.default_rng(len(case) + 100 * len(kind))
    paths = rng.integers(0, N, (R, S, L))
    ln = rng.integers(1, L + 1, (R, S))
    paths[np.arange(L) >= ln[:, :, None]] = N
    cap = np.asarray(dev.capacity)
    hot = rng.choice(np.unique(paths[1:][paths[1:] < N]), n_over,
                     replace=False)
    over = np.zeros(N, np.int64)
    over[hot] = 1 if ties else 1 + rng.permutation(n_over)
    occ = np.where(over > 0, cap + over, rng.integers(0, 2, N) * cap)
    reached = np.ones(R, bool)
    reached[5] = False
    if plant == "dump_only":
        # its only overused nodes are the three LEAST overused
        paths[0] = N
        paths[0, 0, :3] = hot[np.argsort(over[hot])[:3]]
    elif plant == "unreached_clean":
        paths[0] = rng.choice(np.setdiff1d(np.arange(N), hot), (S, L))
        reached[0] = False
    elif plant == "all_sentinel":
        paths[0] = N
    else:
        paths[0] = np.where(np.isin(paths[0], hot), N, paths[0])
    return (dev, jnp.asarray(occ, jnp.int32), jnp.asarray(paths, jnp.int32),
            jnp.asarray(reached), N if topk is None else topk)


@pytest.mark.parametrize("case", sorted(MIS_CASES))
@pytest.mark.parametrize("kind", ["bidirectional", "directional_l4"])
def test_mis_colors_equal_the_searchsorted_form(kind, case):
    """``rrm`` and ``colors`` of the node-indexed table equal the
    searchsorted form's bit for bit; nets of one colour (but the last,
    the rest class) share none of the top-K overused nodes."""
    from jax import lax

    from mis_colors_refs import mis_colors_searchsorted
    from parallel_eda_tpu.route.planes import _mis_colors

    n_colors = 5
    dev, occ, paths, reached, topk = _mis_case(kind, case)
    rrm, colors = _mis_colors(dev, occ, paths, reached, topk, n_colors)
    rrm_ref, colors_ref = mis_colors_searchsorted(
        dev, occ, paths, reached, topk, n_colors)
    assert np.array_equal(rrm, rrm_ref)
    assert np.array_equal(colors, colors_ref)

    N = dev.num_nodes
    n_over, _, _, plant = MIS_CASES[case]
    rrm, colors = np.asarray(rrm), np.asarray(colors)
    over = np.append(np.maximum(np.asarray(occ - dev.capacity), 0), 0)
    assert (over > 0).sum() == n_over
    flat = np.asarray(paths).reshape(len(rrm), -1)
    assert np.array_equal(rrm, (over[flat] > 0).any(1)
                          | ~np.asarray(reached))
    assert (colors[~rrm] == n_colors - 1).all()
    assert rrm[5] and not reached[5]
    val, ids = lax.top_k(jnp.asarray(over[:N]), topk)
    top = np.zeros(N + 1, bool)
    top[np.asarray(ids)[np.asarray(val) > 0]] = True
    assert top.sum() == min(n_over, topk)
    holds = np.zeros((len(rrm), N + 1), bool)
    holds[np.arange(len(rrm))[:, None], flat] = True
    holds &= top
    for c in range(n_colors - 1):
        assert (holds[rrm & (colors == c)].sum(0) <= 1).all()
    if n_over:
        assert (colors[rrm] < n_colors - 1).any()
    # the plant: dirty through the dump column or the flag alone, it
    # contests nothing and joins the first class; all sentinels: clean
    if plant == "all_sentinel":
        assert not rrm[0] and colors[0] == n_colors - 1
    elif plant is not None:
        assert rrm[0] and colors[0] == 0 and not holds[0].any()
        assert (over[flat[0]] > 0).any() == (plant == "dump_only")
    else:
        assert not rrm[0]


@pytest.mark.parametrize("kind", ["directional_l4", "bidirectional"])
def test_route_equals_the_route_under_the_searchsorted_colouring(
        kind, monkeypatch):
    """A whole route on both kinds of wire is the route with the
    searchsorted colouring patched in, node for node, in iterations,
    sweeps, waves and the colours of every window.  One rung a window
    here, so every colouring that ran was read (the two-rung count is
    ``test_directional_cropped_route_equals_the_full_canvas_route``'s)."""
    from mis_colors_refs import mis_colors_searchsorted

    calls0, reads0 = _mis_counters()
    res = _assert_route_unmoved_by(
        _placed(kind), monkeypatch,
        {"_mis_colors": mis_colors_searchsorted})
    calls, reads = _mis_counters()
    # the helper routed twice
    assert calls - calls0 == reads - reads0 == 2 * len(res.stats)
    assert len(res.stats) > 1 and res.total_relax_steps_cropped == 0
