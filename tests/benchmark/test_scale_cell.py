"""The cell ``route_scale`` (MCNC elliptic's counts on k6_N10_40nm):
its files as the manifest names them, the problem they build at full
size, the two readers it brought, and the cropped relaxation alone
against float64 Dijkstra on a small graph of the same wires."""

import os
import types
import warnings

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem

REPO = bench_cells.REPO
SIBLING = "benchmark/configs/mcnc_tseng_like_k6n10_l4.json"
CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
         "route_scale"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_manifest(REPO), REPO,
                             "route_scale")


def test_configuration_keeps_every_published_number(cell):
    cfg, sib = cell.config, bench_cells.load(SIBLING)
    assert cfg["name"] == "mcnc_elliptic_like_k6n10_l4"
    assert cfg["kind"] == "placed_route"
    assert cfg["problem"] == "synth_placed_levelled"
    for key in ("published", "arch", "placement", "router", "guarantees"):
        assert cfg[key] == sib[key], key
    assert cfg["reduced"] == {}
    c = cfg["circuit"]
    assert (c["num_luts"], c["num_inputs"], c["num_outputs"]) == (
        3604, 131, 114)
    # the sibling's assumptions carried over, and the two that scale
    # makes visible
    assert len(cfg["assumed"]) == len(sib["assumed"]) + 2
    assert cfg["assumed"][-2].startswith("fanout")
    assert cfg["assumed"][-1].startswith("logic depth")
    assert len(cfg["source"]) <= 200
    limits = cell.traffic["limits"]
    assert limits == bench_cells.load(
        "benchmark/traffic/route_k6n10_relaxed.json")["limits"]
    assert cell.traffic["driver"] == "route_loop"
    assert cell.traffic["chan_width"] == cfg["as_built"]["chan_width"]


def _levelled(cell, **over):
    builder = harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))
    c = dict(cell.config["circuit"], **over)
    return builder.levelled_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=6, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"])


def test_the_builder_yields_elliptics_flip_flops(cell):
    from parallel_eda_tpu.netlist.netlist import PRIM_FF

    nl, _ = _levelled(cell)
    assert sum(p.kind == PRIM_FF for p in nl.primitives) == 1122
    # the flip-flops are a stream of their own: the depth rule does
    # not move their count
    nl, _ = _levelled(cell, max_lut_levels=6)
    assert sum(p.kind == PRIM_FF for p in nl.primitives) == 1122


@pytest.mark.parametrize("cap", [4, 24])
def test_the_builder_holds_the_stated_depth(cell, cap):
    """No LUT lies deeper than ``max_lut_levels`` behind a register or
    an input, every LUT keeps at least two inputs, and the level the
    builder reports is the netlist's own."""
    from parallel_eda_tpu.netlist.netlist import PRIM_FF, PRIM_LUT

    nl, level = _levelled(cell, num_luts=400, max_lut_levels=cap)
    luts = [p for p in nl.primitives if p.kind == PRIM_LUT]
    assert len(luts) == 400
    registered = {p.inputs[0] for p in nl.primitives if p.kind == PRIM_FF}
    deepest = 0
    for p in luts:          # in creation order: inputs come earlier
        assert 2 <= len(p.inputs) <= 6
        assert len(set(p.inputs)) == len(p.inputs)
        mine = 1 + max(level[s] for s in p.inputs)
        assert mine <= cap
        if p.output not in registered:
            assert level[p.output] == mine
        deepest = max(deepest, mine)
    assert deepest == cap
    assert all(level[p.output] == 0 for p in nl.primitives
               if p.kind == PRIM_FF)


def test_problem_as_built(cell):
    """The stated grid, nets, graph and fingerprint, at full size; and
    the first window's dispatch: the 16 x 16 rung and the full canvas
    both populated, the 8 x 8 rung empty."""
    from parallel_eda_tpu.route.router import (_crop_ladder,
                                               _size_class_buckets)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    built = cell.config["as_built"]
    assert [f.grid.nx, f.grid.ny] == built["grid"] == [19, 19]
    assert f.term.num_nets == built["routed_nets"]
    assert f.rr.num_nodes == built["rr_nodes"]
    assert len(f.rr.in_src) == built["rr_edges"]
    assert f.rr.chan_width == built["chan_width"] == 88
    assert f.rr.unidir and f.rr.group_tracks == 8
    assert problem.fingerprint(f) == cell.traffic["problem_sha256"]
    assert f.term.sinks.shape[1] == built["max_sinks"]
    from parallel_eda_tpu.timing.graph import build_timing_graph
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg.num_tnodes == built["timing_nodes"]
    assert tg.depth == built["timing_depth"] == (
        cell.config["circuit"]["max_lut_levels"] + 2)

    t = f.term
    assert _crop_ladder(f.grid.nx, f.grid.ny) == [(8, 8), (16, 16)]
    classes, assign = _size_class_buckets(
        t.bb_xmax - t.bb_xmin + 1 + 2 * 4, t.bb_ymax - t.bb_ymin + 1 + 2 * 4,
        f.grid.nx, f.grid.ny, min_count=8)
    assert classes == [(16, 16)]
    on_rung, on_canvas = np.bincount(assign).tolist()
    assert on_rung + on_canvas == built["routed_nets"]
    assert on_rung == built["first_window_nets_on_16x16"]
    assert on_canvas > on_rung > 64


def _ctx(steps, cropped, full_nets=None, crop_nets=None):
    reg = {}
    if full_nets is not None:
        reg["route.crop.net_dispatches_full_total"] = full_nets
    if crop_nets is not None:
        reg["route.crop.net_dispatches_cropped_total"] = crop_nets
    route = types.SimpleNamespace(total_relax_steps=steps,
                                  total_relax_steps_cropped=cropped)
    return {"routes": [route, route], "registry": reg}


def _reader(name):
    return harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))


@pytest.mark.parametrize("ctx, want", [
    (_ctx(8000, 1200), 15.0),
    (_ctx(4591, 0), 0.0),           # route_k6n10_relaxed: no rung fits
    (_ctx(0, 0), None),             # a route that counts nothing
    ({"routes": []}, None),
    ({}, None),
    ({"routes": [types.SimpleNamespace(total_relax_steps=10)]}, None),
])
def test_cropped_sweep_share_reader(ctx, want):
    assert _reader("window.cropped_sweep_share").read(ctx) == want


@pytest.mark.parametrize("ctx, want", [
    (_ctx(1, 0, full_nets=7500, crop_nets=2500), 75.0),
    (_ctx(1, 0, full_nets=5085, crop_nets=0), 100.0),
    (_ctx(1, 0, full_nets=0, crop_nets=0), None),   # nothing dispatched
    (_ctx(1, 0), None),             # the parent program: no such counter
    (_ctx(1, 0, full_nets=3), None),
    ({}, None),
])
def test_full_canvas_net_share_reader(ctx, want):
    assert _reader("negotiation.full_canvas_net_share").read(ctx) == want


def test_new_metrics_are_listed_for_every_route_cell():
    manifest = harness.load_manifest(REPO)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in (("window.cropped_sweep_share", "window program"),
                        ("negotiation.full_canvas_net_share",
                         "negotiation driver")):
        m = by_name[name]
        assert m["workloads"] == CELLS and m["layer"] == layer
        assert m["moves"] == "route_s" and m["unit"] == "%"
        assert m["source"] == "program_counter"
    # the cell reports every per-layer metric the three route cells share
    for m in manifest["per_layer"]:
        if "route_relaxed" in m["workloads"]:
            assert "route_scale" in m["workloads"], m["name"]
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "route_scale")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


@pytest.mark.parametrize("seed", [1, 2**31 + 30])
def test_cropped_relaxation_alone_against_dijkstra(seed):
    """``tools/crop_check.py``'s comparison at test size: the length-4
    single-driver wires at W = 16 on a 19 x 19 grid, eight nets whose
    boxes fit the 16 x 16 tile with the crop's margin.  The cropped
    fixpoint is float64 Dijkstra's within the cell's ``relax_gap``
    limit and the full-canvas relaxation's bit for bit (the directional
    sweep has no scan whose tree a shorter row would reshape)."""
    from parallel_eda_tpu.arch.builtin import k6_n10_40nm_arch
    from parallel_eda_tpu.flow import prepare, run_place_native
    from parallel_eda_tpu.netlist.generate import generate_circuit

    tool = harness.load_module(os.path.join(
        REPO, "benchmark", "tools", "crop_check.py"))
    arch = k6_n10_40nm_arch(chan_width=16)
    nl = generate_circuit(num_luts=60, num_inputs=8, num_outputs=8,
                          K=arch.K, seed=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = run_place_native(prepare(nl, arch, 16, seed=5, nx=19, ny=19),
                             seed=7)
    assert f.rr.unidir
    row = tool.check(f.rr, f.term, (16, 16), seed, 8, 256)
    assert row["nets"] == 8 and row["tile"] == [16, 16]
    assert row["fitting_nets"] < f.term.num_nets     # some need the canvas
    assert row["sweeps_cropped"] < 256
    assert 0.0 < row["relax_gap_vs_dijkstra_f64"] < 1e-5
    assert row["same_cells_reached"] and row["cells_reached"] > 1000
    assert row["dist_bits_equal"] and row["pred_equal"]
    assert row["wenter_equal"]
    # the lower precision does not pass: the control of the comparison
    low = tool.check(f.rr, f.term, (16, 16), seed, 8, 256,
                     plane_dtype="bf16")
    assert not low["relax_gap_vs_dijkstra_f64"] < 1e-5
