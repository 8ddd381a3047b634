"""The cell ``route_scale_6k`` (MCNC ``s38417``'s counts on k6_N10_40nm:
6,406 LUTs, 1,463 flip-flops, the first route past 3,000 nets): its
files as the manifest names them, the problem they build at full size
(a BUILD, no route), the readers it brought, and the real files at test
size through ``harness.run_cell`` on the CPU, sound and in bfloat16.
The manifest checks are one-way, so the next cell needs no edit here."""

import json
import os
import types
import warnings

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem

REPO = bench_cells.REPO
NAME = "route_scale_6k"
CONFIG = "benchmark/configs/mcnc_s38417_like_k6n10_l4.json"
TRAFFIC = "benchmark/traffic/route_scale_6k.json"
SIBLING = "benchmark/configs/mcnc_elliptic_like_k6n10_l4.json"
METRIC = "window.cell_sweeps_per_net_route"
GUARD_METRIC = "negotiation.scan_guard_s"
EIGHT_CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
               "route_scale", "route_hetero", "route_fanout", "route_dsp",
               NAME]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_manifest(REPO), REPO, NAME)


def _build(cell, width):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        return problem.build_placed(cell, width)


@pytest.fixture(scope="module")
def built(cell):
    return _build(cell, int(cell.traffic["chan_width"]))


def test_configuration_carries_the_siblings_published_block(cell):
    cfg, sib = cell.config, bench_cells.load(SIBLING)
    assert cfg["name"] == "mcnc_s38417_like_k6n10_l4"
    assert cfg["kind"] == "placed_route"
    assert cfg["problem"] == sib["problem"] == "synth_placed_levelled"
    assert cfg["reduced"] == {} and len(cfg["source"]) <= 200
    assert "s38417" in cfg["source"] and "k6_N10_40nm.xml" in cfg["source"]
    for key in ("published", "arch", "guarantees"):
        assert cfg[key] == sib[key], key
    # the placement and the router are the sibling's to the letter (the
    # anneal's seed 7, B = 64); the notes hold the chip readings: what
    # the parent's programs do on this placement, and the batch table
    assert cfg["placement"] == sib["placement"]
    assert cfg["placement"]["args"] == {"seed": 7, "inner_num": 1.0}
    assert cfg["router"] == sib["router"]
    assert cfg["router"]["opts"]["batch_size"] == 64
    for said in ("seed 7", "NOT legal", "scan_guard", "seed 8", "seed 9"):
        assert said in cfg["placement_note"], said
    for b in ("64", "128"):
        assert f"B={b}" in cfg["router_note"]
    c = cfg["circuit"]
    assert (c["num_luts"], c["num_inputs"], c["num_outputs"]) == (
        6406, 28, 106)
    assert (c["locality"], c["max_lut_levels"], c["generator_seed"]) == (
        sib["circuit"]["locality"], sib["circuit"]["max_lut_levels"], 1)
    # the sibling's assumptions under this circuit's name, and the
    # flip-flop count as recalled
    assert len(cfg["assumed"]) == len(sib["assumed"]) + 1
    for mine, theirs in zip(cfg["assumed"], sib["assumed"]):
        assert mine.split(":")[0] == theirs.split(":")[0]
        assert "elliptic" not in mine
    assert "s38417's published" in cfg["assumed"][0]
    assert cfg["assumed"][1:3] == sib["assumed"][1:3]
    assert "(seed 7, inner_num 1.0)" in cfg["assumed"][2]
    assert cfg["assumed"][-3].startswith("fanout")
    assert cfg["assumed"][-2].startswith("logic depth")
    assert cfg["assumed"][-1].startswith("the flip-flop count")
    assert "RECALLED" in cfg["assumed"][-1]
    traffic = cell.traffic
    assert traffic["limits"] == bench_cells.load(
        "benchmark/traffic/route_scale.json")["limits"]
    assert traffic["driver"] == "route_loop"
    assert traffic["chan_width"] == cfg["as_built"]["chan_width"]
    for key in ("relax_sample_nets", "relax_sweep_ceiling",
                "trace_offset_s", "trace_seconds"):
        assert traffic[key] == bench_cells.load(
            "benchmark/traffic/route_scale.json")[key], key


def test_the_builder_yields_s38417s_flip_flops(cell):
    from parallel_eda_tpu.netlist.netlist import PRIM_FF, PRIM_LUT

    builder = harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))
    c = cell.config["circuit"]

    def flip_flops(ff_ratio):
        nl, _ = builder.levelled_circuit(
            num_luts=c["num_luts"], num_inputs=c["num_inputs"],
            num_outputs=c["num_outputs"], K=6, ff_ratio=ff_ratio,
            locality=c["locality"], max_lut_levels=c["max_lut_levels"],
            seed=c["generator_seed"])
        assert sum(p.kind == PRIM_LUT for p in nl.primitives) == 6406
        return sum(p.kind == PRIM_FF for p in nl.primitives)

    assert flip_flops(c["ff_ratio"]) == 1463 == (
        cell.config["as_built"]["flip_flops"])
    # the ratio ISSUE 44 gives lies a hair under the 1,463rd draw
    assert flip_flops(0.218483) == 1462


def test_problem_as_built(cell, built):
    """The stated grid, nets, graph, timing depth and fingerprint, at
    full size (a build, no route), ONE fanout class, and the first
    window's dispatch by the driver's own bucketing: the 16 x 16 rung
    and the full canvas both populated, the 8 x 8 rung empty, four nets
    in five on the canvas."""
    from parallel_eda_tpu.route.router import (_crop_ladder,
                                               _size_class_buckets)
    from parallel_eda_tpu.timing.graph import build_timing_graph

    f, as_built = built, cell.config["as_built"]
    assert [f.grid.nx, f.grid.ny] == as_built["grid"] == [26, 26]
    assert f.term.num_nets == as_built["routed_nets"] > 3000
    assert f.rr.num_nodes == as_built["rr_nodes"]
    assert len(f.rr.in_src) == as_built["rr_edges"]
    assert f.rr.chan_width == as_built["chan_width"] == 88
    assert f.rr.unidir and f.rr.group_tracks == 8
    assert problem.fingerprint(f) == cell.traffic["problem_sha256"]
    assert f.term.sinks.shape[1] == as_built["max_sinks"] <= 16
    assert len(f.term.fanout_classes) == 1
    kinds = [b.type_name for b in f.pnl.blocks]
    assert kinds.count("clb") == as_built["clusters"]
    assert kinds.count("io") == as_built["io_pads_used"] == 28 + 1 + 106
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg.num_tnodes == as_built["timing_nodes"]
    assert tg.depth == as_built["timing_depth"] == (
        cell.config["circuit"]["max_lut_levels"] + 2)

    t = f.term
    assert _crop_ladder(f.grid.nx, f.grid.ny) == [(8, 8), (16, 16)]
    classes, assign = _size_class_buckets(
        t.bb_xmax - t.bb_xmin + 1 + 2 * 4, t.bb_ymax - t.bb_ymin + 1 + 2 * 4,
        f.grid.nx, f.grid.ny, min_count=8)
    assert classes == [(16, 16)]
    on_rung, on_canvas = np.bincount(assign).tolist()
    assert on_rung == as_built["first_window_nets_on_16x16"]
    assert on_canvas == as_built["first_window_nets_on_full_canvas"]
    assert on_rung + on_canvas == as_built["routed_nets"]
    assert on_canvas > 4 * on_rung > 64 * 4


# ----------------------------------------------- reader and manifest


def _reader(name):
    return harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))


def _route(swept, net_routes):
    rows = [types.SimpleNamespace(net_routes=n) for n in net_routes]
    if swept is None:
        return types.SimpleNamespace(stats=rows)       # the parent's
    return types.SimpleNamespace(stats=rows, total_cell_sweeps=swept)


@pytest.mark.parametrize("ctx, want", [
    ({"routes": [_route(21_000_000_000, [6000, 2000, 698]),
                 _route(1, [1])]}, 21_000_000_000 / 8698),
    ({"routes": [_route(4_279_296, [1])]}, 4_279_296.0),
    ({"routes": [_route(None, [6000, 2000])]}, None),  # the parent program
    ({"routes": [_route(0, [5])]}, None),       # the ELL program: no canvas
    ({"routes": [_route(7, [0, 0])]}, None),    # a route that routed no net
    ({"routes": [_route(7, [])]}, None),
    ({"routes": []}, None),
    ({}, None),
])
def test_cell_sweeps_per_net_route_reader(ctx, want):
    assert _reader(METRIC).read(ctx) == want


def _rows(*rows):
    """``(seconds, guarded)`` rows of a window ledger; guarded None: a
    row from before the field."""
    out = []
    for seconds, guarded in rows:
        row = types.SimpleNamespace(kind="negotiate", route_time_s=seconds)
        if guarded is not None:
            row.scan_guard = guarded
        out.append(row)
    return types.SimpleNamespace(stats=out)


@pytest.mark.parametrize("ctx, want", [
    # the first timed route's guarded windows, in seconds
    ({"routes": [_rows((11.0, False), (4.0, False), (0.25, True),
                       (0.5, True)), _rows((9.0, True))]}, 0.75),
    ({"routes": [_rows((11.0, False), (4.0, False))]}, 0),   # never met it
    ({"routes": [_rows((11.0, None), (4.0, None))]}, None),  # the parent's
    ({"routes": [types.SimpleNamespace(stats=[types.SimpleNamespace(
        kind="", route_time_s=1.0, scan_guard=True)])]}, None),  # no ledger
    ({"routes": [_rows()]}, None),
    ({"routes": []}, None),
    ({}, None),
])
def test_scan_guard_s_reader(ctx, want):
    assert _reader(GUARD_METRIC).read(ctx) == want


def test_the_manifest_lists_the_cell_and_its_metric():
    """One-way checks only: a later cell or metric appended to these
    lists needs no edit of this file."""
    manifest = harness.load_manifest(REPO)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    m = by_name[METRIC]
    assert set(EIGHT_CELLS) <= set(m["workloads"])
    assert (m["layer"], m["moves"], m["unit"], m["better"],
            m["source"]) == ("window program", "route_s", "count", "lower",
                             "program_counter")
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(m["workloads"]) <= cells
    g = by_name[GUARD_METRIC]
    assert set(EIGHT_CELLS) <= set(g["workloads"]) <= cells
    assert (g["layer"], g["moves"], g["unit"], g["better"],
            g["source"]) == ("negotiation driver", "route_s", "s", "lower",
                             "program_span")
    # the cell reports every per-layer metric route_scale does bar two,
    # `window.cropped_sweep_share` and `negotiation.full_canvas_net_share`,
    # which it runs the layer of and test_scale_cell.py:183 holds EQUAL
    # to four cells: PERF.md §7 (34) has the edit a `benchmark` PR owes
    missing = [e["name"] for e in manifest["per_layer"]
               if "route_scale" in e["workloads"]
               and NAME not in e["workloads"]]
    assert len(missing) <= 2 and all("crop" in n or "full_canvas" in n
                                     for n in missing), missing
    w = next(w for w in manifest["workloads"] if w["name"] == NAME)
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert (w["config"], w["traffic"]) == ("mcnc_s38417_like_k6n10_l4",
                                           NAME)
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == [] and entry["file"] == CONFIG
    assert entry["source"] == bench_cells.load(CONFIG)["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    names = {e["name"] for e in manifest["end_to_end"]}
    assert {e["name"] for e in harness.metrics_of(
        manifest, "end_to_end", NAME)} == names >= {"route_s", "setup_s"}


# -------------------------------------------- the real files, test size


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, cell):
    """The real configuration, builder and traffic files at 100 LUTs,
    8 + 8 pads, W = 48, B = 32, under a manifest of their own."""
    root = str(tmp_path_factory.mktemp("scale_6k_cell"))
    name = bench_cells.write_cell(root, "route")
    cfg = json.loads(json.dumps(cell.config))
    cfg["circuit"].update(num_luts=100, num_inputs=8, num_outputs=8)
    cfg["router"]["opts"]["batch_size"] = 32
    traffic = bench_cells.load(TRAFFIC)
    traffic.update(chan_width=48, relax_sample_nets=3, trace_offset_s=0,
                   trace_seconds=0.5)
    cells = os.path.join(root, "cells")

    def dump():
        for rel, obj in (("configs/tiny_k4n4.json", cfg),
                         ("traffic/tiny_w12.json", traffic)):
            with open(os.path.join(cells, rel), "w") as fh:
                json.dump(obj, fh)
    dump()
    tiny = harness.load_cell(harness.load_manifest(root), root, name)
    f = _build(tiny, 48)
    assert f.rr.unidir and len(f.term.fanout_classes) == 1
    traffic["problem_sha256"] = problem.fingerprint(f)
    dump()
    return root, name, f


def _failed_checks(out):
    return [ln.split(":")[0][len("check "):] for ln in out.splitlines()
            if ln.startswith("check ") and ln.endswith("NOT ok")]


def test_tiny_cell_is_correct_and_counts_its_cell_sweeps(tiny_cell, tmp_path,
                                                         capsys):
    """``route_loop`` on the real files at test size: every check sound,
    and the new metric read as a count: between one sweep of the whole
    canvas at the narrowest plan a net route and the route's every
    sweep at the full batch."""
    from benchmark.bytes_model import plane_cells

    root, name, f = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(root, name, seed=2**31 + 44,
                                  seconds=1.0, trace=True,
                                  work_dir=str(tmp_path))
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True, _failed_checks(
        capsys.readouterr().out)
    assert result["attempted"] >= 1 and result["failed"] == 0
    counts = result["rehearsal"]["counts"]
    assert counts["window.sweeps"] >= counts["negotiation.iterations"] >= 1
    canvas = plane_cells(48, f.grid.nx, f.grid.ny)
    assert (8 * canvas / 32 < counts[METRIC]
            < counts["window.sweeps"] * 32 * canvas)


def test_tiny_control_bf16_is_not_correct(tiny_cell, tmp_path, capsys):
    root, name, _ = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(
            root, name, seed=2**31 + 44, seconds=1.0, trace=False,
            work_dir=str(tmp_path),
            router_overrides={"plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    failed = _failed_checks(capsys.readouterr().out)
    assert "sink_delay_gap" in failed or "relax_gap" in failed
