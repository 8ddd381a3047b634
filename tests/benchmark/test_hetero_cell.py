"""The cell ``route_hetero`` (VTR ``or1200``'s counts on
k6_frac_N10_mem32K_40nm): its files as the manifest names them, the
problem they build at full size, ``reference_device`` on it and on the
three controls it has to refuse, the two readers it brought, and the
real files at test size through ``harness.run_cell`` on the CPU, sound
and in bfloat16."""

import json
import os
import types
import warnings

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem, reference_device

REPO = bench_cells.REPO
CONFIG = "benchmark/configs/vtr_or1200_like_k6frac_n10_mem32k.json"
TRAFFIC = "benchmark/traffic/route_hetero.json"
SIBLING = "benchmark/configs/mcnc_elliptic_like_k6n10_l4.json"
CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
         "route_scale", "route_hetero"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_manifest(REPO), REPO,
                             "route_hetero")


@pytest.fixture(scope="module")
def built(cell):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        return problem.build_placed(cell, int(cell.traffic["chan_width"]))


def _builder(cell):
    return harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))


def test_configuration_carries_the_published_block(cell):
    cfg, sib = cell.config, bench_cells.load(SIBLING)
    assert cfg["name"] == "vtr_or1200_like_k6frac_n10_mem32k"
    assert cfg["kind"] == "placed_route"
    assert cfg["problem"] == "synth_placed_hetero"
    assert cfg["reduced"] == {} and len(cfg["source"]) <= 200
    pub = cfg["published"]
    # the routing half is the siblings', number for number
    for key in ("K", "N", "io_capacity", "segment", "mux", "ipin_cblock",
                "Fc_in", "Fc_out", "switch_block",
                "clb_inputs_equivalent", "clb_outputs_equivalent"):
        assert pub[key] == sib["published"][key], key
    for key in ("placement", "router", "guarantees"):
        assert cfg[key] == sib[key], key
    assert (pub["K"], pub["N"], pub["I"], pub["O"]) == (6, 10, 40, 20)
    b = pub["blocks"]
    assert b["io"]["capacity"] == 8
    assert (b["clb"]["inputs"], b["clb"]["outputs"]) == ({"I": 40},
                                                         {"O": 20})
    assert b["mult_36"]["height"] == 4
    assert b["mult_36"]["inputs"] == {"a": 36, "b": 36}
    assert b["mult_36"]["outputs"] == {"out": 72}
    assert b["mult_36"]["columns"] == {"start": 4, "repeat": 8}
    assert b["memory"]["height"] == 6
    assert sum(b["memory"]["inputs"].values()) == 96
    assert b["memory"]["outputs"] == {"out": 64}
    assert b["memory"]["columns"] == {"start": 2, "repeat": 8}
    assert pub["circuit"] == {
        "name": "or1200", "luts": 3054, "flip_flops": 691, "inputs": 385,
        "outputs": 394, "multipliers": 1, "memories": 2,
        "memory_bits": 2048}
    c = cfg["circuit"]
    assert (c["num_luts"], c["num_inputs"], c["num_outputs"]) == (
        3054, 385, 394)
    assert [h["model"] for h in c["hard_blocks"]] == [
        "dual_port_ram", "multiply", "dual_port_ram"]
    starts = [a.split(":")[0] for a in cfg["assumed"]]
    for topic in ("the netlist's connectivity", "the switch block", "pins",
                  "block timing", "the pack", "the memory mode", "fanout",
                  "logic depth", "hard-block traffic"):
        assert topic in starts, topic
    # the traffic: the siblings' limits, the width the search ends at
    t = cell.traffic
    assert t["limits"] == bench_cells.load(
        "benchmark/traffic/route_scale.json")["limits"]
    assert t["driver"] == "route_loop"
    assert t["chan_width"] == cfg["as_built"]["chan_width"] == 64
    assert "W_min 48, 1.3 x 48 = 62.4, so 64" in t["chan_width_why"]


def test_the_architecture_is_built_from_the_published_numbers(cell):
    """``k6_frac_n10_mem32k_40nm_arch`` against the file's ``published``
    block: pin counts, heights, columns, Fc, the routing numbers."""
    from parallel_eda_tpu.arch import builtin

    pub = cell.config["published"]
    arch = getattr(builtin, cell.config["arch"]["builder"])(chan_width=64)
    assert (arch.K, arch.N, arch.I, arch.io_capacity) == (
        pub["K"], pub["N"], pub["I"], pub["io_capacity"])
    assert (arch.Fc_in, arch.Fc_out) == (pub["Fc_in"], pub["Fc_out"])
    cols = {c.type_name: {"start": c.start, "repeat": c.repeat}
            for c in arch.column_types}
    for name, spec in pub["blocks"].items():
        bt = arch.block_type(name)
        assert bt.height == spec.get("height", 1)
        assert bt.capacity == spec.get("capacity", 1)
        assert bt.num_input_pins == sum(spec["inputs"].values())
        assert bt.num_output_pins == sum(spec["outputs"].values())
        assert cols.get(name) == spec.get("columns")
    seg, mux = arch.segments[0], arch.switches[0]
    assert (seg.length, seg.directionality, seg.Rmetal, seg.Cmetal,
            list(seg.sb), list(seg.cb)) == (
        pub["segment"]["length"], pub["segment"]["type"],
        pub["segment"]["Rmetal"], pub["segment"]["Cmetal"],
        pub["segment"]["sb"], pub["segment"]["cb"])
    assert (mux.R, mux.Tdel, mux.Cin, mux.Cout) == (
        pub["mux"]["R"], pub["mux"]["Tdel"], pub["mux"]["Cin"],
        pub["mux"]["Cout"])
    assert arch.switches[arch.ipin_switch].Tdel == pub["ipin_cblock"]["T"]


def test_the_builder_yields_or1200s_counts(cell):
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_FF, PRIM_HARD, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD)

    c = cell.config["circuit"]
    nl, level = _builder(cell).hetero_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=6, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"], hard_blocks=c["hard_blocks"])
    kinds = [p.kind for p in nl.primitives]
    assert kinds.count(PRIM_LUT) == 3054
    assert kinds.count(PRIM_FF) == 691
    assert kinds.count(PRIM_INPAD) == 385 + 1       # and the clock
    assert kinds.count(PRIM_OUTPAD) == 394
    hard = [p for p in nl.primitives if p.kind == PRIM_HARD]
    assert [p.model for p in hard] == ["dual_port_ram", "multiply",
                                       "dual_port_ram"]
    used = lambda names: sum(n is not None for n in names)
    for p in hard:
        ins = [n for n in p.inputs if n is not None]
        assert len(set(ins)) == len(ins)            # a pin a signal
        assert p.clock == "clk"
        assert all(level[o] == 0 for o in p.outputs if o is not None)
        if p.model == "multiply":
            assert (len(p.inputs), used(p.inputs)) == (72, 64)
            assert (len(p.outputs), used(p.outputs)) == (72, 64)
            assert p.inputs[32:36] == [None] * 4
        else:
            assert (len(p.inputs), used(p.inputs)) == (96, 76)
            assert (len(p.outputs), used(p.outputs)) == (64, 64)
            assert p.inputs[5:15] == [None] * 10    # addr1[5:15]
            assert None not in p.inputs[30:96]      # data, we1, we2
    assert max(level.values()) == c["max_lut_levels"]


@pytest.mark.parametrize("seed, ff_ratio", [(1, 0.2266), (7, 0.35),
                                            (2**31 + 32, 0.5)])
def test_without_hard_blocks_the_draw_is_the_siblings(cell, seed,
                                                      ff_ratio):
    """``hetero_circuit`` repeats ``levelled_circuit``'s loop (the
    sibling builder was not this PR's to edit): with no hard block in
    the stream the two give the same netlist, primitive for primitive,
    so a change to the sibling's draw cannot pass this cell by."""
    sibling = harness.load_module(cell.find(
        "problems", "synth_placed_levelled", ".py"))
    kw = dict(num_luts=400, num_inputs=24, num_outputs=30, K=6,
              ff_ratio=ff_ratio, locality=40, max_lut_levels=24, seed=seed)
    nl, level = _builder(cell).hetero_circuit(hard_blocks=(), **kw)
    want_nl, want_level = sibling.levelled_circuit(**kw)
    assert nl.primitives == want_nl.primitives
    assert level == want_level
    assert (nl.net_driver, nl.net_sinks, nl.clocks) == (
        want_nl.net_driver, want_nl.net_sinks, want_nl.clocks)


def test_problem_as_built(cell, built):
    """The stated grid, blocks, nets, graph and fingerprint, at full
    size; the hard blocks on their columns and anchors; the first
    window's dispatch."""
    from parallel_eda_tpu.route.router import (_crop_ladder,
                                               _size_class_buckets)
    from parallel_eda_tpu.timing.graph import build_timing_graph

    f, ab = built, cell.config["as_built"]
    assert [f.grid.nx, f.grid.ny] == ab["grid"] == [25, 25]
    assert f.term.num_nets == ab["routed_nets"]
    assert f.rr.num_nodes == ab["rr_nodes"]
    assert len(f.rr.in_src) == ab["rr_edges"]
    assert f.rr.chan_width == 64 and f.rr.unidir
    assert problem.fingerprint(f) == cell.traffic["problem_sha256"]
    by_type = {}
    for b, xyz in zip(f.pnl.blocks, f.pos.tolist()):
        by_type.setdefault(b.type_name, {})[b.name] = xyz[:2]
    assert len(by_type["clb"]) == ab["clusters"] == 306
    assert len(by_type["io"]) == ab["io_pads_used"] == 385 + 394 + 1
    assert by_type["memory"] == ab["hard_blocks"]["memory"]
    assert by_type["mult_36"] == ab["hard_blocks"]["mult_36"]
    for name, sites in ab["hard_blocks"].items():
        for x, y in sites.values():
            assert x in ab["hard_columns"][name]
            assert y in f.grid.anchor_rows(name)
    assert len(f.grid.clb_sites()) == ab["cluster_sites"]
    assert f.term.sinks.shape[1] == ab["max_sinks"]
    assert int(f.term.hard.sum()) == ab["nets_hard"]
    assert int(f.term.num_sinks.sum()) == ab["sinks"]
    assert int(f.term.num_sinks[f.term.hard].sum()) == ab[
        "sinks_on_hard_nets"]
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg.num_tnodes == ab["timing_nodes"]
    assert tg.depth == ab["timing_depth"] == (
        cell.config["circuit"]["max_lut_levels"] + 2)
    # no timing node carries a hard block's whole bus
    assert tg.in_src.shape[1] <= 6

    t = f.term
    assert _crop_ladder(f.grid.nx, f.grid.ny) == [(8, 8), (16, 16)]
    classes, assign = _size_class_buckets(
        t.bb_xmax - t.bb_xmin + 1 + 2 * 4, t.bb_ymax - t.bb_ymin + 1 + 2 * 4,
        f.grid.nx, f.grid.ny, min_count=8)
    assert classes == [(16, 16)]
    on_rung, on_canvas = np.bincount(assign).tolist()
    assert on_rung == ab["first_window_nets_on_16x16"]
    assert on_canvas == ab["first_window_nets_on_full_canvas"]
    assert int(t.hard[assign == 1].sum()) == ab[
        "first_window_hard_nets_on_full_canvas"]


def _device_problems(cell, f, pos=None, rr=None):
    return reference_device.device_problems(
        cell.config["published"], f.grid.nx, f.grid.ny, f.rr.chan_width,
        rr if rr is not None else f.rr,
        [b.type_name for b in f.pnl.blocks],
        f.pos if pos is None else pos, _builder(cell).net_pins(f))


def _block(f, name):
    return next(i for i, b in enumerate(f.pnl.blocks) if b.name == name)


def test_reference_device_passes_the_built_problem(cell, built):
    assert _device_problems(cell, built) == []


@pytest.mark.parametrize("control", [
    "multiplier_on_a_cluster_column", "ram_off_its_rows",
    "ram_over_the_grids_edge", "two_blocks_on_one_site"])
def test_reference_device_refuses_an_illegal_placement(cell, built,
                                                       control):
    f = built
    pos = f.pos.copy()
    if control == "multiplier_on_a_cluster_column":
        pos[_block(f, "mult0"), 0] = 5
        want = "column 5 holds clb"
    elif control == "ram_off_its_rows":
        pos[_block(f, "rf_a"), 1] += 1
        want = "not anchored at a row 1 + k x 6"
    elif control == "ram_over_the_grids_edge":
        pos[_block(f, "rf_a"), 1] = 25
        want = "footprint leaves the grid"
    else:
        pos[_block(f, "rf_a")] = pos[_block(f, "rf_b")]
        want = "overlaps block"
    got = _device_problems(cell, f, pos=pos)
    assert any(want in p for p in got), got[:5]
    # the program's own audit refuses the same placements
    from parallel_eda_tpu.place.check import check_place
    with pytest.raises(ValueError):
        check_place(f.pnl, f.grid, pos)


@pytest.fixture(scope="module")
def tiny_built(cell):
    """The configuration at test size (``_tiny_config``), W = 16."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = _builder(cell).build(_tiny_config(cell.config), 16)
    assert _device_problems(cell, f) == []
    return f


def test_reference_device_counts_the_published_clock_pins(cell,
                                                          tiny_built):
    """The pins of a row are recounted from the PUBLISHED block: the
    published ``mult_36`` has no clock, the program's has one, and the
    configuration says so (``assumed_clocks``).  Take the statement
    away, or a clock from ``memory``, and the graph is refused."""
    f = tiny_built
    blocks = cell.config["published"]["blocks"]
    assert "clocks" not in blocks["mult_36"]
    assert blocks["mult_36"]["assumed_clocks"] == 1
    assert blocks["memory"]["clocks"] == blocks["clb"]["clocks"] == 1
    assert "clocks" not in blocks["io"]
    for name, drop in (("mult_36", "assumed_clocks"), ("memory", "clocks")):
        pub = json.loads(json.dumps(cell.config["published"]))
        del pub["blocks"][name][drop]
        got = reference_device.device_problems(
            pub, f.grid.nx, f.grid.ny, f.rr.chan_width, f.rr,
            [b.type_name for b in f.pnl.blocks], f.pos,
            _builder(cell).net_pins(f))
        col = next(int(x) for b, (x, _, _) in zip(f.pnl.blocks,
                                                  f.pos.tolist())
                   if b.type_name == name)
        assert any(p.startswith(f"pins a row: tile ({col},")
                   for p in got), (name, got[:3])


def test_reference_device_refuses_a_hard_input_class_of_two(cell,
                                                            tiny_built):
    """The configuration at test size on a graph whose ``memory`` type
    keeps two data pins in ONE class of capacity two (what
    ``make_hard_type`` built until this cell): bit 0 is then routable
    to the pin of bit 1, and the reference says so."""
    from parallel_eda_tpu.arch import builtin
    from parallel_eda_tpu.arch.model import PinClass
    from parallel_eda_tpu.rr.graph import build_rr_graph

    f = tiny_built
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        arch = builtin.k6_frac_n10_mem32k_40nm_arch(chan_width=16)
        mem = arch.block_type("memory")
        mem.pin_classes[30] = PinClass(mem.pin_classes[30].direction,
                                       [30, 31])
        mem.pin_classes[31] = PinClass(mem.pin_classes[31].direction, [])
        mem.pin_class_of[31] = 30
        rr = build_rr_graph(arch, f.grid, chan_width=16)
    got = _device_problems(cell, f, rr=rr)
    assert any("capacity 2, the pin's class holds 1" in p for p in got)
    # and a pin that hears fewer tracks than Fc gives
    ipin = next(iter(f.rr.ipin_of.values()))
    ptr = f.rr.in_row_ptr.copy()
    ptr[ipin + 1:] -= 1
    cut = types.SimpleNamespace(
        **{k: getattr(f.rr, k) for k in (
            "node_type", "xlow", "ylow", "xhigh", "yhigh", "ptc",
            "capacity")},
        in_src=np.delete(f.rr.in_src, int(f.rr.in_row_ptr[ipin])),
        in_row_ptr=ptr)
    assert any(p.startswith("Fc: pin node") for p in
               _device_problems(cell, f, rr=cut))


def _tiny_config(config):
    """The real configuration at 60 LUTs, 8 + 8 pads, one multiplier
    and one register file on 8 + 8 and 3 + 3 + 8 + 2 of their pins: a
    6 x 6 device, one memory column and one multiplier column."""
    cfg = json.loads(json.dumps(config))
    cfg["circuit"].update(num_luts=60, num_inputs=8, num_outputs=8)
    cfg["circuit"]["hard_blocks"] = [
        {"name": "rf_a", "model": "dual_port_ram", "at_lut": 20,
         "inputs": [["addr1", 15, 3], ["addr2", 15, 3], ["data", 64, 8],
                    ["we1", 1, 1], ["we2", 1, 1]],
         "outputs": [["out", 64, 8]]},
        {"name": "mult0", "model": "multiply", "at_lut": 40,
         "inputs": [["a", 36, 8], ["b", 36, 8]],
         "outputs": [["out", 72, 16]]}]
    cfg["router"]["opts"]["batch_size"] = 32
    return cfg


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, cell):
    """The real configuration, builder and traffic files at test size,
    W = 32, under a manifest of their own."""
    root = str(tmp_path_factory.mktemp("hetero_cell"))
    name = bench_cells.write_cell(root, "route")
    cfg = _tiny_config(cell.config)
    traffic = bench_cells.load(TRAFFIC)
    traffic.update(chan_width=32, relax_sample_nets=3, trace_offset_s=0,
                   trace_seconds=0.5)
    cells = os.path.join(root, "cells")

    def dump():
        for rel, obj in (("configs/tiny_k4n4.json", cfg),
                         ("traffic/tiny_w12.json", traffic)):
            with open(os.path.join(cells, rel), "w") as fh:
                json.dump(obj, fh)
    dump()
    tiny = harness.load_cell(harness.load_manifest(root), root, name)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = problem.build_placed(tiny, 32)
    assert [f.grid.nx, f.grid.ny] == [6, 6] and f.rr.unidir
    assert sorted(b.type_name for b in f.pnl.blocks
                  if b.type_name not in ("io", "clb")) == [
        "memory", "mult_36"]
    assert 0 < f.term.hard.sum() < f.term.num_nets
    traffic["problem_sha256"] = problem.fingerprint(f)
    dump()
    return root, name


def _failed_checks(out):
    return [ln.split(":")[0][len("check "):] for ln in out.splitlines()
            if ln.startswith("check ") and ln.endswith("NOT ok")]


def test_tiny_hetero_cell_is_correct(tiny_cell, tmp_path, capsys):
    """The device router against the serial router on a small
    heterogeneous placed problem with the published pin counts and L=4
    wires: both legal, wirelength within 1.10x, sink delays within 1e-5
    of the float64 sums, every route of the run the same; and the
    cell's per-layer counters read what the typed device gives."""
    root, name = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(root, name, seed=2**31 + 32,
                                  seconds=1.0, trace=True,
                                  work_dir=str(tmp_path))
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True, _failed_checks(
        capsys.readouterr().out)
    assert result["attempted"] >= 1 and result["failed"] == 0
    counts = result["rehearsal"]["counts"]
    assert counts["window.sweeps"] >= counts["negotiation.iterations"] >= 1
    withheld = result["rehearsal"]["withheld"]
    assert "negotiation.hard_net_dispatch_share" in withheld
    assert "window.sink_table_fill_share" in withheld


def test_tiny_hetero_control_bf16_is_not_correct(tiny_cell, tmp_path,
                                                 capsys):
    root, name = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(
            root, name, seed=2**31 + 32, seconds=1.0, trace=False,
            work_dir=str(tmp_path),
            router_overrides={"plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    failed = _failed_checks(capsys.readouterr().out)
    assert "sink_delay_gap" in failed or "relax_gap" in failed


def _reader(name):
    return harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))


def _reg(**kv):
    return {"registry": {
        {"hard": "route.hetero.net_dispatches_hard_total",
         "full": "route.crop.net_dispatches_full_total",
         "cropped": "route.crop.net_dispatches_cropped_total",
         "fill": "route.sink_pick.table_fill"}[k]: v
        for k, v in kv.items()}}


@pytest.mark.parametrize("ctx, want", [
    (_reg(hard=900, full=5000, cropped=1000), 15.0),
    (_reg(hard=0, full=5085, cropped=0), 0.0),      # identical clusters
    (_reg(hard=0, full=0, cropped=0), None),        # nothing dispatched
    (_reg(full=5085, cropped=10), None),    # the parent: no such counter
    (_reg(hard=3), None),
    ({}, None),
])
def test_hard_net_dispatch_share_reader(ctx, want):
    assert _reader("negotiation.hard_net_dispatch_share").read(ctx) == want


@pytest.mark.parametrize("ctx, want", [
    (_reg(fill=0.0625), 6.25),
    (_reg(fill=1.0), 100.0),
    (_reg(full=1), None),                   # the parent: no such gauge
    ({}, None),
])
def test_sink_table_fill_share_reader(ctx, want):
    assert _reader("window.sink_table_fill_share").read(ctx) == want


def test_the_manifest_lists_the_cell_and_its_metrics():
    """One-way checks only: a later cell appended to these lists, or the
    pending repair that lists this cell for the two metrics below, needs
    no edit of this file."""
    manifest = harness.load_manifest(REPO)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, better in (
            ("negotiation.hard_net_dispatch_share", "negotiation driver",
             "lower"),
            ("window.sink_table_fill_share", "window program", "higher")):
        m = by_name[name]
        assert set(CELLS) <= set(m["workloads"]) and m["layer"] == layer
        assert (m["moves"], m["unit"], m["better"], m["source"]) == (
            "route_s", "%", better, "program_counter")
    # every per-layer metric of the route cells lists the new cell; the
    # two whose lists an accepted test holds to four cells
    # (tests/benchmark/test_scale_cell.py, not this PR's to edit) may
    pinned = {"window.cropped_sweep_share",
              "negotiation.full_canvas_net_share"}
    for m in manifest["per_layer"]:
        if "route_scale" in m["workloads"] and m["name"] not in pinned:
            assert "route_hetero" in m["workloads"], m["name"]
    w = next(w for w in manifest["workloads"]
             if w["name"] == "route_hetero")
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert w["config"] == "vtr_or1200_like_k6frac_n10_mem32k"
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == [] and entry["file"] == CONFIG
