"""The cell ``route_dsp`` (VTR ``raygentop``'s counts on
k6_frac_N10_mem32K_40nm with the multipliers COMBINATIONAL): its files
as the manifest names them, the problem they build at full size and
both plain references on it, ``route_hetero``'s timing graph held to
the parent's, the driver ``route_loop_sta`` through ``harness.run_cell``
at test size (sound, with the multipliers registered, in bfloat16), the
reader it brought."""

import ast
import json
import os
import warnings

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem, reference_timing

REPO = bench_cells.REPO
CONFIG = "benchmark/configs/vtr_raygentop_like_k6frac_n10_mem32k.json"
TRAFFIC = "benchmark/traffic/route_dsp.json"
HETERO = "benchmark/configs/vtr_or1200_like_k6frac_n10_mem32k.json"
SIX_CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
             "route_scale", "route_hetero", "route_fanout"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_manifest(REPO), REPO,
                             "route_dsp")


@pytest.fixture(scope="module")
def built(cell):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        return problem.build_placed(cell, int(cell.traffic["chan_width"]))


def _builder(cell):
    return harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))


def test_configuration_carries_the_published_block(cell):
    cfg, sib = cell.config, bench_cells.load(HETERO)
    assert cfg["name"] == "vtr_raygentop_like_k6frac_n10_mem32k"
    assert cfg["kind"] == "placed_route"
    assert cfg["problem"] == "synth_placed_dsp"
    assert cfg["reduced"] == {} and len(cfg["source"]) <= 200
    for word in ("k6_frac_N10_mem32K_40nm.xml", "raygentop", "2,134",
                 "18 multipliers", "1 memory"):
        assert word in cfg["source"], word
    pub, old = cfg["published"], sib["published"]
    # the or1200 file's block, but for the multiplier's clock, the two
    # hard blocks' timing and the circuit
    for key in old:
        if key not in ("blocks", "circuit"):
            assert pub[key] == old[key], key
    for name, spec in old["blocks"].items():
        mine = dict(pub["blocks"][name])
        mine.pop("timing", None)
        want = {k: v for k, v in spec.items() if k != "assumed_clocks"}
        assert mine == want, name
    mult, mem = pub["blocks"]["mult_36"], pub["blocks"]["memory"]
    assert "assumed_clocks" not in mult and "clocks" not in mult
    assert mult["timing"] == {
        "kind": "combinational", "default": 1.93e-9,
        "delay_constant": {"mult_9x9": 1.523e-9, "mult_18x18": 1.523e-9,
                           "mult_36x36": 1.93e-9}}
    assert mem["timing"] == {"kind": "registered", "T_setup": 509e-12,
                             "T_clk_to_q": 1.234e-9}
    assert pub["circuit"] == {
        "name": "raygentop", "luts": 2134, "flip_flops": 1423,
        "inputs": 239, "outputs": 305, "multipliers": 18, "memories": 1}
    assert cfg["router"] == sib["router"]
    # the siblings' placer; its seed is this configuration's own
    # (`placement_note`: the first of six that the chip routes legally)
    assert cfg["placement"] == {"placer": "run_place_native",
                                "args": {"seed": 8, "inner_num": 1.0}}
    assert sib["placement"]["args"] == {"seed": 7, "inner_num": 1.0}
    assert cfg["arch"] == {"builder": "k6_frac_n10_mem32k_40nm_arch",
                           "args": {"mult_combinational": True}}
    starts = [a.split(":")[0] for a in cfg["assumed"]]
    for topic in ("the netlist's connectivity",
                  "the flip-flop and I/O counts",
                  "every multiply taken as 18x18 on one mult_36 block",
                  "the memory mode",
                  "logic depth and the multiplier's levels",
                  "block timing", "the pack", "the switch block", "pins",
                  "the placer", "hard-block traffic"):
        assert topic in starts, topic
    t = cell.traffic
    assert t["driver"] == "route_loop_sta"
    assert t["limits"] == {"wirelength_x": 1.10, "sink_delay_gap": 1e-5,
                           "relax_gap": 1e-5, "crit_path_gap": 1e-5}
    assert t["chan_width"] == cfg["as_built"]["chan_width"] == 64
    assert "W_min 48, 1.3 x 48 = 62.4, so 64" in t["chan_width_why"]
    assert (t["trace_offset_s"], t["trace_seconds"]) == (8, 3)
    opts = cfg["router"]["opts"]
    assert (opts["batch_size"], opts["max_router_iterations"],
            opts["initial_pres_fac"], opts["pres_fac_mult"],
            opts["acc_fac"], opts["bb_factor"]) == (64, 50, 0.5, 1.3,
                                                    1.0, 3)


def test_the_architecture_states_the_blocks_timing_kind(cell):
    """The published multiplier: no clock pin, combinational, the
    pin-to-pin delay by mode; the memory registered; and what the
    reference reads from the configuration is what the program's
    architecture carries."""
    from parallel_eda_tpu.arch import builtin

    a = cell.config["arch"]
    arch = getattr(builtin, a["builder"])(chan_width=64, **a["args"])
    pub = cell.config["published"]["blocks"]
    mult, mem = arch.block_type("mult_36"), arch.block_type("memory")
    assert mult.combinational and not mem.combinational
    assert not any(c.is_clock for c in mult.pin_classes)
    assert mult.num_pins == 36 + 36 + 72
    assert (mult.num_input_pins, mult.num_output_pins) == (72, 72)
    assert mem.num_pins == 96 + 64 + 1
    assert mult.mode_T_comb == pub["mult_36"]["timing"]["delay_constant"]
    assert mult.comb_delay(None) == pub["mult_36"]["timing"]["default"]
    assert mult.comb_delay("mult_18x18") == 1.523e-9
    assert (mem.T_setup, mem.T_clk_to_q) == (
        pub["memory"]["timing"]["T_setup"],
        pub["memory"]["timing"]["T_clk_to_q"])
    timing = reference_timing.block_timing(cell.config)
    clb = arch.block_type("clb")
    assert timing["blocks"]["clb"] == {
        "kind": "cluster", "T_comb": clb.T_comb, "T_setup": clb.T_setup,
        "T_clk_to_q": clb.T_clk_to_q}
    from parallel_eda_tpu.timing.graph import T_LOCAL
    assert timing["t_local"] == T_LOCAL
    # the default builds route_hetero's registered stand-in, as before
    old = builtin.k6_frac_n10_mem32k_40nm_arch(chan_width=64)
    assert not old.block_type("mult_36").combinational
    assert old.block_type("mult_36").num_pins == 145


def test_the_builder_yields_raygentops_counts(cell):
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_FF, PRIM_HARD, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD)

    c = cell.config["circuit"]
    b = _builder(cell)
    nl, level = b.dsp_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=6, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"], hard_blocks=c["hard_blocks"])
    kinds = [p.kind for p in nl.primitives]
    assert kinds.count(PRIM_LUT) == 2134
    assert kinds.count(PRIM_FF) == 1423
    assert kinds.count(PRIM_INPAD) == 239 + 1       # and the clock
    assert kinds.count(PRIM_OUTPAD) == 305
    hard = [p for p in nl.primitives if p.kind == PRIM_HARD]
    mults = [p for p in hard if p.model == "multiply"]
    (ram,) = [p for p in hard if p.model == "dual_port_ram"]
    assert len(mults) == 18
    used = lambda names: sum(n is not None for n in names)
    by_name = {p.name: p for p in mults}
    wired = 0
    for h in c["hard_blocks"]:
        if h["model"] != "multiply":
            continue
        p = by_name[h["name"]]
        assert p.clock is None and p.mode == "mult_18x18"
        assert (len(p.inputs), used(p.inputs)) == (72, 36)
        assert (len(p.outputs), used(p.outputs)) == (72, 36)
        assert p.inputs[18:36] == [None] * 18       # a[18:36]
        ins = [n for n in p.inputs if n is not None]
        assert len(set(ins)) == 36                  # a pin a signal
        assert {level[o] for o in p.outputs if o is not None} == {
            3 + max(level[n] for n in ins)}
        src = h.get("operands_from", {}).get("a")
        if src:
            wired += 1
            assert p.inputs[:18] == by_name[src].outputs[:18]
    assert wired == 3
    assert ram.clock == "clk" and used(ram.inputs) == 86
    assert all(level[o] == 0 for o in ram.outputs if o is not None)
    assert max(level.values()) == c["max_lut_levels"] == 10
    assert b.multipliers_in_series(nl) >= 2
    # the multipliers draw their operands from registers AND from LUTs
    drivers = [nl.primitives[nl.net_driver[n]].kind
               for p in mults for n in p.inputs if n is not None]
    assert drivers.count(PRIM_FF) > 200 and drivers.count(PRIM_LUT) > 50


@pytest.mark.parametrize("seed, ff_ratio", [(1, 0.2245), (7, 0.35)])
def test_over_registered_blocks_the_draw_is_the_siblings(cell, seed,
                                                         ff_ratio):
    """Without a combinational block ``dsp_circuit`` gives
    ``hetero_circuit``'s netlist, primitive for primitive (or1200's own
    three blocks at a tenth of its size): a change to the sibling's
    draw cannot pass this cell by."""
    sibling = harness.load_module(cell.find(
        "problems", "synth_placed_hetero", ".py"))
    blocks = [dict(h, at_lut=h["at_lut"] // 10) for h in
              bench_cells.load(HETERO)["circuit"]["hard_blocks"]]
    kw = dict(num_luts=300, num_inputs=40, num_outputs=30, K=6,
              ff_ratio=ff_ratio, locality=40, max_lut_levels=24, seed=seed,
              hard_blocks=blocks)
    nl, level = _builder(cell).dsp_circuit(**kw)
    want_nl, want_level = sibling.hetero_circuit(**kw)
    assert nl.primitives == want_nl.primitives
    assert level == want_level


def test_problem_as_built(cell, built):
    """The stated grid, blocks, nets, graph, timing graph and
    fingerprint at full size; the multiplier columns FULL."""
    from parallel_eda_tpu.timing.graph import build_timing_graph

    f, ab = built, cell.config["as_built"]
    assert [f.grid.nx, f.grid.ny] == ab["grid"] == [24, 24]
    assert f.term.num_nets == ab["routed_nets"] == 2428
    assert f.rr.num_nodes == ab["rr_nodes"]
    assert len(f.rr.in_src) == ab["rr_edges"]
    assert f.rr.chan_width == 64 and f.rr.unidir
    assert problem.fingerprint(f) == cell.traffic["problem_sha256"]
    by_type = {}
    for b, xyz in zip(f.pnl.blocks, f.pos.tolist()):
        by_type.setdefault(b.type_name, {})[b.name] = xyz[:2]
    assert len(by_type["clb"]) == ab["clusters"] == 214
    assert len(by_type["io"]) == ab["io_pads_used"] == 239 + 305 + 1
    assert len(by_type["mult_36"]) == 18 == ab["hard_sites"]["mult_36"]
    assert len(by_type["memory"]) == 1
    assert len(f.grid.clb_sites()) == ab["cluster_sites"] == 432
    for name, cols in ab["hard_columns"].items():
        for x, y in by_type[name].values():
            assert x in cols and y in f.grid.anchor_rows(name)
    assert sorted(map(tuple, by_type["mult_36"].values())) == sorted(
        (x, y) for x in ab["hard_columns"]["mult_36"]
        for y in f.grid.anchor_rows("mult_36"))
    assert f.term.sinks.shape[1] == ab["max_sinks"]
    assert int(f.term.hard.sum()) == ab["nets_hard"] == 1005
    assert int(f.term.num_sinks.sum()) == ab["sinks"]
    assert int(f.term.num_sinks[f.term.hard].sum()) == ab[
        "sinks_on_hard_nets"]
    assert len(f.term.fanout_classes) == 1
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg.num_tnodes == ab["timing_nodes"]
    assert tg.depth == ab["timing_depth"] == (
        cell.config["circuit"]["max_lut_levels"] + 2)
    # the in-edge table keeps the LUT's width; the 18 junctions' other
    # 30 in-edges each lie in the flat list
    assert tg.in_src.shape[1] == ab["timing_in_width"] == 6
    assert ab["timing_widest_in_degree"] == 36
    assert len(tg.in_overflow[0]) == ab["timing_in_overflow"] == 18 * 30
    assert tg.in_edges_wide == ab["timing_in_edges_wide"] == 18 * 36
    assert tg.num_in_edges == ab["timing_in_edges"]
    assert tg.out_dst.shape[1] == ab["timing_out_width"]
    assert len(tg.comb_junction) == 18


def test_both_references_pass_the_built_problem(cell, built):
    b = _builder(cell)
    sib = harness.load_module(cell.find(
        "problems", "synth_placed_hetero", ".py"))
    assert b.netlist_problems(cell.config, built) == []
    assert sib.device_problems(cell.config, built) == []
    # and refuse: a flip-flop count off by one, a clock pin the
    # published multiplier has not
    cfg = json.loads(json.dumps(cell.config))
    cfg["published"]["circuit"]["flip_flops"] += 1
    assert any(p.startswith("flip_flops: built 1423")
               for p in b.netlist_problems(cfg, built))
    cfg = json.loads(json.dumps(cell.config))
    cfg["published"]["blocks"]["mult_36"]["assumed_clocks"] = 1
    assert any(p.startswith("pins a row")
               for p in sib.device_problems(cfg, built))


def test_route_heteros_timing_graph_is_the_parents():
    """``route_hetero``'s full-size ``TimingGraph``, array for array,
    hashes to what commit 19cf3fd built (taken there with the same
    function): the cell's multiplier stays registered and its fused STA
    keeps its programs."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_timing_comb_hard import tg_sha256
    from parallel_eda_tpu.timing.graph import build_timing_graph

    hetero = harness.load_cell(harness.load_manifest(REPO), REPO,
                               "route_hetero")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = problem.build_placed(hetero, 64)
    assert problem.fingerprint(f) == hetero.traffic["problem_sha256"]
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg_sha256(tg) == ("f9c868d1862a1b930df8984ec324fb82"
                             "fa059ff32e5db180ce7f0d76c689a5bf")
    assert tg.in_overflow is None and tg.comb_junction is None
    assert tg.in_edges_wide == 0


def test_the_reference_imports_nothing_of_the_programs_timing():
    with open(os.path.join(REPO, "benchmark", "reference_timing.py")) as fh:
        tree = ast.parse(fh.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    assert mods == {"__future__", "math", "typing"}


# ------------------------------------------------- the cell, test size


def _tiny_config(config, registered=False):
    """The real configuration at 60 LUTs, 8 + 8 pads, two multipliers
    in series on 8 + 8 of their operand pins and the memory on 3 + 3 +
    8 + 2 of its pins: an 8 x 8 device, one memory column and one
    multiplier column.  ``registered``: the control, the program's
    architecture with the multiplier REGISTERED (the semantics of the
    parent commit) under the same published timing."""
    cfg = json.loads(json.dumps(config))
    c = cfg["circuit"]
    c.update(num_luts=60, num_inputs=10, num_outputs=8, ff_ratio=0.3,
             locality=20)
    mult = {"model": "multiply", "mode": "mult_18x18", "levels": 3,
            "inputs": [["a", 36, 8], ["b", 36, 8]],
            "outputs": [["out", 72, 16]]}
    c["hard_blocks"] = [
        dict(mult, name="m0", at_lut=15),
        {"name": "ram0", "model": "dual_port_ram", "at_lut": 25,
         "inputs": [["addr1", 15, 3], ["addr2", 15, 3], ["data", 64, 8],
                    ["we1", 1, 1], ["we2", 1, 1]],
         "outputs": [["out", 64, 8]]},
        dict(mult, name="m1", at_lut=35, operands_from={"a": "m0"})]
    # the stand-in's flip-flop count follows its seed, not a table
    nl, _ = harness.load_module(os.path.join(
        REPO, "benchmark", "problems", "synth_placed_dsp.py")).dsp_circuit(
        num_luts=60, num_inputs=10, num_outputs=8, K=6, ff_ratio=0.3,
        locality=20, max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"], hard_blocks=c["hard_blocks"])
    cfg["published"]["circuit"].update(
        luts=60, flip_flops=nl.num_ffs, inputs=10, outputs=8,
        multipliers=2)
    cfg["router"]["opts"]["batch_size"] = 32
    if registered:
        cfg["arch"]["args"] = {}
        cfg["published"]["blocks"]["mult_36"]["assumed_clocks"] = 1
    return cfg


def _tiny_cell(kind, tmp_path_factory, cell):
    """The real configuration, builder, driver and traffic files at
    test size, W = 48, under a manifest of their own."""
    root = str(tmp_path_factory.mktemp("dsp_cell_" + kind))
    name = bench_cells.write_cell(root, "route")
    cfg = _tiny_config(cell.config, kind == "registered")
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
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = problem.build_placed(tiny, 48)
    assert [f.grid.nx, f.grid.ny] == [8, 8] and f.rr.unidir
    assert sorted(b.type_name for b in f.pnl.blocks
                  if b.type_name not in ("io", "clb")) == [
        "memory", "mult_36", "mult_36"]
    traffic["problem_sha256"] = problem.fingerprint(f)
    dump()
    return kind, root, name


@pytest.fixture(scope="module")
def tiny_sound(tmp_path_factory, cell):
    return _tiny_cell("sound", tmp_path_factory, cell)


@pytest.fixture(scope="module")
def tiny_registered(tmp_path_factory, cell):
    return _tiny_cell("registered", tmp_path_factory, cell)


def _checks(out):
    return {ln.split(":")[0][len("check "):]: ln.endswith("-> ok")
            for ln in out.splitlines() if ln.startswith("check ")}


@pytest.mark.parametrize("which", ["tiny_sound", "tiny_registered"])
def test_tiny_dsp_cell_through_the_new_driver(which, request, tmp_path,
                                              capsys):
    """``harness.run_cell`` finds ``route_loop_sta`` by the name in the
    traffic file: ``route_loop``'s eleven checks and the three new ones
    are printed beside their limits.  Sound: ``correct``, the gauges
    read as the new metric, the hard nets counted.  With the
    multipliers REGISTERED in the program (the parent's semantics)
    under the published timing: NOT ``correct``, by ``crit_path_gap``
    alone."""
    kind, root, name = request.getfixturevalue(which)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(root, name, seed=2**31 + 40,
                                  seconds=1.0, trace=True,
                                  work_dir=str(tmp_path))
    bench_cells.assert_cpu_result(result)
    out = capsys.readouterr().out
    checks = _checks(out)
    assert len(checks) == 14
    assert list(checks)[-3:] == ["crit_path_gap", "crit_path_ref_spread",
                                 "crit_path_crosses_multiplier"]
    failed = [k for k, ok in checks.items() if not ok]
    gap = float(next(ln for ln in out.splitlines() if ln.startswith(
        "check crit_path_gap")).split(": ")[1].split(" against")[0])
    if kind == "sound":
        assert result["correct"] is True, failed
        assert gap < 1e-5
        assert result["attempted"] >= 1 and result["failed"] == 0
        withheld = result["rehearsal"]["withheld"]
        assert "window.sta_wide_in_edge_share" in withheld
        assert "negotiation.hard_net_dispatch_share" in withheld
    else:
        assert result["correct"] is False
        assert failed == ["crit_path_gap"] and gap > 1e-2


def test_tiny_dsp_control_bf16_is_not_correct(tiny_sound, tmp_path, capsys):
    _, root, name = tiny_sound
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(
            root, name, seed=2**31 + 40, seconds=1.0, trace=False,
            work_dir=str(tmp_path),
            router_overrides={"plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    checks = _checks(capsys.readouterr().out)
    assert not (checks["sink_delay_gap"] and checks["relax_gap"])


# ------------------------------------------------ reader and manifest


def _reader(name):
    return harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))


@pytest.mark.parametrize("registry, want", [
    ({"route.timing.in_edges_wide": 648.0,
      "route.timing.in_edges": 12322.0}, 100.0 * 648 / 12322),
    ({"route.timing.in_edges_wide": 0.0,
      "route.timing.in_edges": 11804.0}, 0.0),   # no combinational block
    ({"route.timing.in_edges": 11804.0}, None),
    ({"route.timing.in_edges_wide": 3.0}, None),
    ({"route.crop.net_dispatches_full_total": 5}, None),    # the parent
    (None, None),
])
def test_sta_wide_in_edge_share_reader(registry, want):
    ctx = {} if registry is None else {"registry": registry}
    assert _reader("window.sta_wide_in_edge_share").read(ctx) == want


def test_the_manifest_lists_the_cell_and_its_metric():
    """One-way checks only: a later cell or metric appended to these
    lists needs no edit of this file."""
    manifest = harness.load_manifest(REPO)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    m = by_name["window.sta_wide_in_edge_share"]
    assert set(SIX_CELLS + ["route_dsp"]) <= set(m["workloads"])
    assert (m["layer"], m["moves"], m["unit"], m["better"],
            m["source"]) == ("window program", "route_s", "%", "lower",
                             "program_counter")
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(m["workloads"]) <= cells
    # the cell reports every per-layer metric route_hetero does
    for e in manifest["per_layer"]:
        if "route_hetero" in e["workloads"]:
            assert "route_dsp" in e["workloads"], e["name"]
    w = next(w for w in manifest["workloads"] if w["name"] == "route_dsp")
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert (w["config"], w["traffic"]) == (
        "vtr_raygentop_like_k6frac_n10_mem32k", "route_dsp")
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == [] and entry["file"] == CONFIG
    assert len(entry["source"]) <= 200
    assert entry["source"] == bench_cells.load(CONFIG)["source"]
    assert bench_cells.load(CONFIG)["reduced"] == {}
    names = {e["name"] for e in manifest["end_to_end"]}
    assert {m["name"] for m in harness.metrics_of(
        manifest, "end_to_end", "route_dsp")} == names >= {
        "route_s", "setup_s"}
