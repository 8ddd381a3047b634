"""The cell ``route_fanout`` (MCNC ``spla``'s counts on k6_N10_40nm,
sixteen input nets of fanout in the hundreds): its files as the manifest
names them, the problem they build at full size with its fanout classes,
``reference_netlist`` on it and on the builds it has to refuse, the
ladder of the five accepted configurations (one class of today's width,
their problems unmoved), the two readers it brought, and the real files
at test size through ``harness.run_cell`` on the CPU, sound and in
bfloat16."""

import json
import os
import types
import warnings

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem, reference_netlist

REPO = bench_cells.REPO
CONFIG = "benchmark/configs/mcnc_spla_like_k6n10_l4.json"
TRAFFIC = "benchmark/traffic/route_fanout.json"
SIBLING = "benchmark/configs/mcnc_elliptic_like_k6n10_l4.json"
CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
         "route_scale", "route_hetero", "route_fanout"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_manifest(REPO), REPO,
                             "route_fanout")


def _build(cell, width):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        return problem.build_placed(cell, width)


@pytest.fixture(scope="module")
def built(cell):
    return _build(cell, int(cell.traffic["chan_width"]))


def _builder(cell):
    return harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))


def test_configuration_carries_the_siblings_published_block(cell):
    cfg, sib = cell.config, bench_cells.load(SIBLING)
    assert cfg["name"] == "mcnc_spla_like_k6n10_l4"
    assert cfg["kind"] == "placed_route"
    assert cfg["problem"] == "synth_placed_fanout"
    assert cfg["reduced"] == {} and len(cfg["source"]) <= 200
    assert "spla" in cfg["source"] and "k6_N10_40nm.xml" in cfg["source"]
    for key in ("published", "arch", "placement", "router", "guarantees"):
        assert cfg[key] == sib[key], key
    c = cfg["circuit"]
    assert (c["num_luts"], c["num_inputs"], c["num_outputs"],
            c["ff_ratio"]) == (3690, 16, 46, 0.0)
    assert (c["pi_pin_share"], c["pi_share_band"],
            c["min_widest_sinks"]) == (0.25, 0.05, 100)
    assert (c["locality"], c["max_lut_levels"], c["generator_seed"]) == (
        sib["circuit"]["locality"], sib["circuit"]["max_lut_levels"], 1)
    # the sibling's assumptions, and the fanout's own
    assert cfg["assumed"][:3] == sib["assumed"][:3]
    starts = [a.split(":")[0] for a in cfg["assumed"]]
    for topic in ("the fanout of the primary inputs",
                  "the fanout of the LUT outputs",
                  "the K=6 draw at 4-LUT counts", "logic depth"):
        assert topic in starts, topic
    t = cell.traffic
    assert t["limits"] == bench_cells.load(
        "benchmark/traffic/route_scale.json")["limits"]
    assert t["driver"] == "route_loop"
    assert (t["trace_offset_s"], t["trace_seconds"]) == (8, 3)
    assert t["chan_width"] == cfg["as_built"]["chan_width"] == 56
    assert "W_min 40, 1.3 x 40 = 52, so 56" in t["chan_width_why"]
    assert cfg["router"]["opts"]["batch_size"] == 64
    assert cfg["router"]["opts"]["max_router_iterations"] == 50


def test_the_builder_yields_splas_counts(cell):
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_FF, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD)

    c = cell.config["circuit"]
    nl, level = _builder(cell).fanout_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=6,
        pi_pin_share=c["pi_pin_share"], locality=c["locality"],
        max_lut_levels=c["max_lut_levels"], seed=c["generator_seed"])
    kinds = [p.kind for p in nl.primitives]
    assert kinds.count(PRIM_LUT) == 3690
    assert kinds.count(PRIM_FF) == 0
    assert kinds.count(PRIM_INPAD) == 16            # and no clock pad
    assert kinds.count(PRIM_OUTPAD) == 46
    assert nl.clocks == []
    assert max(level.values()) == c["max_lut_levels"]
    fanin = [len(p.inputs) for p in nl.primitives if p.kind == PRIM_LUT]
    assert (min(fanin), max(fanin)) == (2, 6)
    assert all(len(set(p.inputs)) == len(p.inputs)
               for p in nl.primitives)


def test_a_circuit_with_registers_is_refused(cell):
    cfg = json.loads(json.dumps(cell.config))
    cfg["circuit"]["ff_ratio"] = 0.3
    with pytest.raises(ValueError, match="register-free"):
        _builder(cell).build(cfg, 56)


def test_a_program_without_fanout_classes_fails_at_once(cell,
                                                        monkeypatch):
    """What the parent commit does when asked for the cell: it exits
    with an error before it builds anything."""
    from parallel_eda_tpu.rr import terminals

    monkeypatch.delattr(terminals, "fanout_ladder")
    with pytest.raises(SystemExit, match="keeps no fanout classes"):
        _builder(cell).build(cell.config, 56)


def test_problem_as_built(cell, built):
    """The full-size problem is the one the traffic file fingerprints,
    and ``as_built`` states its fanout: Smax, each input net's sinks,
    the ladder and the nets a class."""
    f, ab = built, cell.config["as_built"]
    assert problem.fingerprint(f) == cell.traffic["problem_sha256"]
    t = f.term
    assert [f.grid.nx, f.grid.ny] == ab["grid"] == [20, 20]
    assert t.num_nets == ab["routed_nets"] == 2159
    assert f.rr.num_nodes == ab["rr_nodes"] and f.rr.unidir
    assert len(f.rr.out_dst) == ab["rr_edges"]
    assert t.max_sinks == ab["max_sinks"] == 204
    assert int(t.num_sinks.sum()) == ab["total_sinks"]
    assert [{"width": c.width, "nets": len(c.nets)}
            for c in t.fanout_classes] == ab["fanout_classes"] == [
        {"width": 15, "nets": 2142}, {"width": 204, "nets": 17}]
    blocks = [b.type_name for b in f.pnl.blocks]
    assert blocks.count("clb") == ab["clusters"] == 369
    assert blocks.count("io") == ab["io_pads_used"] == 62
    # the sixteen input nets are the sixteen widest, 100 sinks and up
    names = [f.pnl.nets[ni].name for ni in t.net_ids]
    wide = sorted(t.num_sinks.tolist())[-16:]
    inputs = [int(t.num_sinks[names.index(f"pi{i}")]) for i in range(16)]
    assert inputs == ab["input_net_sinks"] and sorted(inputs) == wide
    assert min(inputs) >= 100
    assert sum(inputs) / t.num_sinks.sum() == pytest.approx(
        ab["input_nets_share_of_all_sinks"], abs=1e-4)
    slots = sum(c.width * len(c.nets) for c in t.fanout_classes)
    assert t.num_sinks.sum() / slots == pytest.approx(
        ab["sink_slot_fill"], abs=1e-4)
    assert t.num_sinks.sum() / t.sinks.size == pytest.approx(
        ab["sink_slot_fill_dense"], abs=1e-4)
    # classes keep at least five times the dense table's fill
    assert ab["sink_slot_fill"] >= 5 * ab["sink_slot_fill_dense"]
    from parallel_eda_tpu.timing.graph import build_timing_graph
    tg = build_timing_graph(f.nl, f.pnl, t)
    assert (tg.num_tnodes, tg.depth) == (ab["timing_nodes"],
                                         ab["timing_depth"])
    assert tg.num_route_slots == slots
    # an input's tnode has its LUT pins for out-edges: the STA's table
    # stops at 32 a tnode and the rest are the overflow list
    assert tg.out_dst.shape == (ab["timing_nodes"], 32)
    assert len(tg.out_overflow[0]) == ab["timing_out_edges_overflow"]
    assert np.isin(tg.out_overflow[0], np.flatnonzero(
        tg.arrival0 > -np.inf)).mean() > 0.9


def test_reference_netlist_passes_the_build_and_recounts_it(cell, built):
    b = _builder(cell)
    plain = b.plain_netlist(built)
    assert b.netlist_problems(cell.config, built) == []
    c = reference_netlist.count_netlist(plain["prims"])
    ab = cell.config["as_built"]
    assert (c["luts"], c["ffs"], c["inputs"], c["outputs"]) == (
        3690, 0, 16, 46)
    assert (c["pins_lut_fed"], c["pins_input_fed"], c["pins_other"]) == (
        ab["pins_lut_fed"], ab["pins_input_fed"], 0)
    assert c["pins_lut_fed"] + c["pins_input_fed"] == ab["lut_pins"]
    s = reference_netlist.fanout_summary(plain["prims"])
    assert s["input_net_sinks"] == ab["input_net_sinks"]
    assert s["max_lut_output_sinks"] == ab["widest_lut_output_sinks"]
    # every routed net's sink count is its cluster-sink count
    got = [c["cluster_sinks"][n] for n in plain["routed"]]
    assert got == plain["num_sinks"].tolist()


def _small(cell, **circuit):
    cfg = json.loads(json.dumps(cell.config))
    cfg["circuit"].update(num_luts=250, num_inputs=4, num_outputs=8,
                          pi_pin_share=0.4, min_widest_sinks=20)
    cfg["circuit"].update(circuit)
    return cfg


@pytest.mark.parametrize("circuit, says", [
    # the input rule off: the inputs feed what an emptied window leaves
    # them and are not the wide nets
    (dict(pi_pin_share=0.0), "widest nets are not the input nets"),
    (dict(pi_pin_share=0.0, min_widest_sinks=1), "are not the input"),
    # the share the configuration states is not the share built
    (dict(pi_share_band=0.0001), "is not within 0.0001"),
    # inputs that are wide, and not wide enough for the deployment
    (dict(min_widest_sinks=100), "under 100"),
])
def test_reference_netlist_refuses(cell, circuit, says):
    cfg = _small(cell, **circuit)
    with pytest.raises(ValueError, match="reference_netlist refuses") \
            as err:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _builder(cell).build(cfg, 32)
    assert says in str(err.value)


def test_reference_netlist_counts_what_it_is_handed():
    L, I, O, F = (reference_netlist.LUT, reference_netlist.INPAD,
                  reference_netlist.OUTPAD, reference_netlist.FF)
    prims = [(I, "a", [], 0), (I, "b", [], 1),
             (L, "x", ["a", "b"], 2), (L, "y", ["a", "x"], 2),
             (L, "z", ["a", "y", "x"], 3), (O, None, ["z"], 4),
             (F, "q", ["z"], 3)]
    c = reference_netlist.count_netlist(prims)
    assert (c["luts"], c["ffs"], c["inputs"], c["outputs"]) == (3, 1, 2, 1)
    assert (c["pins_lut_fed"], c["pins_input_fed"]) == (3, 4)
    assert c["lut_pins"] == {"a": 3, "b": 1, "x": 2, "y": 1}
    # a enters blocks 2 and 3 once each; x leaves block 2 for block 3;
    # z is read in its own block (no sink) and by the pad
    assert c["cluster_sinks"] == {"a": 2, "b": 1, "x": 1, "y": 1, "z": 1}
    circuit = dict(num_luts=3, num_inputs=2, num_outputs=1,
                   pi_pin_share=4 / 7, pi_share_band=0.01,
                   min_widest_sinks=2)
    out = reference_netlist.netlist_problems(
        circuit, prims, ["a", "b", "x", "y", "z"],
        np.array([2, 1, 1, 1, 1]))
    assert out == ["ffs: built 1, the configuration says 0",
                   "the 2 widest nets are not the input nets (widest: "
                   "[('a', 2), ('b', 1), ('x', 1)])"] or len(out) == 1
    out = reference_netlist.netlist_problems(
        circuit, prims[:-1], ["a", "b", "x"], np.array([2, 1, 3]))
    assert any("sink count is not" in p for p in out)


# ---- the five accepted configurations: one class of today's width ----

ACCEPTED = [("route_relaxed", 8), ("route_tight", 8),
            ("route_k6n10_relaxed", 7), ("route_scale", 9),
            ("route_hetero", 13)]


@pytest.mark.parametrize("name, smax", ACCEPTED)
def test_an_accepted_cell_has_one_fanout_class(name, smax):
    """Their programs are the parent's: one class as wide as the dense
    tables were, no ``fan`` argument, and the problem the traffic file
    fingerprints."""
    c = harness.load_cell(harness.load_manifest(REPO), REPO, name)
    f = _build(c, int(c.traffic["chan_width"]))
    assert problem.fingerprint(f) == c.traffic["problem_sha256"]
    t = f.term
    assert t.max_sinks == smax
    assert [(k.width, len(k.nets)) for k in t.fanout_classes] == [
        (smax, t.num_nets)]
    assert np.array_equal(t.sink_slots(), np.arange(
        t.num_nets * smax).reshape(t.num_nets, smax))
    from parallel_eda_tpu.timing.graph import (OUT_ELL_CAP,
                                               build_timing_graph)
    tg = build_timing_graph(f.nl, f.pnl, t)
    assert tg.route_slots is None
    # and no tnode's out-edges reach the STA's overflow list
    assert tg.out_overflow is None and tg.out_dst.shape[1] < OUT_ELL_CAP


# ---- the readers ----

def _reader(name):
    return harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))


def _reg(**kv):
    return {"registry": {
        {"sinks": "route.fanout.sinks_dispatched_total",
         "slots": "route.fanout.sink_slots_dispatched_total",
         "full": "route.crop.net_dispatches_full_total"}[k]: v
        for k, v in kv.items()}}


@pytest.mark.parametrize("ctx, want", [
    (_reg(sinks=2500, slots=10000), 25.0),
    (_reg(sinks=900, slots=900), 100.0),
    (_reg(sinks=0, slots=0), None),         # nothing dispatched
    (_reg(full=5085), None),        # the parent: no such counter
    (_reg(slots=10), None),
    ({}, None),
])
def test_sink_slot_fill_share_reader(ctx, want):
    assert _reader("window.sink_slot_fill_share").read(ctx) == want


def _routes(**kw):
    return {"routes": [types.SimpleNamespace(**kw)]}


@pytest.mark.parametrize("ctx, want", [
    (_routes(total_relax_steps=8000, total_relax_steps_wide=2000), 25.0),
    (_routes(total_relax_steps=5185, total_relax_steps_wide=0), 0.0),
    (_routes(total_relax_steps=0, total_relax_steps_wide=0), None),
    (_routes(total_relax_steps=10), None),  # the parent: no such count
    ({"routes": []}, None),
    ({}, None),
])
def test_wide_net_sweep_share_reader(ctx, want):
    assert _reader("negotiation.wide_net_sweep_share").read(ctx) == want


def test_the_manifest_lists_the_cell_and_its_metrics():
    """One-way checks only: a later cell appended to these lists needs
    no edit of this file."""
    manifest = harness.load_manifest(REPO)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, better in (
            ("window.sink_slot_fill_share", "window program", "higher"),
            ("negotiation.wide_net_sweep_share", "negotiation driver",
             "lower")):
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
            assert "route_fanout" in m["workloads"], m["name"]
    w = next(w for w in manifest["workloads"]
             if w["name"] == "route_fanout")
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert (w["config"], w["traffic"]) == ("mcnc_spla_like_k6n10_l4",
                                           "route_fanout")
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == [] and entry["file"] == CONFIG
    assert entry["source"] == bench_cells.load(CONFIG)["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200


# ---- the real files at test size ----

@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, cell):
    """The real configuration, builder and traffic files at 250 LUTs of
    four inputs, W = 32, under a manifest of their own: a 5 x 5 device,
    four input nets of 24 or 25 sinks beside 155 of at most six."""
    root = str(tmp_path_factory.mktemp("fanout_cell"))
    name = bench_cells.write_cell(root, "route")
    cfg = _small(cell)
    cfg["router"]["opts"]["batch_size"] = 32
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
    f = _build(tiny, 32)
    assert [f.grid.nx, f.grid.ny] == [5, 5] and f.rr.unidir
    assert [(c.width, len(c.nets)) for c in f.term.fanout_classes] == [
        (6, 155), (25, 4)]
    traffic["problem_sha256"] = problem.fingerprint(f)
    dump()
    return root, name


def _failed_checks(out):
    return [ln.split(":")[0][len("check "):] for ln in out.splitlines()
            if ln.startswith("check ") and ln.endswith("NOT ok")]


def test_tiny_fanout_cell_is_correct(tiny_cell, tmp_path, capsys):
    """The device router in two fanout classes against the serial router
    on a small register-free placed problem with L=4 wires: both legal,
    wirelength within 1.10x, sink delays within 1e-5 of the float64
    sums, every route of the run the same; and the cell's two per-layer
    numbers are read."""
    root, name = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(root, name, seed=2**31 + 37,
                                  seconds=1.0, trace=True,
                                  work_dir=str(tmp_path))
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True, _failed_checks(
        capsys.readouterr().out)
    assert result["attempted"] >= 1 and result["failed"] == 0
    counts = result["rehearsal"]["counts"]
    assert counts["window.sweeps"] >= counts["negotiation.iterations"] >= 1
    withheld = result["rehearsal"]["withheld"]
    assert "window.sink_slot_fill_share" in withheld
    assert "negotiation.wide_net_sweep_share" in withheld


def test_tiny_fanout_control_bf16_is_not_correct(tiny_cell, tmp_path,
                                                 capsys):
    root, name = tiny_cell
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = harness.run_cell(
            root, name, seed=2**31 + 37, seconds=1.0, trace=False,
            work_dir=str(tmp_path),
            router_overrides={"plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    failed = _failed_checks(capsys.readouterr().out)
    assert "sink_delay_gap" in failed or "relax_gap" in failed
