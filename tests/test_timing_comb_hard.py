"""Combinational hard blocks in the timing graph (the published
``mult_36``: a path runs THROUGH a multiplier): the device STA, through
the host wrapper and as the window program calls it, against the
benchmark's plain float64 reference on seeded delays; the in-edge
table's width; graphs without such a block unchanged; the loop check;
a whole timing-driven route."""

import hashlib
import json
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness, reference, reference_timing  # noqa: E402
from parallel_eda_tpu import flow as F  # noqa: E402
from parallel_eda_tpu.arch import builtin  # noqa: E402
from parallel_eda_tpu.netlist.netlist import (  # noqa: E402
    PRIM_FF, PRIM_HARD, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD, LogicalNetlist,
    Primitive)
from parallel_eda_tpu.route.router import RouterOpts  # noqa: E402
from parallel_eda_tpu.timing.graph import build_timing_graph  # noqa: E402
from parallel_eda_tpu.timing.sdc import SdcConstraints  # noqa: E402
from parallel_eda_tpu.timing.sta import (  # noqa: E402
    TimingAnalyzer, sta_crit, to_device)

CONFIG = "benchmark/configs/vtr_raygentop_like_k6frac_n10_mem32k.json"
HETERO = "benchmark/configs/vtr_or1200_like_k6frac_n10_mem32k.json"


def _load(rel):
    with open(os.path.join(REPO, rel)) as fh:
        return json.load(fh)


def _builder(name):
    return harness.load_module(os.path.join(
        REPO, "benchmark", "problems", name + ".py"))


def _mult(name, at, used=8, a_from=None, levels=3):
    h = {"name": name, "model": "multiply", "mode": "mult_18x18",
         "levels": levels, "at_lut": at,
         "inputs": [["a", 36, used], ["b", 36, used]],
         "outputs": [["out", 72, 2 * used]]}
    if a_from:
        h["operands_from"] = {"a": a_from}
    return h


RAM = {"name": "ram0", "model": "dual_port_ram", "at_lut": 25,
       "inputs": [["addr1", 15, 3], ["addr2", 15, 3], ["data", 64, 8],
                  ["we1", 1, 1], ["we2", 1, 1]],
       "outputs": [["out", 64, 8]]}


def _placed(seed, hard_blocks, num_luts=60, num_inputs=10, W=32):
    """LUT -> multiplier -> LUT -> multiplier -> flip-flop chains: the
    benchmark's generator at test size, the second multiplier's ``a``
    wired to the first's product, a registered RAM between them, packed
    and placed on the published device with combinational multipliers."""
    nl, _ = _builder("synth_placed_dsp").dsp_circuit(
        num_luts=num_luts, num_inputs=num_inputs, num_outputs=8, K=6,
        ff_ratio=0.3, locality=20, max_lut_levels=10, seed=seed,
        hard_blocks=hard_blocks)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        arch = builtin.k6_frac_n10_mem32k_40nm_arch(
            chan_width=W, mult_combinational=True)
        return F.run_place_native(F.prepare(nl, arch, W), seed=7)


CHAIN = [_mult("m0", 15), RAM, _mult("m1", 35, a_from="m0")]


@pytest.fixture(scope="module", params=[3, 2**31 + 40])
def chain(request):
    f = _placed(request.param, CHAIN)
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    rng = np.random.default_rng(request.param)
    delay = rng.uniform(1e-10, 2e-9, f.term.sinks.shape).astype(np.float32)
    return f, tg, delay


@pytest.fixture(scope="module")
def timing():
    return reference_timing.block_timing(_load(CONFIG))


def _pin_key(tg, t):
    role, net = tg.tnode_pin[t]
    return (int(tg.tnode_prim[t]), role, net)


def _analyze(via, tg, delay, sdc):
    """(crit [R, S], dmax, worst slack, arrival [T]) through the host
    wrapper, or as route/planes.py calls ``sta_crit`` inside the window
    program (the delays flat with a trailing zero, under one jit)."""
    ta = TimingAnalyzer(tg, sdc=sdc)
    if via == "analyzer":
        crit = ta.analyze(delay)
        return (crit, ta.crit_path_delay, ta.worst_slack,
                np.asarray(ta._last[1]))
    assert tg.route_slots is None

    @jax.jit
    def fused(dev, sink_delay, req_seed):
        flat = jnp.append(sink_delay.reshape(-1), jnp.float32(0.0))
        crit, dmax, worst, arr = sta_crit(
            dev, flat, tg.depth, 1.0, 0.99, req_seed=req_seed,
            use_sdc=sdc is not None)
        return crit.reshape(sink_delay.shape), dmax, worst, arr
    crit, dmax, worst, arr = fused(ta.dev, jnp.asarray(delay),
                                   ta._req_seed)
    return np.asarray(crit), float(dmax), float(worst), np.asarray(arr)


@pytest.mark.parametrize("via", ["analyzer", "fused"])
@pytest.mark.parametrize("period_x", [None, 0.6, 1.7],
                         ids=["plain", "sdc_tight", "sdc_loose"])
def test_sta_matches_the_plain_reference(chain, timing, via, period_x):
    """Critical path, every pin's arrival and every routed connection's
    criticality to 1e-6 relative (float32 sums of a dozen terms against
    float64; a criticality is 1 - slack / D with the slack a difference
    of such sums, so it is held to 5e-6 absolute)."""
    f, tg, delay = chain
    conn = reference_timing.connection_delays(
        f.pnl, f.term.net_ids, delay.astype(np.float64))
    plain = reference_timing.analyze(f.nl, f.pnl, timing, conn)
    sdc = kw = None
    if period_x is not None:
        period = period_x * plain["dmax"]
        sdc = SdcConstraints(clock_periods={"clk": period})
        kw = {"periods": {"clk": period}, "default_period": period}
    ref = (plain if kw is None else
           reference_timing.analyze(f.nl, f.pnl, timing, conn, **kw))
    crit, dmax, worst, arr = _analyze(via, tg, delay, sdc)

    assert ref["hard_arcs"] == 2        # m0 -> m1 on the critical path
    assert dmax == pytest.approx(ref["dmax"], rel=1e-6)
    if period_x is not None:
        assert worst == pytest.approx(ref["worst_slack"], rel=1e-5,
                                      abs=1e-15)
    checked = 0
    for t in range(tg.num_tnodes):
        if tg.tnode_pin[t][0] == "junction":
            continue
        want = ref["arrival"][_pin_key(tg, t)]
        if np.isfinite(want):
            assert arr[t] == pytest.approx(want, rel=1e-6), tg.tnode_pin[t]
            checked += 1
        else:
            assert arr[t] == want
    assert checked > 100
    # a junction's arrival is the max over its block's input pins
    for j in tg.comb_junction:
        ins = [t for t in range(tg.num_tnodes)
               if tg.tnode_prim[t] == tg.tnode_prim[j]
               and tg.tnode_pin[t][0] == "hin"]
        assert arr[j] == arr[ins].max()
    got = {}
    for r, ni in enumerate(f.term.net_ids):
        net = f.pnl.nets[int(ni)]
        for s, pin in enumerate(net.sinks):
            got[(net.name, int(pin.block))] = float(crit[r, s])
    assert set(ref["crit"]) <= set(got)
    for key, want in ref["crit"].items():
        assert got[key] == pytest.approx(want, abs=5e-6), key
    # connections into a multiplier carry criticalities of their own
    hard = [k for k in ref["crit"]
            if f.pnl.blocks[k[1]].type_name == "mult_36"]
    assert len(hard) >= 16 and max(ref["crit"][k] for k in hard) > 0.5
    if period_x == 0.6:
        assert max(got.values()) == pytest.approx(0.99)


def test_the_analyzers_path_walk_finds_the_multiplier_arcs(chain, timing):
    f, tg, delay = chain
    ta = TimingAnalyzer(tg)
    ta.analyze(delay)
    ref = reference_timing.analyze(
        f.nl, f.pnl, timing, reference_timing.connection_delays(
            f.pnl, f.term.net_ids, delay.astype(np.float64)))
    assert ta.crit_path_hard_arcs() == ref["hard_arcs"] == 2
    walked = [_pin_key(tg, t) for t in ta.critical_path()
              if tg.tnode_pin[t][0] != "junction"]
    assert walked == ref["path"]


def test_a_72_input_block_leaves_the_table_at_the_luts_width():
    """36x36 mode on all 72 operand pins: the in-edge ELL keeps K = 6
    columns, the junction's other 66 in-edges lie in the overflow list,
    and its 72 out-edges leave the out-edge ELL at the nets' fanout."""
    wide = {"name": "m0", "model": "multiply", "mode": "mult_36x36",
            "levels": 3, "at_lut": 30,
            "inputs": [["a", 36, 36], ["b", 36, 36]],
            "outputs": [["out", 72, 72]]}
    f = _placed(5, [wide], num_luts=80, num_inputs=90, W=48)
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    (j,) = tg.comb_junction
    fanin = max(len(p.inputs) for p in f.nl.primitives
                if p.kind == PRIM_LUT)
    assert tg.in_src.shape[1] == fanin == 6
    assert len(tg.in_overflow[0]) == 72 - 6 and tg.in_edges_wide == 72
    assert (tg.in_overflow[0] == j).all()
    assert tg.in_valid[j].sum() == 6
    widest_other = max(int(tg.out_valid[t].sum())
                       for t in range(tg.num_tnodes) if t != j)
    assert tg.out_dst.shape[1] == widest_other < 32
    assert (tg.out_overflow[0] == j).all()
    assert tg.out_valid[j].sum() + len(tg.out_overflow[0]) == 72
    assert tg.num_in_edges == int(tg.in_valid.sum()) + 66
    # the 36x36 mode's delay lies on the junction's out-edges
    assert np.allclose(tg.out_const[j][tg.out_valid[j]], 1.93e-9)
    dev = to_device(tg)
    assert len(jax.tree_util.tree_leaves(dev)) == 10 + 4 + 4


FIELDS = ("in_src", "in_const", "in_ridx", "in_valid", "out_dst",
          "out_const", "out_ridx", "out_valid", "arrival0", "is_endpoint",
          "tnode_prim", "endpoint_domain")


def tg_sha256(tg) -> str:
    """What the parent commit's ``build_timing_graph`` returned, as one
    hash: the three sizes and the twelve arrays, dtype and shape
    included."""
    h = hashlib.sha256()
    h.update(np.asarray([tg.num_tnodes, tg.depth, tg.num_route_slots],
                        np.int64).tobytes())
    for k in FIELDS:
        a = np.ascontiguousarray(getattr(tg, k))
        h.update(str((k, a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_a_graph_without_a_combinational_block_is_the_parents():
    """The or1200 stand-in at test size (its multiplier REGISTERED, as
    its configuration states): the arrays hash to what commit 19cf3fd
    built, and the device pytree has the parent's ten leaves."""
    cfg = _load(HETERO)
    cfg["circuit"].update(num_luts=60, num_inputs=8, num_outputs=8)
    cfg["circuit"]["hard_blocks"] = [
        dict(RAM, name="rf_a", at_lut=20),
        {"name": "mult0", "model": "multiply", "at_lut": 40,
         "inputs": [["a", 36, 8], ["b", 36, 8]],
         "outputs": [["out", 72, 16]]}]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = _builder("synth_placed_hetero").build(cfg, 16)
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg_sha256(tg) == ("f491f2abbcf31a9f33654860862c453f"
                             "226d6cf83d064c9e573b228b038a0181")
    assert tg.in_overflow is None and tg.comb_junction is None
    assert tg.in_edges_wide == 0 and tg.out_overflow is None
    dev = to_device(tg)
    assert dev.in_overflow is None and dev.out_overflow is None
    assert len(jax.tree_util.tree_leaves(dev)) == 10
    assert TimingAnalyzer(tg).crit_path_hard_arcs() == 0


def test_a_cycle_through_a_multiplier_raises():
    """x = LUT(pi0, product bit 0) feeds the multiplier's operand: a
    combinational loop through the block, refused as one through LUTs
    is; with the multiplier REGISTERED the same netlist is a legal
    pipeline."""
    def netlist(clock):
        nl = LogicalNetlist(name="loop")
        nl.add(Primitive(name="clk", kind=PRIM_INPAD, output="clk"))
        for n in ("pi0", "pi1"):
            nl.add(Primitive(name=n, kind=PRIM_INPAD, output=n))
        nl.add(Primitive(name="x", kind=PRIM_LUT, inputs=["pi0", "m.o0"],
                         output="x", truth_table=["11 1"]))
        nl.add(Primitive(
            name="m", kind=PRIM_HARD, model="multiply", clock=clock,
            inputs=["x"] + [None] * 35 + ["pi1"] + [None] * 35,
            outputs=["m.o0", "m.o1"] + [None] * 70))
        nl.add(Primitive(name="q", kind=PRIM_FF, inputs=["x"], output="q",
                         clock="clk"))
        nl.add(Primitive(name="out:a", kind=PRIM_OUTPAD, inputs=["m.o1"]))
        nl.add(Primitive(name="out:b", kind=PRIM_OUTPAD, inputs=["q"]))
        nl.finalize()
        return nl

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = F.prepare(netlist(None), builtin.k6_frac_n10_mem32k_40nm_arch(
            chan_width=16, mult_combinational=True), 16)
        with pytest.raises(ValueError, match="combinational loop"):
            build_timing_graph(f.nl, f.pnl, f.term)
        f = F.prepare(netlist("clk"),
                      builtin.k6_frac_n10_mem32k_40nm_arch(chan_width=16),
                      16)
    assert build_timing_graph(f.nl, f.pnl, f.term).depth >= 2


def _float64_sink_delays(f):
    g = reference.GraphArrays.of(f.rr)
    t = f.term
    legal = reference.check_legality(g, t.source, t.sinks, t.num_sinks,
                                     f.route.paths)
    assert legal["problems"] == []
    return reference.tree_sink_delays(g, t.source, t.sinks, t.num_sinks,
                                      legal["parents"])


def test_a_timing_driven_route_through_combinational_multipliers(timing):
    """``flow.run_route`` on the planes window program: legal, the
    reported critical path within 1e-5 of the plain reference's on the
    float64 sink delays and through both multipliers; the same delays
    analysed with the multipliers REGISTERED (the parent's semantics)
    miss it by over 1e-2; the window program names the wide fold."""
    from parallel_eda_tpu.obs import (DevProfiler, Tracer, get_devprof,
                                      get_metrics, set_devprof, set_tracer)

    f = _placed(3, CHAIN, W=48)
    old, tr = get_devprof(), Tracer()
    dp = set_devprof(DevProfiler(enabled=True))
    set_tracer(tr)
    try:
        f = F.run_route(f, RouterOpts(program="planes", batch_size=32),
                        timing_driven=True)
        pending = list(dp._pending)
    finally:
        set_devprof(old)
        set_tracer(None)
    assert f.route.success
    conn = reference_timing.connection_delays(
        f.pnl, f.term.net_ids, _float64_sink_delays(f))
    ref = reference_timing.analyze(f.nl, f.pnl, timing, conn)
    gap = abs(f.crit_path_delay - ref["dmax"]) / ref["dmax"]
    assert gap < 1e-5 and ref["hard_arcs"] == 2
    control = reference_timing.analyze(
        f.nl, f.pnl, reference_timing.registered(timing), conn)
    assert control["hard_arcs"] == 0
    assert abs(f.crit_path_delay - control["dmax"]) / control["dmax"] > 1e-2

    # the span, its args and the gauges
    (build,) = [e for e in tr.events if e["name"] == "timing.graph.build"]
    tg = f.tg
    assert build["args"] == {
        "tnodes": tg.num_tnodes, "depth": tg.depth,
        "in_edges": tg.num_in_edges, "in_edges_wide": 16 + 16,
        "comb_hard_blocks": 2, "in_width": tg.in_src.shape[1]}
    values = get_metrics().values("route.timing.")
    assert values["route.timing.in_edges_wide"] == 32
    assert values["route.timing.comb_hard_blocks"] == 2
    assert values["route.timing.tnodes"] == tg.num_tnodes
    assert values["route.timing.depth"] == tg.depth
    assert values["route.timing.in_edges"] == tg.num_in_edges
    (sta,) = [e for e in tr.events if e["name"] == "flow.route.sta"]
    assert sta["args"]["crit_path_hard_arcs"] == 2
    # the wide fold is a named stage of the compiled window program
    _, _, fn, args, kwargs = pending[0]
    text = fn.lower(*args, **kwargs).compile().as_text()
    assert "route.dev.sta/route.dev.sta.wide_fold" in text
