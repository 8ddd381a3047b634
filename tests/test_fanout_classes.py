"""Nets routed in fanout classes: what a net costs follows its own sink
count and not the widest net's of the circuit.

The ladder (``rr/terminals.py`` ``fanout_ladder``) is a fixed function
of a problem's sink counts; the device's tables dense in the sink axis
(terminals, path store, sink delays, criticalities) are one table a
class; a batch holds nets of one class and ``_step_core`` runs at that
class's width; occupancy, history, the conflict colouring and the STA
see every class.  One register-free circuit (100 LUTs of three inputs,
each input a net of 34 to 39 cluster sinks beside 68 nets of at most
six) shows the whole path on the CPU."""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.problems.synth_placed_fanout import fanout_circuit
from parallel_eda_tpu import flow as F
from parallel_eda_tpu.arch.builtin import minimal_arch
from parallel_eda_tpu.route import Router, RouterOpts
from parallel_eda_tpu.route import planes
from parallel_eda_tpu.route.router import ClassedPaths, _by_class, _dense
from parallel_eda_tpu.route.serial_native import NativeSerialRouter
from parallel_eda_tpu.rr import terminals as T
from parallel_eda_tpu.timing.graph import build_timing_graph
from parallel_eda_tpu.timing.sta import TimingAnalyzer

W = 24


# ---- the ladder: a pure function of the sink counts ----

def test_the_ladders_constants_are_pinned():
    assert (T.FANOUT_BASE, T.FANOUT_STEP, T.FANOUT_MIN_NETS) == (16, 4, 8)


@pytest.mark.parametrize("counts, width, want", [
    # every accepted configuration: one class of the table's width
    ([1, 2, 9, 3], 9, [(9, 4)]),
    ([13] + [3] * 40, 13, [(13, 41)]),
    ([16] * 3, 16, [(16, 3)]),
    # a subset of a circuit keeps the circuit's width
    ([1, 2, 3], 9, [(9, 3)]),
    # sixteen nets in the hundreds beside a hundred of three: two
    # classes, each as wide as its widest net
    ([3] * 100 + [180] * 15 + [204], 204, [(3, 100), (204, 16)]),
    # a rung of fewer than eight nets joins the next one up ...
    ([3] * 100 + [23] + [180] * 16, 180, [(3, 100), (180, 17)]),
    # ... a rung of eight stands ...
    ([3] * 100 + [23] * 8 + [180] * 8, 180,
     [(3, 100), (23, 8), (180, 8)]),
    # ... the widest class joins nothing below it, however few it has
    ([3] * 100 + [40] * 2, 40, [(3, 100), (40, 2)]),
    # ... and small rungs cascade upward
    ([3] * 4 + [23] * 2 + [180] * 2, 190, [(190, 8)]),
    ([17], 17, [(17, 1)]),
    ([], 1, [(1, 0)]),
])
def test_fanout_ladder(counts, width, want):
    got = T.fanout_ladder(np.asarray(counts, dtype=np.int64), width)
    assert [(c.width, len(c.nets)) for c in got] == want
    nets = np.concatenate([c.nets for c in got])
    assert sorted(nets.tolist()) == list(range(len(counts)))
    for c in got:
        assert (np.diff(c.nets) > 0).all()
        assert (np.asarray(counts)[c.nets] <= c.width).all()


def test_rungs_stand_at_base_times_step():
    counts = np.asarray([16] * 8 + [17] * 8 + [64] * 8 + [65] * 8)
    got = T.fanout_ladder(counts, 65)
    assert [(c.width, len(c.nets)) for c in got] == [
        (16, 8), (64, 16), (65, 8)]


# ---- the circuit ----

@pytest.fixture(scope="module")
def placed():
    arch = minimal_arch(chan_width=W)
    nl, _ = fanout_circuit(num_luts=100, num_inputs=3, num_outputs=6,
                           K=arch.K, pi_pin_share=0.5, locality=40,
                           max_lut_levels=24, seed=1)
    return F.run_place(F.prepare(nl, arch, W))


def _route(f, resume=None, **kw):
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    opts = RouterOpts(program="planes", batch_size=32, **kw)
    return Router(f.rr, opts).route(f.term, analyzer=TimingAnalyzer(tg),
                                    resume=resume)


@pytest.fixture(scope="module")
def routed(placed):
    return _route(placed)


def test_the_circuit_has_two_classes(placed):
    t = placed.term
    assert [(c.width, len(c.nets)) for c in t.fanout_classes] == [
        (6, 68), (39, 3)]
    assert sorted(t.num_sinks[t.fanout_classes[1].nets].tolist()) == [
        34, 36, 39]
    cls, row = t.class_rows()
    assert cls.sum() == 3 and row[t.fanout_classes[1].nets].tolist() == [
        0, 1, 2]
    # the STA's slots: the classes' tables end to end, each slot once
    slots = t.sink_slots()
    have = slots[slots >= 0]
    assert sorted(have.tolist()) == list(range(68 * 6 + 3 * 39))
    assert (slots[cls == 0, 6:] == -1).all()
    real = np.arange(t.max_sinks)[None, :] < t.num_sinks[:, None]
    assert (slots[real] >= 0).all()


def test_one_class_slots_are_the_dense_layout():
    t = T.NetTerminals(
        net_ids=np.arange(3), source=np.zeros(3, np.int32),
        sinks=np.full((3, 4), -1, np.int32),
        num_sinks=np.array([1, 4, 2]), bb_xmin=np.zeros(3, np.int32),
        bb_xmax=np.zeros(3, np.int32), bb_ymin=np.zeros(3, np.int32),
        bb_ymax=np.zeros(3, np.int32))
    assert np.array_equal(t.sink_slots(), np.arange(12).reshape(3, 4))


def test_the_route_is_legal_and_close_to_the_serial_routers(placed,
                                                            routed):
    f, r = placed, routed
    assert r.success and isinstance(r.paths, ClassedPaths)
    assert r.paths.shape[:2] == f.term.sinks.shape == r.sink_delay.shape
    g = reference.GraphArrays.of(f.rr)
    j = reference.judge(g, f.term.source, f.term.sinks, f.term.num_sinks,
                        r.paths, r.sink_delay)
    assert j["problems"] == []
    assert j["wirelength"] == r.wirelength
    assert np.array_equal(j["occ"], np.asarray(r.occ))
    assert j["delay_gap"] < 1e-5
    native = NativeSerialRouter(f.rr).route(f.term)
    assert native.success and r.wirelength <= 1.10 * native.wirelength
    # the wide nets reach every sink, and only the slots a net has
    # hold a delay
    real = np.arange(f.term.max_sinks)[None, :] < f.term.num_sinks[:, None]
    assert np.isfinite(r.sink_delay[real]).all()
    assert np.isinf(r.sink_delay[~real]).all()
    N = f.rr.num_nodes
    for net in f.term.fanout_classes[1].nets:
        for s in range(int(f.term.num_sinks[net])):
            assert r.paths[net][s][0] == f.term.sinks[net, s]
            assert r.paths[net, s, 0] == f.term.sinks[net, s]
    dense = np.asarray(r.paths)
    assert dense.shape == r.paths.shape and (dense[~real] == N).all()
    narrow = int(f.term.fanout_classes[0].nets[0])
    assert np.array_equal(dense[narrow, :6], r.paths[narrow])


def test_the_rows_say_what_the_wide_class_cost(routed):
    r = routed
    assert [row.fanout_class for row in r.stats][0] == 1
    assert sum(row.relax_steps_wide for row in r.stats) == \
        r.total_relax_steps_wide
    assert 0 < r.total_relax_steps_wide < r.total_relax_steps
    for row in r.stats:
        assert 0 <= row.waves_wide <= row.waves
        assert 0 <= row.relax_steps_wide <= row.relax_steps
        assert (row.fanout_class > 0) == (row.waves_wide > 0)


def test_the_route_is_the_route_of_dense_sink_picks(placed, routed):
    """The waves' live sink pick (planes.sink_pick_wave) moves nothing:
    with its ladder forced to the dense rung the two-class route comes
    back node for node and count for count; the ladder as built read
    fewer distances, in the narrow class and in the wide one."""
    from sink_pick_refs import assert_same_route, dense_ladder

    with dense_ladder():
        dense = _route(placed)
    assert_same_route(routed, dense)
    assert dense.total_sink_reads == dense.total_sink_reads_dense > 0
    assert routed.total_sink_reads_dense == dense.total_sink_reads_dense
    assert 0 < routed.total_sink_reads < routed.total_sink_reads_dense
    for row, row_dense in zip(routed.stats, dense.stats):
        assert row_dense.sink_reads == row_dense.sink_reads_dense \
            == row.sink_reads_dense
        assert 0 < row.sink_reads < row.sink_reads_dense
    assert sum(s.sink_reads for s in routed.stats) \
        == routed.total_sink_reads


def test_the_route_is_the_route_of_dense_walk_scatters(placed, routed):
    """The waves' chunked walk scatters (planes.walk_scatters) move
    nothing: with ONE scatter each over every walk slot (the program
    until PR 42) the two-class route comes back node for node and count
    for count, the wide class's waves of 40 picks among them."""
    from sink_pick_refs import assert_same_route
    from walk_refs import dense_scatters

    with dense_scatters():
        dense = _route(placed)
    assert_same_route(routed, dense, but=("total_walk_slots_read",))
    assert 0 < routed.total_walk_slots_read < routed.total_walk_budget \
        == dense.total_walk_slots_read


def test_two_runs_are_identical(placed, routed):
    again = _route(placed)
    assert again.wirelength == routed.wirelength
    assert again.iterations == routed.iterations
    assert again.total_relax_steps == routed.total_relax_steps
    assert np.array_equal(np.asarray(again.paths), np.asarray(routed.paths))
    assert np.array_equal(again.sink_delay, routed.sink_delay)
    assert np.array_equal(again.occ, routed.occ)


def test_a_route_resumed_from_a_checkpoint_ends_bit_identical(placed,
                                                              routed):
    cut = _route(placed, max_router_iterations=4, checkpoint_every=1)
    ck = cut.checkpoint
    assert ck.it_done == 4 and isinstance(ck.paths, tuple)
    assert [p.shape[:2] for p in ck.paths] == [(68, 6), (3, 39)]
    assert [d.shape for d in ck.sink_delay] == [(68, 6), (3, 39)]
    res = _route(placed, resume=ck)
    assert res.success and res.iterations == routed.iterations
    assert res.wirelength == routed.wirelength
    assert np.array_equal(np.asarray(res.paths), np.asarray(routed.paths))
    assert np.array_equal(res.sink_delay, routed.sink_delay)
    assert np.array_equal(res.occ, routed.occ)


def test_the_counters_and_gauges(placed):
    from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics

    old = get_metrics()
    reg = set_metrics(MetricsRegistry())
    try:
        t = T.net_terminals(placed.pnl, placed.rr, placed.pos)
        r = _route(placed)
    finally:
        set_metrics(old)
    v = reg.values("route.fanout.")
    assert v["route.fanout.max_sinks"] == 39
    assert v["route.fanout.classes"] == 2
    assert v["route.fanout.sink_slot_fill"] == pytest.approx(
        t.num_sinks.sum() / (68 * 6 + 3 * 39))
    assert v["route.fanout.net_dispatches_wide_total"] >= 3
    slots, sinks = (v["route.fanout.sink_slots_dispatched_total"],
                    v["route.fanout.sinks_dispatched_total"])
    # every dispatched net brings its class's width: far fewer slots
    # than the widest net's for every net
    nets = (reg.values("route.crop.")[
        "route.crop.net_dispatches_full_total"] + reg.values(
        "route.crop.")["route.crop.net_dispatches_cropped_total"])
    assert sinks <= slots < nets * 39 / 3
    assert r.success


# ---- the pieces ----

def test_classed_paths_and_the_dense_tables_round_trip(placed):
    t = placed.term
    classes = t.fanout_classes
    rng = np.random.default_rng(0)
    dense = rng.integers(0, 100, (t.num_nets, t.max_sinks, 5)).astype(
        np.int32)
    real = np.arange(t.max_sinks)[None, :] < np.array(
        [classes[c].width for c in t.class_rows()[0]])[:, None]
    dense[~real] = 777
    parts = _by_class(dense, classes)
    assert [p.shape for p in parts] == [(68, 6, 5), (3, 39, 5)]
    back = _dense(parts, classes, 777)
    assert np.array_equal(back, dense)
    cp = ClassedPaths(parts, classes, 777)
    assert cp.shape == dense.shape and len(cp) == t.num_nets
    wide, narrow = int(classes[1].nets[0]), int(classes[0].nets[0])
    assert np.array_equal(cp[wide], dense[wide])
    assert np.array_equal(cp[narrow], dense[narrow, :6])
    assert np.array_equal(cp[wide, 20], dense[wide, 20])
    assert np.array_equal(np.asarray(cp), dense)
    assert np.array_equal(cp[np.array([narrow, wide])],
                          dense[[narrow, wide]])
    one = T.fanout_ladder(np.ones(t.num_nets, np.int64), t.max_sinks)
    assert not isinstance(_by_class(dense, one), tuple)
    assert np.array_equal(_dense(_by_class(dense, one), one, 0), dense)


def test_mis_colors_in_classes_is_the_dense_colouring(placed, routed):
    """The conflict picture is ONE picture of all classes: the stores a
    class colour as the dense store does, net for net."""
    t = placed.term
    router = Router(placed.rr, RouterOpts(program="planes"))
    N = placed.rr.num_nodes
    dense = np.asarray(routed.paths)
    rng = np.random.default_rng(3)
    occ = np.asarray(routed.occ).copy()
    wires = np.flatnonzero(occ > 0)
    occ[rng.choice(wires, 40, replace=False)] += 3      # overuse
    reached = rng.random(t.num_nets) > 0.1
    want = planes._mis_colors(router.dev, jnp.asarray(occ),
                              jnp.asarray(dense), jnp.asarray(reached),
                              64, 5)
    _, _, fan = router._planes_terminals(t)
    got = planes._mis_colors(router.dev, jnp.asarray(occ),
                             _by_class(dense, t.fanout_classes),
                             jnp.asarray(reached), 64, 5, fan)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(want[0]).any() and len(set(
        np.asarray(want[1]).tolist())) > 1


def test_a_batch_at_a_wider_table_is_the_same_batch():
    """Pad invariance: ``_step_core`` at the class's width and at a
    wider table gives the same paths, delays and occupancy on the real
    slots, bit for bit -- what lets a class be as wide as its widest
    net and a net ride in a wider class."""
    import __graft_entry__ as graft

    def run(extra):
        p = graft.planes_step_problem()
        src, sks, crit = p["nets"][:3]
        tbl = list(p["nets"][3:])
        occ, acc, paths, sink_delay, all_reached, bb = p["state"]
        N = p["dev"].num_nodes
        U = tbl[5].shape[0] - 1

        def wider(a, fill):
            return jnp.pad(a, ((0, 0), (0, extra)) + ((0, 0),) * (
                a.ndim - 2), constant_values=fill)
        tbl[4] = wider(tbl[4], U)           # sink_uid
        tbl[9] = wider(tbl[9], -1)          # direct_oidx
        tbl[10] = wider(tbl[10], N)         # direct_ipin
        tbl[11] = wider(tbl[11], 0)         # direct_delay
        S = sks.shape[1]
        out = planes.route_batch_resident_planes(
            p["pg"], p["dev"], occ, acc, jnp.float32(0.5),
            wider(paths, N), wider(sink_delay, jnp.inf), all_reached, bb,
            src, wider(sks, -1), wider(crit, 0), *tbl,
            p["sel"], p["valid"], p["full_bb"], p["nsweeps"],
            p["max_len"], p["num_waves"], S + extra, True, None)
        return S, [np.asarray(o) for o in out]

    S, narrow = run(0)
    _, wide = run(5)
    assert np.array_equal(wide[0][:, :S], narrow[0])
    assert (wide[0][:, S:] == wide[0].max()).all()
    assert np.array_equal(wide[1][:, :S], narrow[1])
    assert np.isinf(wide[1][:, S:]).all()
    for k in (2, 3, 4, 5):
        assert np.array_equal(wide[k], narrow[k]), k
    assert narrow[2].any() and narrow[4].sum() > 0


# ---- the STA's out-edge table: a tnode's out-edges past OUT_ELL_CAP
# are a flat overflow list (a primary input of 260 LUT pins made every
# tnode pay for 285 out-edge slots a level) ----

def test_the_out_edge_cap_is_pinned():
    from parallel_eda_tpu.timing import graph

    assert graph.OUT_ELL_CAP == 32


@pytest.mark.parametrize("sdc_text", [
    None, "create_clock -period 3.0 clk\n"], ids=["one_clock", "sdc"])
def test_the_capped_out_edge_table_gives_the_full_tables_times(
        monkeypatch, sdc_text):
    """Required times, criticalities, critical path and worst slack are
    the uncapped table's to the last bit: the overflow's scatter-min is
    the same min."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.timing import graph
    from parallel_eda_tpu.timing.sdc import parse_sdc

    f = synth_flow(num_luts=60, chan_width=12, seed=2)
    sdc = None if sdc_text is None else parse_sdc(sdc_text)
    rng = np.random.default_rng(5)
    delay = rng.uniform(1e-10, 2e-9, f.term.sinks.shape).astype(
        np.float32)
    got = {}
    for cap in (2, 10 ** 9):
        monkeypatch.setattr(graph, "OUT_ELL_CAP", cap)
        tg = build_timing_graph(f.nl, f.pnl, f.term)
        an = TimingAnalyzer(tg, sdc=sdc)
        got[cap] = (an.analyze(delay), an.crit_path_delay,
                    an.worst_slack, tg)
    capped, full = got[2][3], got[10 ** 9][3]
    assert full.out_overflow is None and full.out_dst.shape[1] > 2
    assert capped.out_dst.shape[1] == 2
    assert len(capped.out_overflow[0]) == (
        full.out_valid.sum() - capped.out_valid.sum()) > 0
    assert np.array_equal(got[2][0], got[10 ** 9][0])
    assert got[2][1:3] == got[10 ** 9][1:3]
    assert got[2][0].max() > 0
