"""Unidirectional (single-driver) routing architectures.

The reference handles UNI_DIRECTIONAL vs BI_DIRECTIONAL segments in
rr_graph.c:432-548; every modern VTR/Titan arch is unidir.  Here: the
builder's directed graph invariants, planes-vs-ELL relaxation parity on
directed planes (the two independent implementations are each other's
oracle), full-flow legality/determinism, and crit-path parity vs the
serial oracle on the same unidir graph.
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import k6_n10_40nm_arch, unidir_arch
from parallel_eda_tpu.arch.model import SegmentInf
from parallel_eda_tpu.flow import prepare, run_place, run_place_native
from parallel_eda_tpu.netlist.generate import generate_circuit
from parallel_eda_tpu.netlist.synthesis import array_multiplier
from parallel_eda_tpu.route.check import check_route
from parallel_eda_tpu.route.device_graph import to_device
from parallel_eda_tpu.route.planes import build_planes, planes_relax
from parallel_eda_tpu.route.qor import qor_compare
from parallel_eda_tpu.route.router import Router, RouterOpts
from parallel_eda_tpu.route.search import _relax
from parallel_eda_tpu.route.serial_native import NativeSerialRouter
from parallel_eda_tpu.route.serial_ref import SerialRouter
from parallel_eda_tpu.rr.graph import (CHANX, CHANY, OPIN, RRGraph,
                                       build_rr_graph, check_rr_graph,
                                       unidir_box_stats)
from parallel_eda_tpu.rr.grid import DeviceGrid


def _mixed_unidir():
    arch = unidir_arch(chan_width=12)
    arch.segments = [
        SegmentInf(name="l1", length=1, frequency=0.4, wire_switch=0,
                   opin_switch=1, directionality="unidir"),
        SegmentInf(name="l2", length=2, frequency=0.3, Rmetal=80.0,
                   Cmetal=15e-15, wire_switch=1, opin_switch=1,
                   directionality="unidir"),
        SegmentInf(name="l4", length=4, frequency=0.3, Rmetal=60.0,
                   Cmetal=12e-15, wire_switch=0, opin_switch=0,
                   directionality="unidir"),
    ]
    return arch


@pytest.mark.parametrize("length", [1, 2, 4])
def test_unidir_builder_invariants(length):
    """Directed graph sanity: every wire single-driver-reachable, no
    symmetric wire<->wire edge pairs, all SINKs reachable
    (check_rr_graph reachability sweep)."""
    arch = unidir_arch(chan_width=12, length=length)
    grid = DeviceGrid(nx=6, ny=6, io_capacity=arch.io_capacity)
    rr = build_rr_graph(arch, grid, chan_width=12)
    assert rr.unidir
    check_rr_graph(rr)
    wires = (rr.node_type == CHANX) | (rr.node_type == CHANY)
    indeg = np.diff(rr.in_row_ptr)
    assert int((indeg[wires] == 0).sum()) == 0, "driverless wire"
    src_ids = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    ww = wires[src_ids] & wires[rr.out_dst]
    pairs = set(zip(src_ids[ww].tolist(), rr.out_dst[ww].tolist()))
    assert not any((b, a) in pairs for (a, b) in pairs), \
        "symmetric wire edges in a unidir graph"


def _plain_unidir_edges(rr, arch):
    """The unidir switch box, stated in plain Python from
    build_rr_graph's docstring alone (no helper of the builder): the
    set of wire -> wire edges the rules give, and per OPIN and channel
    the number of starts it must drive."""
    W, nx, ny = rr.chan_width, rr.grid.nx, rr.grid.ny
    seg = arch.segments[0]
    L, sb = seg.length, seg.sb_marks()
    G = W // (2 * L)
    wires = {}                  # (kind, chan, track) -> [(lo, hi, node)]
    for n in np.flatnonzero((rr.node_type == CHANX)
                            | (rr.node_type == CHANY)):
        n = int(n)
        if rr.node_type[n] == CHANX:
            key, lo, hi = ("x", int(rr.ylow[n])), rr.xlow[n], rr.xhigh[n]
        else:
            key, lo, hi = ("y", int(rr.xlow[n])), rr.ylow[n], rr.yhigh[n]
        wires.setdefault(key + (int(rr.ptc[n]),), []).append(
            (int(lo), int(hi), n))

    def exits_at(kind, chan, c):
        """[(track, node, ends here)] of the wires of one channel that
        exit at the corner of coordinate c along it."""
        out = []
        for t in range(W):
            lane_stagger = (t // 2) % L
            for lo, hi, n in wires[(kind, chan, t)]:
                if t % 2 == 0:          # INC: starts at lo, points
                    if not lo <= c <= hi:       # 1..L at lo..hi
                        continue
                    # the uncut wire starts after a break of its lane
                    k = (c - lane_stagger - 1) % L + 1
                    end = c == hi
                else:                   # DEC: starts at hi
                    if not lo - 1 <= c <= hi - 1:
                        continue
                    k = L - (c - lane_stagger) % L
                    end = c == lo - 1
                if end or sb[k]:
                    out.append((t, n, end))
        return out

    def starts_at(kind, chan, c):
        out = []
        for t in range(W):
            for lo, hi, n in wires[(kind, chan, t)]:
                if (t % 2 == 0 and lo == c + 1) or (t % 2 and hi == c):
                    out.append((t, n))
        return out

    edges = set()
    for x in range(nx + 1):
        for y in range(ny + 1):
            p = (x + y) % 2
            ex, ey = exits_at("x", y, x), exits_at("y", x, y)
            sx, sy = starts_at("x", y, x), starts_at("y", x, y)
            for src, own, other, sign in ((ex, sx, sy, 1), (ey, sy, sx, -1)):
                for t, n, end in src:
                    g = (t // (2 * L) + sign * p) % G
                    edges |= {(n, n2) for t2, n2 in other
                              if t2 // (2 * L) == g}
                    if end:
                        edges |= {(n, n2) for t2, n2 in own if t2 == t}
    return edges


@pytest.mark.parametrize("sb", [(1, 1, 1, 1, 1), (1, 0, 0, 0, 1),
                                (1, 0, 1, 0, 1)])
def test_unidir_l4_box_follows_the_plain_rules(sb):
    """Length 4, the published Fc (0.15 / 0.10), a 9x9 grid: the
    builder's wire -> wire edges are exactly the plain-Python rule
    set's (exits per sb, Fs=3 onto the group's starting wires, straight
    on only at the end), every wire is driven only at its start, and
    every OPIN drives its Fc share of distinct starts in every channel
    it faces, the perimeter included."""
    arch = _k6_l4(sb)
    grid = DeviceGrid(nx=9, ny=9, io_capacity=arch.io_capacity)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rr = build_rr_graph(arch, grid, chan_width=16)
    check_rr_graph(rr, arch=arch)
    wire = (rr.node_type == CHANX) | (rr.node_type == CHANY)
    src = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    ww = wire[src] & wire[rr.out_dst]
    got = set(zip(src[ww].tolist(), rr.out_dst[ww].tolist()))
    want = _plain_unidir_edges(rr, arch)
    assert got == want, (len(got - want), len(want - got))
    # Fs = 3: no exit drives more than an up, a down and a straight-on
    # wire (a perimeter corner, where every lane starts, apart)
    turns_min, starts_min = unidir_box_stats(rr)
    assert turns_min == 2
    assert starts_min == round(0.10 * 16)
    interior = [n for n in np.flatnonzero(wire)
                if 2 <= rr.xlow[n] and rr.xhigh[n] <= 7
                and 2 <= rr.ylow[n] and rr.yhigh[n] <= 7]
    out_deg = np.bincount(src[ww], minlength=rr.num_nodes)
    assert out_deg[interior].max() == 1 + 2 * sum(sb[1:])


def _without_edges(rr, drop):
    """A copy of the graph less the edges ``drop`` (bool over the
    out-CSR) marks: what a builder that lost them would have built."""
    src = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    keep = ~drop
    s, d, sw = src[keep], rr.out_dst[keep], rr.out_switch[keep]
    out_ptr = np.concatenate([[0], np.cumsum(np.bincount(
        s, minlength=rr.num_nodes))]).astype(np.int32)
    order = np.argsort(d, kind="stable")
    in_ptr = np.concatenate([[0], np.cumsum(np.bincount(
        d, minlength=rr.num_nodes))]).astype(np.int32)
    kw = {f: getattr(rr, f) for f in RRGraph.__dataclass_fields__}
    kw.update(out_row_ptr=out_ptr, out_dst=d, out_switch=sw,
              in_row_ptr=in_ptr, in_src=s[order].astype(np.int32),
              in_switch=sw[order], in_delay=np.zeros(len(d), np.float32))
    return RRGraph(**kw)


def test_check_rr_graph_refuses_the_old_unidir_box():
    """The rules today's graph passes and the old one broke in
    silence: a wire that turns only at its end (the old box, with sb
    all ones asked for), and an OPIN short of its Fc share of starts."""
    arch = _k6_l4()
    grid = DeviceGrid(nx=9, ny=9, io_capacity=arch.io_capacity)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rr = build_rr_graph(arch, grid, chan_width=16)
        end_only = build_rr_graph(_k6_l4((1, 0, 0, 0, 1)), grid,
                                  chan_width=16)
    check_rr_graph(rr, arch=arch)
    # the end-only graph, held to the all-ones pattern it was asked for
    end_only.sb_of_track = rr.sb_of_track
    with pytest.raises(AssertionError, match="no turn edge"):
        check_rr_graph(end_only, arch=arch)
    # one OPIN of an interior cluster loses a start
    src = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    o = rr.opin_of[(4, 4, 0, arch.I)]
    drop = np.zeros(rr.num_edges, bool)
    drop[np.flatnonzero(src == o)[0]] = True
    with pytest.raises(AssertionError, match="Fc share"):
        check_rr_graph(_without_edges(rr, drop), arch=arch)
    # a turn that lands on a wire's middle (the old box chose targets
    # by track index, whether or not the track starts at the corner)
    bad = _without_edges(rr, drop & False)
    wire = (bad.node_type == CHANX) | (bad.node_type == CHANY)
    e = next(i for i in range(bad.num_edges)
             if wire[src[i]] and bad.node_type[src[i]] == CHANX
             and bad.node_type[bad.out_dst[i]] == CHANY)
    tgt = int(bad.out_dst[e])
    nxt = next(int(n) for n in np.flatnonzero(bad.node_type == CHANY)
               if bad.ptc[n] == bad.ptc[tgt] and bad.xlow[n] ==
               bad.xlow[tgt] and n != tgt)
    bad.out_dst = bad.out_dst.copy()
    bad.out_dst[e] = nxt
    with pytest.raises(AssertionError):
        check_rr_graph(bad, arch=arch, reachability=False)


def test_unidir_width_rounds_to_whole_groups():
    """Unidir W rounds up to a multiple of twice the longest segment:
    even, and every turn group whole."""
    for length, asked, built in ((1, 13, 14), (2, 14, 16), (4, 12, 16),
                                 (4, 64, 64)):
        arch = unidir_arch(chan_width=asked, length=length)
        grid = DeviceGrid(nx=4, ny=4, io_capacity=arch.io_capacity)
        rr = build_rr_graph(arch, grid, chan_width=asked)
        assert rr.chan_width == built and rr.group_tracks == 2 * length


def test_unidir_odd_width_rounds_even():
    arch = unidir_arch(chan_width=13)
    grid = DeviceGrid(nx=4, ny=4, io_capacity=arch.io_capacity)
    rr = build_rr_graph(arch, grid, chan_width=13)
    assert rr.chan_width == 14


def test_unidir_mixed_directionality_rejected():
    arch = unidir_arch(chan_width=12)
    arch.segments.append(SegmentInf(name="b", directionality="bidir"))
    grid = DeviceGrid(nx=4, ny=4, io_capacity=arch.io_capacity)
    with pytest.raises(ValueError):
        build_rr_graph(arch, grid, chan_width=12)


def _k6_l4(sb=None, chan_width=16):
    """The published routing architecture at a tests' width, with
    another sb pattern as data."""
    arch = k6_n10_40nm_arch(chan_width=chan_width)
    if sb is not None:
        arch.segments[0].sb = tuple(sb)
    return arch


@pytest.mark.parametrize("arch,nx,ny,seed", [
    pytest.param(unidir_arch(chan_width=6), 4, 4, 0,
                 marks=pytest.mark.slow),
    pytest.param(_mixed_unidir(), 7, 7, 7, marks=pytest.mark.slow),
    pytest.param(_mixed_unidir(), 5, 9, 11, marks=pytest.mark.slow),
    # tier-1: length-4 wires, exits per sb, the published Fc, on a
    # grid wider than two spans
    (_k6_l4(), 9, 9, 13),
])
def test_unidir_planes_relax_matches_ell(arch, nx, ny, seed):
    """Directed-planes relaxation distances equal the ELL pull-relaxation
    over the directed CSR on random seeds/congestion/criticalities/boxes
    (same oracle pattern as the bidir test, on unidir graphs)."""
    grid = DeviceGrid(nx, ny, arch.io_capacity)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the 40nm file asks Wilton
        rr = build_rr_graph(arch, grid)
    dev = to_device(rr)
    pg = build_planes(rr)
    assert pg.directional
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
    dist_flat, pred, _, _ = planes_relax(
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


@pytest.mark.parametrize("length,luts,width", [
    pytest.param(1, 40, 14, marks=pytest.mark.slow),
    pytest.param(2, 40, 14, marks=pytest.mark.slow),
    # tier-1: length-4 wires on a 9 x 9 grid, wider than two spans
    (4, 150, 16),
])
def test_unidir_route_legal_deterministic(length, luts, width):
    arch = unidir_arch(chan_width=width, length=length)
    nl = generate_circuit(num_luts=luts, num_inputs=6 if luts == 40 else 10,
                          num_outputs=6 if luts == 40 else 10,
                          K=arch.K, seed=3)
    f = prepare(nl, arch, width, seed=5)
    if length == 4:
        f = run_place_native(f, seed=7)
        assert min(f.grid.nx, f.grid.ny) > 2 * length
    else:
        f = run_place(f, timing_driven=False)
    r1 = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    assert r1.success
    check_route(f.rr, f.term, r1.paths, occ=r1.occ)
    r2 = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    assert np.array_equal(r1.paths, r2.paths)
    # the serial oracle routes the same directed graph
    if length == 4:
        rs = NativeSerialRouter(f.rr).route(f.term)
        assert rs.success
        assert r1.wirelength <= 1.10 * rs.wirelength
    else:
        rs = SerialRouter(f.rr).route(f.term)
        assert rs.success


def _serial_wmin(sb, widths, luts=150):
    """(smallest of ``widths`` the serial router legalises at on the
    40nm architecture with this sb pattern, the placed problem at it)."""
    from parallel_eda_tpu.flow import synth_flow

    for W in widths:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            f = run_place_native(synth_flow(
                num_luts=luts, num_inputs=12, num_outputs=12, chan_width=W,
                seed=1, ff_ratio=0.4, arch=_k6_l4(sb, chan_width=W)),
                seed=7, inner_num=1.0)
        if NativeSerialRouter(f.rr).route(f.term).success:
            return W, f
    raise AssertionError(f"sb {sb}: not routable at any of {widths}")


def test_exits_per_sb_do_not_cost_routability():
    """The exits tied to routability, as data alone (two sb patterns of
    one architecture): the serial router's W_min with sb all ones is no
    larger than with the end-only pattern ``1 0 0 0 1``, and the device
    route is legal at the former's W_min + 8.  (At tseng's full size
    the patterns part: W_min 48 against 72, PERF.md PR 26; at this
    size the device's edge cuts most wires short and they tie.)"""
    widths = range(24, 73, 8)
    w_all, _ = _serial_wmin((1, 1, 1, 1, 1), widths)
    w_end, _ = _serial_wmin((1, 0, 0, 0, 1), widths)
    assert w_all <= w_end
    _, f = _serial_wmin((1, 1, 1, 1, 1), [w_all + 8])
    r = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    assert r.success
    check_route(f.rr, f.term, r.paths, occ=r.occ)


@pytest.mark.slow
def test_unidir_crit_path_parity():
    """BASELINE bar on a unidir (L=2) graph: device crit path within 1%
    of the serial oracle on the same placed problem."""
    arch = unidir_arch(chan_width=16, length=2)
    nl = array_multiplier(5)
    f = prepare(nl, arch, 16, seed=7)
    f = run_place(f)
    row = qor_compare(f, "mult5_unidir")
    assert row.cpd_delta_pct <= 1.0, (
        f"unidir crit path {row.device_cpd:.3e} vs serial "
        f"{row.serial_cpd:.3e} (+{row.cpd_delta_pct:.2f}%)")
    assert row.wl_delta_pct <= 15.0
