"""The plain reference against routings and relaxations with known
faults: it must refuse an over-used node and a dangling sink, and the
Dijkstra comparison must pass float32 planes and FAIL bfloat16 ones at
the limit ``correct`` uses."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import bench_cells
from benchmark import reference

LIMITS = bench_cells.load("benchmark/traffic/route_w20.json")["limits"]


def _line_graph():
    """SOURCE 0 -> OPIN 1 -> wire 2 -> wire 3 -> IPIN 4 -> SINK 5, and
    a second net's SOURCE 6 -> OPIN 7 -> wire 2 (shared) ... -> IPIN 8
    -> SINK 9 hanging off wire 3.  Every node has capacity 1."""
    R = reference
    node_type = np.array([R.SOURCE, R.OPIN, R.CHANX, R.CHANX, R.IPIN,
                          R.SINK, R.SOURCE, R.OPIN, R.IPIN, R.SINK])
    edges = [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5),
             (6, 7), (7, 2), (3, 8), (8, 9)]
    N = len(node_type)
    by_dst = sorted(edges, key=lambda e: e[1])
    in_src = np.array([s for s, _ in by_dst], np.int32)
    counts = np.bincount([d for _, d in by_dst], minlength=N)
    in_row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    in_delay = np.full(len(edges), 1e-10, np.float32)
    return reference.GraphArrays(node_type, np.ones(N, np.int16),
                                 in_row_ptr, in_src, in_delay)


def _paths(g, segs):
    """[R, 1, L] paths padded with the sentinel N."""
    N, L = g.num_nodes, max(len(s) for s in segs)
    out = np.full((len(segs), 1, L), N, np.int32)
    for r, s in enumerate(segs):
        out[r, 0, :len(s)] = s
    return out


def test_legal_routing_passes_and_delays_sum():
    g = _line_graph()
    src, sinks, ns = np.array([0]), np.array([[5]]), np.array([1])
    got = reference.check_legality(g, src, sinks, ns,
                                   _paths(g, [[5, 4, 3, 2, 1, 0]]))
    assert got["problems"] == [] and got["wirelength"] == 2
    d = reference.tree_sink_delays(g, src, sinks, ns, got["parents"])
    assert d[0, 0] == pytest.approx(5e-10, rel=1e-6)
    assert reference.sink_delay_gap(d, np.array([[5e-10]])) < 1e-6
    assert reference.sink_delay_gap(d, np.array([[5.01e-10]])) > 1e-3


def test_over_used_node_is_refused():
    g = _line_graph()
    src, sinks, ns = np.array([0, 6]), np.array([[5], [9]]), \
        np.array([1, 1])
    got = reference.check_legality(g, src, sinks, ns, _paths(
        g, [[5, 4, 3, 2, 1, 0], [9, 8, 3, 2, 7, 6]]))
    assert any("capacity" in p for p in got["problems"])
    assert got["occ"][2] == 2 and got["occ"][3] == 2


@pytest.mark.parametrize("seg,why", [
    ([5, 4, 3], "not connected"),           # stops short of the source
    ([], "no path"),                        # nothing routed at all
    ([5, 4, 2, 1, 0], "no rr edge"),        # hops over wire 3
    ([4, 3, 2, 1, 0], "not at its sink"),   # ends on the pin
])
def test_dangling_sink_is_refused(seg, why):
    g = _line_graph()
    got = reference.check_legality(
        g, np.array([0]), np.array([[5]]), np.array([1]),
        _paths(g, [seg or [g.num_nodes]]))
    assert any(why in p for p in got["problems"]), got["problems"]


@pytest.fixture(scope="module")
def canvas():
    """A 6x6 grid of the test architecture, two nets' cost fields."""
    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route.planes import build_planes
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import DeviceGrid

    arch = minimal_arch(chan_width=8)
    rr = build_rr_graph(arch, DeviceGrid(6, 6, arch.io_capacity))
    g = reference.GraphArrays.of(rr)
    rng = np.random.default_rng(23)
    wire = (g.node_type == reference.CHANX) | (g.node_type == reference.CHANY)
    B, N = 2, g.num_nodes
    crit = np.array([[0.0], [0.7]], np.float32)
    cong = (1 - crit) * rng.uniform(0.5, 2.0, (B, N)).astype(
        np.float32) * 1e-10
    seeds = [rng.choice(np.flatnonzero(wire), 2, replace=False)
             for _ in range(B)]
    ref = [reference.dijkstra_wire_dist(g, seeds[b], cong[b],
                                        float(crit[b, 0]))
           for b in range(B)]
    return rr, build_planes(rr), wire, crit, cong.astype(np.float32), \
        seeds, ref


def _relax(canvas, plane_dtype):
    from parallel_eda_tpu.route.planes import planes_relax

    rr, pg, wire, crit, cong, seeds, ref = canvas
    B, N = cong.shape
    noc, con = np.asarray(pg.node_of_cell), np.asarray(pg.cell_of_node)
    d0 = np.full((B, N), np.inf, np.float32)
    for b in range(B):
        d0[b, seeds[b]] = 0.0
    dist, _, _, stats = planes_relax(
        pg, jnp.asarray(d0[:, noc]), jnp.asarray(cong[:, noc]),
        jnp.asarray(crit)[:, :, None, None],
        jnp.zeros((B, pg.ncells), jnp.float32), 64,
        plane_dtype=plane_dtype)
    assert int(np.asarray(stats)[0]) < 64       # a fixpoint
    got = np.full((B, N), np.inf)
    got[:, wire] = np.asarray(dist)[:, con[wire]]
    return max(reference.relax_gap(ref[b], got[b]) for b in range(B))


def test_f32_relaxation_reaches_dijkstra(canvas):
    assert _relax(canvas, "f32") <= LIMITS["relax_gap"]


def test_bf16_relaxation_fails_the_same_limit(canvas):
    assert _relax(canvas, "bf16") > LIMITS["relax_gap"]


def test_limits_file_is_what_the_cells_use():
    for rel in ("benchmark/traffic/route_w20.json",
                "benchmark/traffic/small_heavy_open.json"):
        with open(os.path.join(bench_cells.REPO, rel)) as fh:
            assert json.load(fh)["limits"]["sink_delay_gap"] == \
                LIMITS["sink_delay_gap"]
