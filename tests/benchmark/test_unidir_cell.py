"""A tiny cell on the published single-driver routing architecture
(length-4 one-way wires, Fc 0.15 / 0.10) through ``harness.run_cell``
on the CPU: the sound run is ``correct``, the lower-precision control
and an altered route are not; and the plain reference is held to the
DIRECTION of an edge on a two-wire one-way graph."""

import json
import os

import numpy as np
import pytest

import bench_cells
from benchmark import harness, problem, reference

CONFIG = "benchmark/configs/mcnc_tseng_like_k6n10_l4.json"
TRAFFIC = "benchmark/traffic/route_k6n10_relaxed.json"


@pytest.fixture(scope="module")
def l4_cell(tmp_path_factory):
    """The real configuration and traffic files at 60 LUTs (a 3 x 3
    grid), W = 32, under a manifest of their own."""
    root = str(tmp_path_factory.mktemp("l4_cell"))
    name = bench_cells.write_cell(root, "route")
    cfg = bench_cells.load(CONFIG)
    cfg["circuit"].update(num_luts=60, num_inputs=8, num_outputs=8)
    cfg["router"]["opts"]["batch_size"] = 32
    traffic = bench_cells.load(TRAFFIC)
    traffic.update(chan_width=32, relax_sample_nets=3, trace_offset_s=0,
                   trace_seconds=0.5)
    cells = os.path.join(root, "cells")
    for rel, obj in (("configs/tiny_k4n4.json", cfg),
                     ("traffic/tiny_w12.json", traffic)):
        with open(os.path.join(cells, rel), "w") as fh:
            json.dump(obj, fh)
    cell = harness.load_cell(harness.load_manifest(root), root, name)
    f = problem.build_placed(cell, 32)
    assert f.rr.unidir and f.rr.group_tracks == 8
    traffic["problem_sha256"] = problem.fingerprint(f)
    with open(os.path.join(cells, "traffic/tiny_w12.json"), "w") as fh:
        json.dump(traffic, fh)
    return root, name


def _run(l4_cell, tmp_path, **kw):
    root, name = l4_cell
    return harness.run_cell(root, name, seed=2**31 + 26, seconds=1.0,
                            work_dir=str(tmp_path), **kw)


def _failed_checks(out):
    return [ln.split(":")[0][len("check "):] for ln in out.splitlines()
            if ln.startswith("check ") and ln.endswith("NOT ok")]


def test_l4_unidir_cell_is_correct(l4_cell, tmp_path, capsys):
    result = _run(l4_cell, tmp_path, trace=True)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True, _failed_checks(
        capsys.readouterr().out)
    assert result["attempted"] >= 1 and result["failed"] == 0
    counts = result["rehearsal"]["counts"]
    assert counts["window.sweeps"] >= counts["negotiation.iterations"] >= 1
    # sweeps a wave: at least one, and a wave converges well inside
    # the sweep ceiling
    assert 1.0 <= counts["window.sweeps_per_wave"] < 64.0


def test_l4_unidir_control_bf16_is_not_correct(l4_cell, tmp_path, capsys):
    result = _run(l4_cell, tmp_path, trace=False, router_overrides={
        "plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    failed = _failed_checks(capsys.readouterr().out)
    assert "sink_delay_gap" in failed and "relax_gap" in failed


def test_l4_unidir_altered_route_is_not_correct(l4_cell, tmp_path,
                                                monkeypatch):
    """A route that walks a one-way wire pair against its direction:
    the first two wires of one path swapped after every run_route."""
    from parallel_eda_tpu import flow as F

    real = F.run_route

    def against_the_wires(f, *a, **kw):
        out = real(f, *a, **kw)
        paths = np.array(f.route.paths)
        wire = (f.rr.node_type == reference.CHANX) | (
            f.rr.node_type == reference.CHANY)
        N = f.rr.num_nodes
        for r, s in np.argwhere(f.term.sinks >= 0):
            p = paths[r, s]
            w = [i for i in range(len(p)) if p[i] < N and wire[p[i]]]
            if len(w) >= 2:
                p[w[0]], p[w[1]] = p[w[1]], p[w[0]]
                break
        f.route.paths = paths
        return out

    monkeypatch.setattr(F, "run_route", against_the_wires)
    result = _run(l4_cell, tmp_path, trace=False)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1


def _one_way_pair():
    """SOURCE 0 -> OPIN 1 -> wire 2 -> wire 3 -> IPIN 4 -> SINK 5, and
    a tap IPIN 6 -> SINK 7 on wire 2: wire 3 cannot be left backwards."""
    R = reference
    node_type = np.array([R.SOURCE, R.OPIN, R.CHANX, R.CHANX, R.IPIN,
                          R.SINK, R.IPIN, R.SINK])
    edges = sorted([(0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (2, 6),
                    (6, 7)], key=lambda e: e[1])
    N = len(node_type)
    counts = np.bincount([d for _, d in edges], minlength=N)
    return reference.GraphArrays(
        node_type, np.ones(N, np.int16),
        np.concatenate([[0], np.cumsum(counts)]).astype(np.int32),
        np.array([s for s, _ in edges], np.int32),
        np.full(len(edges), 1e-10, np.float32))


def test_reference_follows_out_edges_only():
    """Dijkstra never walks an edge backwards, and a path that does is
    refused: the reference reads ``in_src`` as the edge's SOURCE."""
    g = _one_way_pair()
    cong = np.full(g.num_nodes, 1e-10)
    fwd = reference.dijkstra_wire_dist(g, [2], cong, 0.5)
    assert np.isfinite(fwd[3]) and fwd[2] == 0.0
    back = reference.dijkstra_wire_dist(g, [3], cong, 0.5)
    assert back[3] == 0.0 and np.isinf(back[2])
    assert reference.relax_gap(fwd, np.where(np.isinf(back), fwd, back)) > 0

    src, sinks, ns = np.array([0]), np.array([[7]]), np.array([1])
    N = g.num_nodes

    def path(nodes):
        out = np.full((1, 1, 8), N, np.int32)
        out[0, 0, :len(nodes)] = nodes
        return out

    ok = reference.check_legality(g, src, sinks, ns,
                                  path([7, 6, 2, 1, 0]))
    assert ok["problems"] == []
    # the same sink reached "through" wire 3 and back onto wire 2
    bad = reference.check_legality(g, src, sinks, ns,
                                   path([7, 6, 2, 3, 1, 0]))
    assert any("no rr edge" in p for p in bad["problems"])
