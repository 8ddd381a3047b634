"""The reader of ``negotiation.discarded_sweep_share`` and its entry in
the manifest (one-way checks only: a later cell appended to its list
needs no edit of this file)."""

import types

import pytest

import bench_cells
from benchmark import harness

REPO = bench_cells.REPO
CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
         "route_scale", "route_hetero"]
NAME = "negotiation.discarded_sweep_share"


def _ctx(**kv):
    route = types.SimpleNamespace(**kv)
    return {"routes": [route, route]}


@pytest.mark.parametrize("ctx, want", [
    # route_hetero before this counter's PR: windows 6-9 of 9 thrown away
    (_ctx(total_relax_steps=15058, total_relax_steps_discarded=11276),
     100.0 * 11276 / 15058),
    (_ctx(total_relax_steps=8000, total_relax_steps_discarded=2000), 25.0),
    # the finished route is kept, or no finishing pass ran
    (_ctx(total_relax_steps=5029, total_relax_steps_discarded=0), 0.0),
    # a program from before the counter (the parent)
    (_ctx(total_relax_steps=5029), None),
    # a route that counts nothing
    (_ctx(total_relax_steps=0, total_relax_steps_discarded=0), None),
    ({"routes": []}, None),
    ({}, None),
])
def test_discarded_sweep_share_reader(ctx, want):
    reader = harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), NAME))
    assert reader.read(ctx) == want


def test_the_manifest_lists_the_metric_for_the_route_cells():
    manifest = harness.load_manifest(REPO)
    m = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert set(CELLS) <= set(m["workloads"])
    assert (m["layer"], m["moves"], m["unit"], m["better"], m["source"]) == (
        "negotiation driver", "route_s", "%", "lower", "program_counter")
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(m["workloads"]) <= cells
