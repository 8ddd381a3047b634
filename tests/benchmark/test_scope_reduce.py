"""scope_reduce on the small hand-written trace (the answer worked out
in the fixture), on a real XLA:CPU trace of the tiny route cell (the
op_name looked up in the HLO module the trace stores), and the two
readers of the program's dispatch counters on a rehearsed cell."""

import os

import pytest

import bench_cells
from benchmark import harness, scope_reduce

FIXTURE = bench_cells.load("benchmark/fixtures/three_scopes_one_gap.json")
TOOL = os.path.join(bench_cells.REPO, "benchmark", "tools",
                    "scope_trace.py")


def test_fixture_reduces_to_the_hand_worked_numbers():
    red = scope_reduce.reduce(FIXTURE["planes"])
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(750e-9)
    assert red["self_s"] == pytest.approx(750e-9)
    assert red["idle_share"] == pytest.approx(0.25)
    assert red["n_device_events"] == 6
    assert {r[0]: r[1] for r in red["scopes"]} == pytest.approx({
        "route.dev.relax": 400e-9, "route.dev.cost_fields": 200e-9,
        "route.dev.traceback": 100e-9, scope_reduce.UNSCOPED: 50e-9})
    assert [r[0] for r in red["scopes"]][0] == "route.dev.relax"
    assert sum(r[2] for r in red["scopes"]) == pytest.approx(100.0)
    # the while is charged only what its scoped body leaves
    assert {r[0]: r[1] for r in red["nested"]["route.dev.relax"]} == \
        pytest.approx({"route.dev.relax.scan": 150e-9,
                       "route.dev.relax.turn": 100e-9})
    assert red["unscoped_share"] == pytest.approx(100 * 50 / 750)
    assert red["unscoped_ops"] == [["copy.9", pytest.approx(50e-9),
                                    pytest.approx(100 * 50 / 750)]]
    # the innermost of the four host spans that cover the gap
    assert red["idle_gaps"] == [
        ["route.pipeline.stall", pytest.approx(250e-9),
         {"route.pipeline.stall": pytest.approx(250e-9)}]]
    assert red["host_spans"]["route.window"] == 1
    text = scope_reduce.table(red)
    assert "route.dev.relax.scan" in text and "100.000%" in text


def test_a_gap_outside_every_span_and_no_device_plane():
    host = FIXTURE["planes"][1]
    red = scope_reduce.reduce([host])
    assert red["scopes"] == [] and red["idle_gaps"] == []
    assert red["busy_s"] == 0.0 and red["unscoped_share"] == 0.0
    bare = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        e for e in host["lines"][0]["events"]
        if e[0] == "bench.traced_window"]}]}
    red = scope_reduce.reduce([FIXTURE["planes"][0], bare])
    assert red["idle_gaps"] == [[
        scope_reduce.UNNAMED, pytest.approx(250e-9),
        {scope_reduce.UNNAMED: pytest.approx(250e-9)}]]
    # a gap partly under an inner span: each instant to its innermost
    # span, the name to the one that owns most
    host2 = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.traced_window", 0, 1000], ["bench.route", 0, 1000],
        ["route.pipeline.control", 650, 100],
        ["route.pipeline.plan", 750, 40]]}]}
    red = scope_reduce.reduce([FIXTURE["planes"][0], host2])
    assert red["idle_gaps"] == [["bench.route", pytest.approx(250e-9), {
        "bench.route": pytest.approx(110e-9),
        "route.pipeline.control": pytest.approx(100e-9),
        "route.pipeline.plan": pytest.approx(40e-9)}]]


def test_scope_of_an_op_name():
    assert scope_reduce.scope_of(
        "jit(f)/while/body/closed_call/route.dev.relax/while/body/"
        "route.dev.relax.scan/slice:") == \
        "route.dev.relax/route.dev.relax.scan"
    assert scope_reduce.scope_of("jit(f)/while/cond/lt") == ""
    assert scope_reduce.top_level("route.dev.relax.scan") == \
        "route.dev.relax"


@pytest.fixture(scope="module")
def route_cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("scope_cell")
    return str(root), bench_cells.write_cell(
        str(root), "route", trace_seconds=1.0)


def test_a_real_cpu_trace_reads_as_scopes_and_program_spans(
        route_cell, tmp_path, monkeypatch):
    """The tool's Tracing around the tiny cell on XLA:CPU: op events
    name ``hlo_op`` and ``program_id``, the scope comes from the HLO
    module stored in the trace, and the gaps fall in the router's own
    spans -- with no Tracer installed."""
    tool = harness.load_module(TOOL)
    monkeypatch.setattr(harness, "Tracing", tool.ScopeTracing)
    root, name = route_cell
    result = harness.run_cell(root, name, seed=7, seconds=1.0,
                              trace=True, work_dir=str(tmp_path))
    assert result["correct"] is True
    red = tool.ScopeTracing.found
    assert red and red["n_device_events"] > 100
    tops = {r[0]: r[2] for r in red["scopes"]}
    assert sum(tops.values()) == pytest.approx(100.0)
    assert {"route.dev.relax", "route.dev.cost_fields",
            "route.dev.traceback"} <= set(tops)
    assert {r[0] for r in red["nested"]["route.dev.relax"]} >= {
        "route.dev.relax.scan", "route.dev.relax.turn"}
    # what the compiler made without a name stays a minority
    assert red["unscoped_share"] < 25.0, red["unscoped_ops"]
    # the longest gaps lie in the router's own spans: inside a window's
    # pipeline spans, or inside the route and between two windows
    named = {g[0] for g in red["idle_gaps"]}
    assert named and named <= {
        "bench.route", "route", "route.window", "route.pipeline.plan",
        "route.pipeline.dispatch", "route.pipeline.stall",
        "route.pipeline.control", scope_reduce.UNNAMED}
    assert red["host_spans"]["route.pipeline.dispatch"] >= 1
    assert any(n == "route" or n.startswith("route.") for n in named), \
        red["idle_gaps"]


def test_the_dispatch_readers_on_a_rehearsed_cell(route_cell, tmp_path):
    """Off the chip the harness withholds every time from the result
    line, so the readers are called on the driver's own ctx."""
    root, name = route_cell
    manifest = harness.load_manifest(root)
    cell = harness.load_cell(manifest, root, name)
    driver = harness.load_module(cell.find(
        "drivers", cell.traffic["driver"], ".py"))
    work = harness.fresh_dir(str(tmp_path), name)
    import time
    out = driver.run(cell, harness.Env(
        seed=5, seconds=0.5, tracing=harness.Tracing(False, work),
        t_start=time.perf_counter(), work_dir=work))
    ctx = out.ctx
    per_window = harness.load_module(harness.find_reader(
        cell.search, "negotiation.dispatch_ms_per_window")).read(ctx)
    first_s = harness.load_module(harness.find_reader(
        cell.search, "setup.first_dispatch_s")).read(ctx)
    gauges = ctx["pipeline_gauges"][0]
    windows = len(ctx["routes"][0].stats)
    assert per_window == pytest.approx(
        gauges["route.pipeline.dispatch_ms_total"] / windows)
    assert 0.0 < per_window * windows <= (
        gauges["route.pipeline.host_plan_ms_total"]
        + gauges["route.pipeline.stall_ms_total"])
    assert first_s == pytest.approx(
        ctx["registry"]["route.dispatch.first_call_ms_total"] / 1e3)
    assert first_s >= 0.0
    # a program without the counters (the parent commit): nothing to
    # read, and nothing raised
    old = dict(ctx, registry={}, pipeline_gauges=[{}])
    for metric in ("negotiation.dispatch_ms_per_window",
                   "setup.first_dispatch_s"):
        reader = harness.load_module(harness.find_reader(
            cell.search, metric))
        assert reader.read(old) is None
        assert reader.read({}) is None
