"""The six readers of the window ledger (``RouteResult.stats`` rows
with ``kind`` and ``kept``) on hand-made contexts, and their entries in
the manifest (one-way checks only: a later cell appended to a list
needs no edit of this file).

The fixture is ``route_hetero``'s route by window as PERF.md section 5
records it (PR 33, the change): five windows of negotiation, legal at
16; the finishing pass; three windows that do not re-legalise it; the
snapshot restored, so windows 6-9 are thrown away."""

import types

import pytest

import bench_cells
from benchmark import harness

REPO = bench_cells.REPO
CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
         "route_scale", "route_hetero"]
# window: kind, seconds, sweeps, kept
HETERO = [("first", 4.595, 1278, True), ("negotiate", 2.997, 723, True),
          ("negotiate", 2.978, 770, True), ("negotiate", 2.817, 685, True),
          ("negotiate", 1.236, 326, True), ("finish", 12.961, 5253, False),
          ("relegalise", 3.395, 1277, False),
          ("relegalise", 1.228, 340, False),
          ("relegalise", 1.227, 340, False)]
# route_scale (PR 33): the restart is window 6 of 7, everything kept
SCALE = [("first", 4.181, 1428, True), ("negotiate", 1.928, 597, True),
         ("negotiate", 1.400, 371, True), ("negotiate", 1.373, 333, True),
         ("negotiate", 0.613, 224, True), ("restart", 8.665, 4045, True),
         ("negotiate", 0.341, 40, True)]
NEW = {
    "negotiation.restart_s": ("s", "negotiation driver"),
    "negotiation.finish_pass_s": ("s", "negotiation driver"),
    "negotiation.discarded_s": ("s", "negotiation driver"),
    "negotiation.outside_window_share": ("%", "negotiation driver"),
    "window.negotiate_us_per_sweep": ("us", "window program"),
    "device.window_time_ratio_max": ("x", "device"),
}
# the two that read only what a row always had
OLD_ROWS_TOO = ("negotiation.outside_window_share",
                "device.window_time_ratio_max")


def _route(table, ledger=True, scale=()):
    """A result whose rows are ``table``'s; ``scale`` stretches single
    windows (index -> factor); without ``ledger`` the rows are those of
    a program from before it."""
    rows = []
    for i, (kind, seconds, sweeps, kept) in enumerate(table):
        row = types.SimpleNamespace(
            route_time_s=seconds * dict(scale).get(i, 1.0),
            relax_steps=sweeps)
        if ledger:
            row.kind, row.kept = kind, kept
        rows.append(row)
    return types.SimpleNamespace(stats=rows)


def _ctx(*routes, times=None):
    return {"routes": list(routes), "route_times": times or [
        1.01 * sum(s.route_time_s for s in r.stats) for r in routes]}


def _read(name, ctx):
    reader = harness.load_module(harness.find_reader(
        harness.search_dirs(harness.load_manifest(REPO), REPO), name))
    return reader.read(ctx)


HETERO_CTX = _ctx(_route(HETERO), _route(HETERO), times=[33.80, 33.79])
SCALE_CTX = _ctx(_route(SCALE), _route(SCALE), times=[18.837, 18.84])


@pytest.mark.parametrize("name, ctx, want", [
    ("negotiation.restart_s", HETERO_CTX, 0.0),
    ("negotiation.finish_pass_s", HETERO_CTX, 12.961),
    ("negotiation.discarded_s", HETERO_CTX, 18.811),
    ("negotiation.outside_window_share", HETERO_CTX,
     100.0 * (33.80 - 33.434) / 33.80),
    ("window.negotiate_us_per_sweep", HETERO_CTX, 1e6 * 14.623 / 3782),
    ("device.window_time_ratio_max", HETERO_CTX, 1.0),
    ("negotiation.restart_s", SCALE_CTX, 8.665),
    ("negotiation.finish_pass_s", SCALE_CTX, 0.0),
    ("negotiation.discarded_s", SCALE_CTX, 0.0),
    ("negotiation.outside_window_share", SCALE_CTX,
     100.0 * (18.837 - 18.501) / 18.837),
    # 9.836 s over 2,993 sweeps, where the restart alone reads 2,142
    ("window.negotiate_us_per_sweep", SCALE_CTX, 1e6 * 9.836 / 2993),
])
def test_the_readers_on_the_recorded_tables(name, ctx, want):
    assert _read(name, ctx) == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_the_hetero_fixture_is_the_recorded_table():
    assert sum(s for _, s, _, _ in HETERO) == pytest.approx(33.434)
    assert sum(n for _, _, n, _ in HETERO) == 10992
    assert sum(n for _, _, n, kept in HETERO if not kept) == 7210
    assert sum(s for _, s, _, _ in SCALE) == pytest.approx(18.501)
    assert sum(n for _, _, n, _ in SCALE) == 7038


@pytest.mark.parametrize("name", sorted(NEW))
def test_rows_without_a_kind(name):
    """The parent's rows: four readers say nothing, the two that read
    seconds only still give their number."""
    ctx = _ctx(_route(HETERO, ledger=False), _route(HETERO, ledger=False),
               times=[33.80, 33.79])
    got = _read(name, ctx)
    if name in OLD_ROWS_TOO:
        assert got == pytest.approx(_read(name, HETERO_CTX))
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("ctx", [{}, {"routes": []},
                                 _ctx(types.SimpleNamespace(stats=[]))],
                         ids=["empty", "no-routes", "no-rows"])
def test_nothing_to_read(name, ctx):
    assert _read(name, ctx) is None


def test_the_slow_window_of_one_route_shows():
    """PR 30's slow route: the sixth window 11.94 s where the other
    routes' took 9.77 s, every other window the same."""
    table = SCALE[:5] + [("restart", 9.77, 4045, True)] + SCALE[6:]
    quiet = _route(table)
    slow = _route(table, scale={5: 11.94 / 9.77})
    name = "device.window_time_ratio_max"
    assert _read(name, _ctx(quiet, slow, quiet)) == pytest.approx(
        11.94 / 9.77)
    assert round(_read(name, _ctx(slow, quiet)), 2) == 1.22
    # windows that differ by their noise only
    near = _route(table, scale={0: 1.0015, 3: 0.9995})
    assert _read(name, _ctx(quiet, near)) == pytest.approx(1.0015)
    # one route, or routes of unequal row counts: nothing to compare
    assert _read(name, _ctx(quiet)) is None
    assert _read(name, _ctx(quiet, _route(table[:6]))) is None
    # the first route alone feeds the other five
    assert _read("negotiation.restart_s",
                 _ctx(quiet, slow)) == pytest.approx(9.77)
    assert _read("negotiation.restart_s",
                 _ctx(slow, quiet)) == pytest.approx(11.94)


def test_a_route_whose_negotiation_ran_no_sweep():
    idle = _route([("first", 0.5, 0, True), ("restart", 1.0, 9, True)])
    assert _read("window.negotiate_us_per_sweep", _ctx(idle)) is None
    assert _read("negotiation.restart_s", _ctx(idle)) == 1.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_metric_for_the_route_cells(name):
    manifest = harness.load_manifest(REPO)
    m = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert set(CELLS) <= set(m["workloads"])
    unit, layer = NEW[name]
    assert (m["unit"], m["layer"], m["moves"], m["better"], m["source"]) == (
        unit, layer, "route_s", "lower", "program_span")
    assert set(m["workloads"]) <= {w["name"] for w in manifest["workloads"]}
    assert harness.find_reader(
        harness.search_dirs(manifest, REPO), name).endswith(name + ".py")


def test_the_window_report_tool_on_a_tiny_cell(tmp_path):
    """``tools/window_report.py`` on the CPU at a tiny size: the table,
    the wall of every timed route, and under a tracer the stages of
    ``flow.run_route`` around it."""
    import os

    root = str(tmp_path / "cell")
    name = bench_cells.write_cell(root, "route")
    tool = harness.load_module(os.path.join(REPO, "tools",
                                            "window_report.py"))
    text, rec = tool.report(root, name, seed=2**31 + 5, seconds=1.0,
                            tracer=True, work_dir=str(tmp_path / "work"))
    assert rec["correct"] is True and rec["device"]["platform"] == "cpu"
    assert rec["windows"][0]["kind"] == "first"
    assert [w["window"] for w in rec["windows"]] == list(
        range(1, len(rec["windows"]) + 1))
    lines = text.splitlines()
    assert lines[2].split()[:3] == ["window", "iter", "kind"]
    for row in rec["routes"]:
        assert row["wall_s"] == pytest.approx(
            row["prologue_s"] + row["windows_s"] + row["control_s"]
            + row["epilogue_s"])
        # the wall is the route stage, and with the set-up before it and
        # the STA after it flow.run_route's whole call
        assert abs(row["wall_s"] - row["stage_s"]) <= max(
            1e-3, 1e-3 * row["stage_s"])
        assert row["route_s"] == pytest.approx(
            row["setup_s"] + row["stage_s"] + row["sta_s"], abs=5e-3)
    assert len(rec["window_seconds_max_over_min"]) == len(rec["windows"])
    assert rec["counters"]["route.window.count_total.first"] == (
        len(rec["routes"]) + 1)          # the warm-up route too
