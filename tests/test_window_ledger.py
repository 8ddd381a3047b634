"""The window ledger: every ``RouteStats`` row of the planes window
driver says what KIND of window it is and what it cost, the route's
wall closes over named spans (``RouteResult.wall``,
``FlowResult.times``), and the counters by kind add up to the rows.

The fixtures are ``tests/test_endgame.py``'s circuit (40 LUTs, 38 nets)
at the three settings that reach a finishing pass, a restored snapshot
and a phase-2 restart."""

import dataclasses
import itertools

import numpy as np
import pytest

from parallel_eda_tpu.flow import run_route, synth_flow
from parallel_eda_tpu.obs import Tracer, get_metrics, set_tracer
from parallel_eda_tpu.route import Router, RouterOpts
from parallel_eda_tpu.route.report import format_window_table
from parallel_eda_tpu.route.router import (WINDOW_KINDS, RouteStats,
                                           _window_kind, write_stats_files)

FIXTURES = {
    # legal at 11, the pass, three nets re-legalise it: kept
    "pass_kept": (9, {}),
    # the pass's window ends two nodes over at the cap: snapshot restored
    "pass_restored": (9, {"max_router_iterations": 16}),
    # one node over after window 4: a restart before any legal window
    "restart": (8, {}),
}
KINDS = {
    "pass_kept": ["first", "negotiate", "negotiate", "negotiate",
                  "finish", "relegalise"],
    "pass_restored": ["first", "negotiate", "negotiate", "negotiate",
                      "finish"],
    "restart": ["first", "negotiate", "negotiate", "negotiate",
                "restart", "negotiate"],
}
COUNTERS = [f"route.window.{what}_total.{kind}" for what in (
    "seconds", "sweeps", "count") for kind in WINDOW_KINDS] + [
    "route.window.discarded_seconds_total"]


def _flow(name, traced, resume=None, **more):
    """One fixture routed through ``flow.run_route``: the flow, the
    tracer's events (None untraced) and what the counters gained."""
    chan_width, opts = FIXTURES[name]
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                   chan_width=chan_width, seed=3)
    reg = get_metrics()
    before = {k: reg.counter(k).value for k in COUNTERS}
    tr = Tracer() if traced else None
    set_tracer(tr)
    try:
        if resume is None:
            run_route(f, RouterOpts(batch_size=32, **opts, **more),
                      timing_driven=False)
        else:
            f.route = Router(f.rr, RouterOpts(batch_size=32)).route(
                f.term, resume=resume)
    finally:
        set_tracer(None)
    gained = {k: reg.counter(k).value - v for k, v in before.items()}
    return f, (tr.events if traced else None), gained


@pytest.fixture(scope="module", params=list(FIXTURES))
def routed(request):
    name = request.param
    traced = _flow(name, True)
    plain = _flow(name, False)
    return name, traced, plain


def _kind_table():
    for widx, force_all, restarted, finished in itertools.product(
            (1, 2, 5, 9), (False, True), (False, True), (False, True)):
        if force_all and restarted and finished:
            continue        # the two full rebuilds exclude each other
        if widx == 1:
            want = "first"
        elif force_all and finished:
            want = "finish"
        elif force_all and restarted:
            want = "restart"
        elif finished:
            want = "relegalise"
        else:
            want = "negotiate"
        yield pytest.param(widx, force_all, restarted, finished, want,
                           id="w%d-all%d-r%d-f%d" % (
                               widx, force_all, restarted, finished))


@pytest.mark.parametrize("widx, force_all, restarted, finished, want",
                         list(_kind_table()))
def test_window_kind_rule(widx, force_all, restarted, finished, want):
    got = _window_kind(widx, force_all, restarted, finished)
    assert got == want and got in WINDOW_KINDS


def test_window_kind_the_cases_that_matter():
    assert WINDOW_KINDS == ("first", "negotiate", "restart", "finish",
                            "relegalise")
    assert _window_kind(1, False, False, False) == "first"
    # the control step that fired the restart / started the pass
    assert _window_kind(6, True, True, False) == "restart"
    assert _window_kind(6, True, False, True) == "finish"
    # and the windows after each
    assert _window_kind(7, False, True, False) == "negotiate"
    assert _window_kind(7, False, False, True) == "relegalise"


def test_one_row_a_window_and_the_kinds_in_order(routed):
    name, (f, _, _), _ = routed
    rows = f.route.stats
    assert [s.window for s in rows] == list(range(1, len(rows) + 1))
    kinds = [s.kind for s in rows]
    assert kinds == KINDS[name]
    assert set(kinds) <= set(WINDOW_KINDS)
    assert kinds[0] == "first" and kinds.count("first") == 1
    assert kinds.count("restart") <= 1 and kinds.count("finish") <= 1
    if "relegalise" in kinds:
        assert kinds.index("finish") < kinds.index("relegalise")
    # the schedule a window ran under: the two rebuilds are precise
    assert all(s.precise for s in rows
               if s.kind in ("restart", "finish", "relegalise"))
    assert not rows[0].precise and rows[0].sweep_boost == 1
    assert all(s.sweep_boost in (1, 2, 4) for s in rows)


def test_the_fixtures_cover_the_vocabulary():
    assert {k for kinds in KINDS.values() for k in kinds} == set(
        WINDOW_KINDS)


def test_the_rows_add_up_to_the_result(routed):
    _, (f, _, _), _ = routed
    r = f.route
    for field, total in (("relax_steps", r.total_relax_steps),
                         ("waves", r.total_waves),
                         ("net_routes", r.total_net_routes),
                         ("relax_steps_cropped",
                          r.total_relax_steps_cropped),
                         ("waves_cropped", r.total_waves_cropped),
                         ("cell_sweeps", r.total_cell_sweeps)):
        assert sum(getattr(s, field) for s in r.stats) == total, field
    assert r.total_relax_steps > 0 and r.total_waves > 0
    # ... so every sweep covered the whole 5 x 5 canvas, at a width of
    # at most the batch and at least the narrowest plan
    cells = f.route.total_cell_sweeps / r.total_relax_steps
    assert 8 * _grid_cells(f) <= cells <= 32 * _grid_cells(f)
    # a 5 x 5 grid has no crop rung: no cropped relaxation was called
    assert r.total_waves_cropped == r.total_relax_steps_cropped == 0
    for s in r.stats:
        assert 0 <= s.stall_s <= s.route_time_s
        assert 0 <= s.plan_s <= s.route_time_s
        assert 0 <= s.dispatch_ms <= s.plan_s * 1e3
        assert s.control_s > 0


def test_kept_is_false_exactly_past_the_restored_snapshot(routed):
    name, (f, _, _), _ = routed
    r = f.route
    kept = [s.kept for s in r.stats]
    if name == "pass_restored":
        assert kept == [True, True, True, True, False]
        assert r.iterations == 11 and r.success
        assert [s.iteration > r.iterations for s in r.stats] == [
            not k for k in kept]
    else:
        assert all(kept)
    assert r.total_relax_steps_discarded == sum(
        s.relax_steps for s in r.stats if not s.kept)
    assert r.total_relax_steps_discarded == (
        224 if name == "pass_restored" else 0)


def test_the_wall_closes_over_the_named_intervals(routed):
    for f, _, _ in routed[1:]:
        r, wall = f.route, f.route.wall
        assert set(wall) == {"prologue_s", "windows_s", "control_s",
                             "epilogue_s"}
        assert wall["windows_s"] == pytest.approx(
            sum(s.route_time_s for s in r.stats), abs=1e-9)
        assert wall["control_s"] == pytest.approx(
            sum(s.control_s for s in r.stats), abs=1e-9)
        assert min(wall.values()) > 0
        stage = f.times["route"]
        assert abs(sum(wall.values()) - stage) <= max(1e-3, 1e-3 * stage)
        # what flow.run_route does around the route is named too
        assert f.times["route.setup"] > 0 and f.times["route.verify"] > 0
        assert "route.sta" not in f.times     # not timing-driven here


def test_the_counters_by_kind_equal_the_rows(routed):
    for f, _, gained in routed[1:]:
        rows = f.route.stats
        for kind in WINDOW_KINDS:
            mine = [s for s in rows if s.kind == kind]
            assert gained[f"route.window.count_total.{kind}"] == len(mine)
            assert gained[f"route.window.sweeps_total.{kind}"] == sum(
                s.relax_steps for s in mine)
            assert gained[f"route.window.seconds_total.{kind}"] == (
                pytest.approx(sum(s.route_time_s for s in mine), abs=1e-6))
        assert gained["route.window.discarded_seconds_total"] == (
            pytest.approx(sum(s.route_time_s for s in rows if not s.kept),
                          abs=1e-6))


def _events(events, name):
    return [e for e in events if e["name"] == name]


def test_with_a_tracer_the_window_spans_say_what_the_rows_say(routed):
    _, (f, events, _), _ = routed
    r = f.route
    rid = r.route_id
    assert rid > 0
    wins = [e for e in _events(events, "route.window")
            if e["args"]["route"] == rid]
    assert len(wins) == len(r.stats)
    for e, s in zip(wins, r.stats):
        row = {k: v for k, v in dataclasses.asdict(s).items() if v == v}
        assert {k: e["args"][k] for k in row} == row
        assert e["args"]["nets"] == s.rerouted_nets
        assert e["dur"] == pytest.approx(s.route_time_s * 1e6, abs=500)
    # the four intervals of the wall are spans, once a route, with its id
    for name, key in (("route.prologue", "prologue_s"),
                      ("route.epilogue", "epilogue_s")):
        (e,) = [e for e in _events(events, name)
                if e["args"]["route"] == rid]
        assert e["dur"] == pytest.approx(r.wall[key] * 1e6, abs=2000)
    (e,) = _events(events, "flow.route.verify")
    assert e["args"]["route"] == rid
    assert len(_events(events, "flow.route.setup")) == 1
    # the control step names the window it plans; the last plans none
    ctl = [e for e in _events(events, "route.pipeline.control")
           if e["args"]["route"] == rid]
    assert len(ctl) == len(r.stats)
    assert [e["args"].get("next_kind") for e in ctl] == (
        [s.kind for s in r.stats[1:]] + [None])
    assert [e["dur"] for e in ctl] == pytest.approx(
        [s.control_s * 1e6 for s in r.stats], abs=500)


def test_without_a_tracer_the_route_is_the_same(routed):
    _, (f, _, _), (g, none, _) = routed
    assert none is None
    a, b = f.route, g.route
    assert np.array_equal(a.paths, b.paths)
    assert (a.wirelength, a.iterations, a.success) == (
        b.wirelength, b.iterations, b.success)
    clocks = {"route_time_s", "stall_s", "plan_s", "dispatch_ms",
              "control_s"}

    def counts(s):
        return {k: v for k, v in dataclasses.asdict(s).items()
                if k not in clocks and v == v}
    assert [counts(s) for s in a.stats] == [counts(s) for s in b.stats]


def test_a_timing_driven_route_names_its_sta():
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8, chan_width=9,
                   seed=3)
    tr = Tracer()
    set_tracer(tr)
    try:
        run_route(f, RouterOpts(batch_size=32), timing_driven=True)
    finally:
        set_tracer(None)
    (e,) = _events(tr.events, "flow.route.sta")
    assert e["args"]["route"] == f.route.route_id > 0
    assert f.times["route.sta"] == pytest.approx(e["dur"] / 1e6, abs=2e-3)
    stage = f.times["route"]
    assert abs(sum(f.route.wall.values()) - stage) <= max(
        1e-3, 1e-3 * stage)
    assert f.route.stats[0].kind == "first"
    assert all(s.kind in WINDOW_KINDS for s in f.route.stats)


def test_a_resumed_route_takes_its_first_kind_from_the_checkpoint():
    """The checkpoint written at the end of the pass's window carries
    ``finish_done`` and no ``force_all_next``: the resumed route's one
    window re-legalises, and is never ``first``."""
    cut, _, _ = _flow("pass_restored", False, checkpoint_every=1)
    ck = cut.route.checkpoint
    assert ck.it_done == 16 and ck.driver["finish_done"]
    assert not ck.driver["force_all_next"]
    f, _, _ = _flow("pass_kept", False, resume=ck)
    assert [(s.window, s.kind, s.rerouted_nets, s.kept)
            for s in f.route.stats] == [(6, "relegalise", 3, True)]
    # a slice that ends as the pass is PLANNED: the checkpoint carries
    # both flags and the resumed route's first window is the pass
    early, _, _ = _flow("pass_kept", False, slice_iterations=11)
    ck = early.route.checkpoint
    assert ck.it_done == 11 and ck.fin_save is not None
    assert ck.driver["finish_done"] and ck.driver["force_all_next"]
    assert [s.kind for s in early.route.stats] == KINDS["pass_kept"][:4]
    f, _, _ = _flow("pass_kept", False, resume=ck)
    assert [(s.window, s.kind, s.rerouted_nets) for s in f.route.stats] == [
        (5, "finish", 26), (6, "relegalise", 3)]
    assert f.route.success and f.route.iterations == 22


def _grid_cells(f):
    """Cells of one net's full canvas, by the benchmark's formula."""
    from benchmark.bytes_model import plane_cells

    return plane_cells(f.rr.chan_width, f.grid.nx, f.grid.ny)


@pytest.fixture(scope="module")
def cropped_route():
    """tests/test_planes.py's 19 x 19 fixture, whose 16 x 16 rung
    every window populates, routed once with the driver's PLAN on
    record: the width of every batch plan in the order the rungs were
    planned, and per window each rung's executed sweeps and its tile
    (``_book_window``'s own inputs, before it adds anything up)."""
    from test_planes import _placed

    from parallel_eda_tpu.route.planes import SCAL_S_EXEC

    f = _placed("directional_l4_19x19")
    router = Router(f.rr, RouterOpts(batch_size=16))
    widths, windows = [], []
    plan, book = router._plan_groups, router._book_window

    def plan_groups(*a, **kw):
        sel, valid = plan(*a, **kw)
        widths.append(sel.shape[1])
        return sel, valid

    def book_window(bk, result, mlog):
        windows.append([(int(np.asarray(scal)[SCAL_S_EXEC]), kp["tile"])
                        for (scal, _), kp in zip(bk["rung_scals"],
                                                 bk["kplans"])])
        return book(bk, result, mlog)

    router._plan_groups, router._book_window = plan_groups, book_window
    r = router.route(f.term)
    assert r.success
    return f, r, widths, windows


def test_cell_sweeps_are_sweeps_times_width_times_canvas(cropped_route):
    """``total_cell_sweeps`` recounted from the plan: over every rung
    of every window, the sweeps it executed x the width of its batch
    plan x the cells of the canvas it ran on, by the benchmark's own
    ``plane_cells`` (the 16 x 16 tile's, or the 19 x 19 grid's); by row
    the same; and the benchmark's reader divides it by the net routes."""
    from benchmark.bytes_model import plane_cells

    f, r, widths, windows = cropped_route
    assert len(widths) == sum(len(w) for w in windows)
    assert {t and tuple(t) for w in windows for _, t in w} == {
        None, (16, 16)}
    assert len(set(widths)) > 1 and max(widths) == 16   # narrowed plans
    W, it, want = f.rr.chan_width, iter(widths), []
    for rungs in windows:
        want.append(sum(
            steps * next(it) * plane_cells(W, *(tile or (19, 19)))
            for steps, tile in rungs))
    assert [s.cell_sweeps for s in r.stats] == want
    assert r.total_cell_sweeps == sum(want) > 0
    # between every sweep on the tile and every sweep on the canvas
    assert (plane_cells(W, 16, 16) * 8 * r.total_relax_steps
            < r.total_cell_sweeps
            < _grid_cells(f) * 16 * r.total_relax_steps)
    lines = format_window_table(r).splitlines()
    assert lines[0].split()[-1] == "Mcell_sweeps"
    assert [ln.split()[-1] for ln in lines[1:len(r.stats) + 2]] == [
        f"{n / 1e6:.1f}" for n in want + [sum(want)]]


def test_a_route_with_a_populated_rung_books_its_cropped_waves(
        cropped_route):
    """``waves_cropped``: the calls of the cropped relaxation, by row
    and on the result, on a route whose 19 x 19 grid has a 16 x 16 rung
    that every window populates (tests/test_planes.py's fixture): of a
    row's waves, those of its cropped rungs; with its cropped sweeps or
    not at all; the rows' sum the result's; a column of the table."""
    f, r, _, _ = cropped_route
    assert 0 < r.total_waves_cropped < r.total_waves
    assert sum(s.waves_cropped for s in r.stats) == r.total_waves_cropped
    for s in r.stats:
        assert 0 <= s.waves_cropped <= s.waves
        assert (s.waves_cropped > 0) == (s.relax_steps_cropped > 0)
        # a wave runs at least the sweep that finds its fixpoint
        assert s.relax_steps_cropped >= s.waves_cropped
    lines = format_window_table(r).splitlines()
    # from the right: the sum row leaves three cells empty on the left
    col = lines[0].split().index("waves_crop") - len(lines[0].split())
    assert [ln.split()[col] for ln in lines[1:len(r.stats) + 2]] == [
        str(s.waves_cropped) for s in r.stats] + [
        str(r.total_waves_cropped)]


def test_other_constructors_of_a_row_still_work():
    s = RouteStats(3, 0, 0, 5, 0.25)
    assert (s.window, s.kind, s.kept, s.control_s) == (0, "", True, 0.0)


def test_the_window_table_prints_the_rows(routed, tmp_path):
    name, (f, _, _), _ = routed
    r = f.route
    text = format_window_table(r)
    lines = text.splitlines()
    assert lines[0].split() == [
        "window", "iter", "kind", "overused", "nets", "seconds", "stall_s",
        "control_s", "sweeps", "waves", "waves_crop", "batches", "routes",
        "routes/batch", "kept", "pick_read%", "Mcell_sweeps"]
    assert len(lines) == len(r.stats) + 3
    for line, s in zip(lines[1:], r.stats):
        cells = line.split()
        assert cells[:5] == [str(s.window), str(s.iteration), s.kind,
                             str(s.overused_nodes), str(s.rerouted_nets)]
        assert cells[8:] == [str(s.relax_steps), str(s.waves),
                             str(s.waves_cropped),
                             str(s.batches), str(s.net_routes),
                             "%.1f" % (s.net_routes / s.batches),
                             "yes" if s.kept else "NO",
                             "%.1f" % (100.0 * s.sink_reads
                                       / s.sink_reads_dense),
                             "%.1f" % (s.cell_sweeps / 1e6)]
    assert lines[-2].split()[0] == "sum"
    assert lines[-2].split()[-2:] == [
        "%.1f" % (100.0 * r.total_sink_reads / r.total_sink_reads_dense),
        "%.1f" % (r.total_cell_sweeps / 1e6)]
    assert lines[-2].split()[-5:-3] == [
        str(r.total_net_routes), "%.1f" % (
            r.total_net_routes / sum(s.batches for s in r.stats))]
    assert lines[-1].startswith("wall: prologue_s ")
    assert ("NO" in text) == (name == "pass_restored")
    write_stats_files(str(tmp_path), r)
    assert (tmp_path / "window_table.txt").read_text() == text + "\n"
    assert (tmp_path / "iter_stats.txt").exists()
