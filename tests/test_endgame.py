"""The planes window driver's endgame: the wirelength finishing pass,
the phase-2 restart, and the snapshot a finish that does not land falls
back to.  The two full rebuilds exclude each other
(``_phase2_restart_due``): a restart stands in for the pass, and after
the pass only the nets that fight are re-legalised.

One circuit (40 LUTs, 38 nets, 26 of them multi-sink) shows every case
by its channel width and iteration cap; the per-window counts of the
W=8 route were recorded from the commit before the rule changed."""

import itertools

import pytest

from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.obs import get_metrics
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.route.router import _phase2_restart_due

R, MULTI_SINK = 38, 26
ENDGAME = ("finish_passes_total", "full_restarts_total",
           "finish_restored_total")


def _table():
    """Every flag combination at the values of n_over and widx on both
    sides of the rule's two thresholds."""
    for precise, restarted, finished, n_over, widx in itertools.product(
            (False, True), (False, True), (False, True), (0, 1, 53),
            (3, 4, 7)):
        # ONE flag combination fires: precise, no restart yet, no pass
        want = ((precise, restarted, finished) == (True, False, False)
                and n_over in (1, 53) and widx in (4, 7))
        yield pytest.param(precise, restarted, finished, n_over, widx,
                           want, id="p%d-r%d-f%d-over%d-w%d" % (
                               precise, restarted, finished, n_over, widx))


@pytest.mark.parametrize(
    "precise, restarted, finished, n_over, widx, want", list(_table()))
def test_phase2_restart_rule(precise, restarted, finished, n_over, widx,
                             want):
    assert _phase2_restart_due(precise, restarted, finished, n_over,
                               widx) is want


def test_phase2_restart_rule_the_two_cases_that_matter():
    # a stalled endgame before any legal window (the plateau valve or
    # n_over <= 8 set ``precise``): every net is rebuilt, once
    assert _phase2_restart_due(True, False, False, 3, 4)
    assert not _phase2_restart_due(True, True, False, 3, 5)
    # overuse after the finishing pass, which set ``precise`` itself and
    # has just rebuilt the multi-sink trees: no second full re-route
    assert not _phase2_restart_due(True, False, True, 53, 6)
    # not before the fifth window, and not on a legal window
    assert not _phase2_restart_due(True, False, False, 3, 3)
    assert not _phase2_restart_due(True, False, False, 0, 6)


def _route(chan_width, resume=None, **opts):
    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                   chan_width=chan_width, seed=3)
    assert (len(f.term.num_sinks), int((f.term.num_sinks > 1).sum())) == (
        R, MULTI_SINK)
    reg = get_metrics()
    before = [reg.counter("route.endgame." + k).value for k in ENDGAME]
    res = Router(f.rr, RouterOpts(batch_size=32, **opts)).route(
        f.term, resume=resume)
    counted = tuple(reg.counter("route.endgame." + k).value - b
                    for k, b in zip(ENDGAME, before))
    if res.success:
        check_route(f.rr, f.term, res.paths, occ=res.occ)
    windows = [(s.iteration, s.overused_nodes, s.rerouted_nets,
                s.relax_steps, s.batches) for s in res.stats]
    return res, windows, counted


# W=9, windows 1-5: legal at iteration 11, then the finishing pass (the
# 26 multi-sink nets), which leaves two nodes over
W9_TO_THE_PASS = [(2, 82, 38, 44, 4), (4, 64, 31, 154, 10),
                  (7, 30, 29, 263, 15), (11, 0, 21, 112, 6),
                  (16, 2, 26, 224, 5)]


def test_after_the_finishing_pass_only_the_fighting_nets_are_rerouted():
    res, windows, counted = _route(9)
    assert windows[:5] == W9_TO_THE_PASS
    assert windows[4][2] == MULTI_SINK
    # the window after the pass: the parent re-routed all 38 nets here
    # (22, 0, 38, 127, 6); the nets on the two overused nodes do
    assert windows[5:] == [(22, 0, 3, 45, 2)]
    assert 0 < windows[5][2] < R
    assert res.success and res.iterations == 22
    assert counted == (1, 0, 0)
    # the finished route is the one returned: nothing was thrown away
    assert res.total_relax_steps_discarded == 0
    assert res.total_relax_steps == sum(w[3] for w in windows) == 842


def test_the_endgame_is_the_endgame_of_dense_sink_picks():
    """Negotiation, finishing pass and re-legalisation with the live
    sink pick's ladder forced to the dense rung: the same route, window
    for window, and the ladder as built read fewer distances."""
    from sink_pick_refs import assert_same_route, dense_ladder

    res, windows, _ = _route(9)
    with dense_ladder():
        dense, windows_dense, _ = _route(9)
    assert windows == windows_dense == W9_TO_THE_PASS + [(22, 0, 3, 45, 2)]
    assert_same_route(res, dense)
    assert dense.total_sink_reads == dense.total_sink_reads_dense
    assert 0 < res.total_sink_reads < res.total_sink_reads_dense \
        == dense.total_sink_reads_dense


def test_the_endgame_is_the_endgame_of_dense_walk_scatters():
    """Negotiation, finishing pass and re-legalisation with every wave
    scattering ALL its walk slots (tests/walk_refs.py, the program until
    PR 42): the same route, window for window; the trips as built read
    the steps in whole chunks, never the budget."""
    from sink_pick_refs import assert_same_route
    from walk_refs import dense_scatters

    res, windows, _ = _route(9)
    with dense_scatters():
        dense, windows_dense, _ = _route(9)
    assert windows == windows_dense == W9_TO_THE_PASS + [(22, 0, 3, 45, 2)]
    assert_same_route(res, dense, but=("total_walk_slots_read",))
    assert 0 < res.total_walk_slots_read < res.total_walk_budget \
        == dense.total_walk_slots_read


def test_a_finish_that_does_not_land_restores_the_snapshot():
    """The pass starts (11 + 4 < 16), its window ends two nodes over and
    the iterations run out: the route returned is the snapshot of
    iteration 11 and the pass's sweeps are reported as discarded."""
    res, windows, counted = _route(9, max_router_iterations=16)
    assert windows == W9_TO_THE_PASS
    assert res.success and res.iterations == 11
    assert res.wirelength == 311
    assert counted == (1, 0, 1)
    assert res.total_relax_steps == 797
    assert res.total_relax_steps_discarded == 224 == windows[4][3]


def test_a_route_resumed_after_the_pass_takes_the_same_decision():
    """The checkpoint written at the end of the pass's window carries
    ``finish_done`` (as the parent's did: the format is unchanged); the
    resumed route re-legalises the same three nets, no restart."""
    cut, _, _ = _route(9, max_router_iterations=16, checkpoint_every=1)
    ck = cut.checkpoint
    assert ck.it_done == 16 and ck.fin_save is not None
    assert ck.driver["finish_done"] and ck.driver["precise"]
    assert not ck.driver["full_reroute_done"]
    res, windows, counted = _route(9, resume=ck)
    assert windows == [(22, 0, 3, 45, 2)]
    assert res.success and res.iterations == 22
    assert counted == (0, 0, 0)
    assert res.total_relax_steps_discarded == 0
    whole, _, _ = _route(9)
    assert res.wirelength == whole.wirelength == 314


def test_a_restart_before_the_first_legal_window_is_unchanged():
    """W=8: one node over after window 4 sets ``precise`` and the
    restart fires (window 5 re-routes all 38 nets) before any window
    was legal; the finishing pass never runs.  Every count is the
    parent's."""
    res, windows, counted = _route(8)
    assert windows == [(2, 70, 38, 43, 4), (4, 55, 35, 161, 10),
                       (7, 29, 30, 260, 15), (11, 1, 15, 148, 9),
                       (16, 1, 38, 259, 8), (22, 0, 2, 40, 1)]
    assert res.success and res.iterations == 22
    assert (res.wirelength, res.total_relax_steps) == (313, 911)
    assert counted == (0, 1, 0)
    assert res.total_relax_steps_discarded == 0
