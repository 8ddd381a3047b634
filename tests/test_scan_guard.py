"""The scans' guard against a predecessor 2-cycle inside a wire span
(``planes._scan_update(guard=True)``, the static ``scan_guard`` of
``PlanesGraph`` / ``PlanesGeom``): ROADMAP Queue 1 item 2's two rows,
the field's default leaving every program as it was, the rule by which
the window driver switches it on (``router._scan_guard_due``) and a
length-4 route whose windows run guarded from the second on."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.route import router as router_mod
from parallel_eda_tpu.route.planes import _scan_update
from parallel_eda_tpu.route.router import _scan_guard_due

INF = np.float32(np.inf)
V = np.float32(6.0305461e-10)           # the distance PR 36 found it at
BELOW = np.nextafter(V, np.float32(0))  # one ulp under it
IDX = jnp.arange(6, dtype=jnp.int32)[None]
# a row of six cells: cell 0 | one wire over cells 1..4 | cell 5.  A
# backward step is free INTO cells 1, 2, 3 (from the span's next cell)
# and pays the switch into 0 and 4
CB = jnp.asarray([[1e-10, 0.0, 0.0, 0.0, 1e-10, 0.0]], jnp.float32)
WB = jnp.where(CB > 0, jnp.float32(5e-11), 0.0)


def _backward(d, pred, guard):
    w = jnp.zeros((1, 6), jnp.float32)
    d2, p2, _ = _scan_update(jnp.asarray([d], jnp.float32),
                             jnp.asarray([pred], jnp.int32), w, CB, WB,
                             IDX, 1, 1, True, guard)
    return np.asarray(d2)[0], np.asarray(p2)[0].tolist()


def test_cells_an_ulp_below_the_neighbour_they_came_through():
    """Cells 3 and 4 were reached THROUGH cell 2 (the forward scan:
    ``pred[3] = 2``, ``pred[4] = 3``) and the associative scan's order
    of summation left them an ulp BELOW it.  Unguarded, the backward
    scan improves 2 from 3: ``pred[2] = 3`` and ``pred[3] = 2``, the
    2-cycle a traceback circles in.  Guarded, cell 2 keeps its distance
    and its predecessor (99: the turn it was entered by); cell 1, which
    hangs on 2, takes the scan's value (the ulp) and keeps hanging on
    2, and a second backward scan changes nothing: a fixpoint."""
    d = [INF, V, V, BELOW, BELOW, INF]
    pred = [-1, 2, 99, 2, 3, -1]
    _, plain = _backward(d, pred, False)
    assert plain[2] == 3 and plain[3] == 2          # the cycle
    got, guarded = _backward(d, pred, True)
    assert guarded[1:5] == [2, 99, 2, 3]
    assert got[2] == V and got[1] == BELOW
    again, same = _backward(got.tolist(), guarded, True)
    assert same == guarded and again.tolist() == got.tolist()
    # no walk from a cell of the span circles: each ends at the turn
    for start in (1, 2, 3, 4):
        cell, steps = start, 0
        while guarded[cell] != 99:
            cell, steps = guarded[cell], steps + 1
            assert steps < 6
        assert cell == 2


def test_a_span_that_truly_turns_round_is_improved_as_before():
    """Cell 3 was entered by a turn of its own (``pred[3] = 77``) at a
    distance well under cell 2's: the path through the span now runs
    the other way, 3 -> 2 -> 1, and the guard leaves that alone; into
    cell 0 the step pays the switch, guarded or not."""
    lo = np.float32(2e-10)
    d = [INF, V, V, lo, INF, INF]
    pred = [-1, 2, 99, 77, -1, -1]
    want_d, want_p = _backward(d, pred, False)
    got_d, got_p = _backward(d, pred, True)
    assert got_p == want_p == [1, 2, 3, 77, -1, -1]
    np.testing.assert_array_equal(got_d, want_d)
    assert got_d[2] == got_d[1] == lo and got_d[0] == lo + np.float32(1e-10)


def test_the_fields_default_leaves_the_relaxation_as_it_was():
    """``scan_guard`` is static and off by default: the relaxation's
    jaxpr with the field at its default is the jaxpr of a graph built
    before the field existed (no roll, no compare), and differs once
    the field is on."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.route.planes import build_planes, planes_relax

    f = synth_flow(num_luts=12, num_inputs=4, num_outputs=4, chan_width=8,
                   seed=1)
    pg = build_planes(f.rr)
    assert pg.scan_guard is False
    z = jnp.zeros((2, pg.ncells), jnp.float32)

    def text(g):
        return str(jax.make_jaxpr(
            lambda a: planes_relax(g, a, a, jnp.zeros((2, 1, 1, 1)), a, 4)
        )(z))

    off, on = text(pg), text(pg.replace(scan_guard=True))
    assert off == text(pg.replace(scan_guard=False))
    assert "roll" not in off or off.count("roll") < on.count("roll")
    assert off != on


def _rule_table():
    for n_over, unreached, snapshot, span in itertools.product(
            (0, 1, 23), (False, True), (False, True), (1, 4)):
        # ONE combination fires: nothing over, a sink unreached, no
        # snapshot to return, wires longer than a tile
        want = (n_over, unreached, snapshot, span) == (0, True, False, 4)
        yield pytest.param(n_over, unreached, snapshot, span, want,
                           id="over%d-u%d-snap%d-span%d" % (
                               n_over, unreached, snapshot, span))


@pytest.mark.parametrize("n_over, unreached, snapshot, span, want",
                         list(_rule_table()))
def test_scan_guard_rule(n_over, unreached, snapshot, span, want):
    assert _scan_guard_due(n_over, unreached, snapshot, span) is want


def _route_directional(resume=None, **opts):
    """tests/test_walk_forms.py's route: length-4 single-driver wires
    on a 6 x 6 grid, 50 LUTs."""
    import warnings

    from parallel_eda_tpu.arch.builtin import unidir_arch
    from parallel_eda_tpu.flow import prepare, run_place_native
    from parallel_eda_tpu.netlist.generate import generate_circuit
    from parallel_eda_tpu.route import Router, RouterOpts, check_route

    arch = unidir_arch(chan_width=16, length=4)
    nl = generate_circuit(num_luts=50, num_inputs=8, num_outputs=8,
                          K=arch.K, seed=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = run_place_native(prepare(nl, arch, 16, seed=5), seed=7)
    r = Router(f.rr, RouterOpts(batch_size=32, **opts))
    res = r.route(f.term, resume=resume)
    if res.success:
        check_route(f.rr, f.term, res.paths, occ=res.occ)
    assert r.pg.scan_guard is False     # the router's own graph stays
    return res


def test_a_route_runs_guarded_from_the_window_after_the_rule_fires(
        monkeypatch):
    """The rule forced to fire at the end of window 1 (this small route
    never meets the state by itself): window 1 is the plain route's to
    the last count, every later window's row says ``scan_guard``, the
    guarded windows are dispatch variants of their own, and the route
    they end is legal by ``check_route``.  A plain route has no such
    row."""
    plain = _route_directional()
    assert plain.success and len(plain.stats) > 1
    assert not any(s.scan_guard for s in plain.stats)
    seen = []
    note = router_mod._note_dispatch_variant

    def noting(vkey):
        seen.append(vkey)
        return note(vkey)

    monkeypatch.setattr(router_mod, "_note_dispatch_variant", noting)
    monkeypatch.setattr(router_mod, "_scan_guard_due",
                        lambda n_over, unreached, snapshot, span: span > 1)
    guarded = _route_directional()
    assert guarded.success
    assert [s.scan_guard for s in guarded.stats] == (
        [False] + [True] * (len(guarded.stats) - 1))

    def counts(s):
        return (s.iteration, s.overused_nodes, s.rerouted_nets,
                s.relax_steps, s.batches, s.net_routes)

    assert counts(guarded.stats[0]) == counts(plain.stats[0])
    from parallel_eda_tpu.route.report import format_window_table
    head, *lines = format_window_table(guarded).splitlines()
    assert head.split()[-1] == "guard"
    assert [ln.split()[-1] for ln in lines[:len(guarded.stats) + 1]] == (
        ["-"] + ["yes"] * (len(guarded.stats) - 1)
        + [f"{len(guarded.stats) - 1}/{len(guarded.stats)}"])
    assert "guard" not in format_window_table(plain).splitlines()[0]
    first = [k for k in seen if "scan_guard" not in k]
    later = [k for k in seen if "scan_guard" in k]
    assert first and later and all(k[-1] == "scan_guard" for k in later)


def test_a_resumed_route_stays_guarded(monkeypatch):
    """The checkpoint written at the end of the window after which the
    rule fired carries the flag: the resumed route (the rule itself
    never firing again) runs every window guarded and ends as the
    uninterrupted guarded route does."""
    with monkeypatch.context() as m:
        m.setattr(router_mod, "_scan_guard_due",
                  lambda n_over, unreached, snapshot, span: span > 1)
        whole = _route_directional()
        cut = _route_directional(max_router_iterations=2,
                                 checkpoint_every=1)
    ck = cut.checkpoint
    assert not cut.success and ck.it_done == 2
    assert ck.driver["scan_guard"] is True
    res = _route_directional(resume=ck)
    assert res.success and all(s.scan_guard for s in res.stats)
    assert (res.iterations, res.wirelength) == (whole.iterations,
                                                whole.wirelength)
