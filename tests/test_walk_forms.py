"""A wave's two walk scatters read the slots its KEPT walks ran
(``planes.walk_scatters``: trips of ``WALK_CHUNK`` slots, as many as
hold the wave's longest kept walk) and write what one scatter each over
the whole budget wrote (``planes.walk_scatters_dense``, the program
until PR 42): the forms alone on seeded walks, the count of the slots
read, tools/walk_forms.py on a tiny shape, and a whole route on the
directional wires (tests/test_endgame.py and
tests/test_fanout_classes.py hold the bidirectional and the two-class
ones)."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.route import planes
from walk_refs import dense_scatters, seeded_walks, walk_scatters_dense


def _lengths(B, G, seed, top, longest=None):
    """Walk lengths 0..top, a third of them 0 (direct or invalid picks),
    one of them ``longest`` if given."""
    rng = np.random.default_rng(seed)
    L = rng.integers(0, top + 1, (B, G)) * (rng.random((B, G)) > 0.33)
    if longest is not None:
        L[B // 2, G // 2] = longest
    return L


CASES = {
    # B, G, Kw, ncells, N, lengths, chunk, (b, g) kept whatever is drawn
    "mixed": (8, 3, 70, 600, 400, _lengths(8, 3, 1, 40), 16, ()),
    "mixed_chunk32": (8, 3, 70, 600, 400, _lengths(8, 3, 1, 40), 32, ()),
    "no_walk": (4, 5, 70, 600, 400, 0, 16, ()),
    "one_overrun_kept": (8, 3, 70, 600, 400, _lengths(8, 3, 2, 9, 70), 16,
                         ((4, 1),)),
    "every_walk_overruns": (3, 2, 70, 600, 400, 70, 16, ()),
    "budget_of_whole_chunks": (4, 3, 64, 500, 300,
                               _lengths(4, 3, 3, 64, 64), 16, ((2, 1),)),
    "budget_under_a_chunk": (4, 2, 20, 300, 200, _lengths(4, 2, 4, 20),
                             16, ()),
    "one_pick": (6, 1, 70, 600, 400, _lengths(6, 1, 5, 50), 16, ()),
    "wide_pick": (2, 48, 45, 900, 500, _lengths(2, 48, 6, 33), 16, ()),
    "one_step": (4, 3, 70, 600, 400, _lengths(4, 3, 7, 0, 1), 16,
                 ((2, 1),)),
    "a_chunk_and_one": (4, 3, 70, 600, 400, _lengths(4, 3, 8, 10, 17), 16,
                        ((2, 1),)),
    "chunk8_last_chunk_clamped": (4, 3, 70, 600, 400,
                                  _lengths(4, 3, 9, 30, 69), 8, ((2, 1),)),
}


def _slots(last, Kw, chunk):
    chunk = min(chunk, Kw)
    return min(Kw, -(-last // chunk) * chunk)


@pytest.mark.parametrize("case", list(CASES))
def test_the_trips_write_what_the_dense_scatters_write(case):
    B, G, Kw, ncells, N, lengths, chunk, kept = CASES[case]
    *args, last = seeded_walks(B, G, Kw, ncells, N, lengths, seed=11,
                               direct=0.2, kept=kept)
    with mock.patch.object(planes, "WALK_CHUNK", chunk):
        # a jit of its own: the chunk is no part of a cached trace's key
        buf, seg, slots = jax.jit(
            lambda *a: planes.walk_scatters(*a))(*args)
    buf_ref, seg_ref, slots_ref = jax.jit(walk_scatters_dense)(*args)
    # column ncells is the dump: the dense form mins the fill's delays
    # into it, the trips skip them, nobody reads it
    assert np.array_equal(np.asarray(buf)[:, :ncells],
                          np.asarray(buf_ref)[:, :ncells])
    assert np.array_equal(np.asarray(seg), np.asarray(seg_ref))
    walked = int(np.isfinite(np.asarray(buf_ref)[:, :ncells]).sum())
    assert (walked > 0) == (int(last) > 0)
    assert (int(slots), int(slots_ref)) == (_slots(int(last), Kw, chunk), Kw)


def test_a_walk_that_overran_and_is_not_kept_costs_no_trip():
    """The wave's longest walk ran its whole budget and is not ``ok``
    (its cells are the dump column's, none of its nodes is kept): the
    trips follow the longest walk that IS kept, and still write what
    the dense scatters write."""
    B, G, Kw, ncells, N = 8, 3, 70, 600, 400
    lengths = _lengths(B, G, 2, 9)
    lengths[4, 1] = Kw
    *args, last = seeded_walks(B, G, Kw, ncells, N, lengths, seed=11,
                               kept=[(b, g) for b in range(B)
                                     for g in range(G) if (b, g) != (4, 1)])
    keep = args[5]
    args[2][4, 1] = ncells          # walk_cells: not ok -> the dump
    keep[4, 1] = False
    args[6][...] = np.cumsum(keep, axis=2) - 1
    buf, seg, slots = jax.jit(planes.walk_scatters)(*args)
    buf_ref, seg_ref, _ = jax.jit(walk_scatters_dense)(*args)
    assert np.array_equal(np.asarray(buf)[:, :ncells],
                          np.asarray(buf_ref)[:, :ncells])
    assert np.array_equal(np.asarray(seg), np.asarray(seg_ref))
    assert 0 < int(slots) == planes.WALK_CHUNK < Kw


@pytest.mark.parametrize("steps, Kw, chunk, want", [
    (0, 188, 32, (0, 0)), (1, 188, 32, (1, 32)), (32, 188, 32, (1, 32)),
    (33, 188, 32, (2, 64)), (160, 188, 32, (5, 160)),
    (161, 188, 32, (6, 188)), (188, 188, 32, (6, 188)),
    # a budget under a chunk is one trip of the budget
    (0, 20, 32, (0, 0)), (3, 20, 32, (1, 20)), (20, 20, 32, (1, 20)),
    (64, 64, 32, (2, 64)), (9, 148, 16, (1, 16))])
def test_slots_read_are_the_steps_in_whole_chunks(steps, Kw, chunk, want):
    with mock.patch.object(planes, "WALK_CHUNK", chunk):
        trips, slots = planes.walk_slots_read(jnp.int32(steps), Kw)
    assert (int(trips), int(slots)) == want
    assert steps <= int(slots) <= Kw


def test_the_chunk_is_a_module_constant():
    """No option and no cell's name picks it (ISSUE 42): the form table
    of tools/walk_forms.py does, for every shape at once."""
    import dataclasses

    from parallel_eda_tpu.route.router import RouterOpts

    assert planes.WALK_CHUNK == 16
    assert not [f.name for f in dataclasses.fields(RouterOpts)
                if "walk" in f.name or "chunk" in f.name]
    assert planes.STEP_LEDGER_LEN == 8
    assert planes.SCAL_WALK_SLOTS == planes.SCAL_MIS_FORM - 1 \
        == planes.SCAL_S_EXEC + planes.STEP_LEDGER_LEN - 1


def _route_directional():
    """Length-4 single-driver wires on a 6 x 6 grid: the directional
    relaxation's predecessors under the walk."""
    import warnings

    from parallel_eda_tpu.arch.builtin import unidir_arch
    from parallel_eda_tpu.flow import prepare, run_place_native
    from parallel_eda_tpu.netlist.generate import generate_circuit
    from parallel_eda_tpu.route import Router, RouterOpts

    arch = unidir_arch(chan_width=16, length=4)
    nl = generate_circuit(num_luts=50, num_inputs=8, num_outputs=8,
                          K=arch.K, seed=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = run_place_native(prepare(nl, arch, 16, seed=5), seed=7)
    return Router(f.rr, RouterOpts(batch_size=32)).route(f.term)


def test_the_directional_route_is_the_route_of_dense_scatters():
    from sink_pick_refs import assert_same_route

    res = _route_directional()
    with dense_scatters():
        dense = _route_directional()
    assert res.success
    assert_same_route(res, dense, but=("total_walk_slots_read",))
    assert 0 < res.total_walk_slots_read < res.total_walk_budget \
        == dense.total_walk_slots_read


def _walk_forms_tool():
    import importlib.util
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)       # it times with crop_forms' clock
    spec = importlib.util.spec_from_file_location(
        "walk_forms", os.path.join(tools, "walk_forms.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_the_tool_compares_and_times_its_forms_on_a_tiny_shape(monkeypatch):
    tool = _walk_forms_tool()
    monkeypatch.setitem(tool.SHAPES, "tiny", (4, 3, 70, 600, 9))
    assert tool.forms_agree("tiny", [8, 16], seed=1)
    row = tool.time_shape("tiny", [16], reps=2, seed=1)
    assert row["device"] == "cpu" and row["ladder_rungs"] == [16, 24, 40]
    assert {"dense.us@9", "floor.us@9", "ladder.us@70"} <= set(row)
    assert {f"loop@16.us@{s}" for s in (0, 9, 18, 36, 70)} <= set(row)


def test_the_tool_counts_slots_and_trips_by_whole_chunks():
    tool = _walk_forms_tool()
    hist = {0: 1, 9: 2, 17: 1, 70: 1}       # waves by steps, Kw = 70
    assert tool.trips_hist(hist, 70, 16) == {0: 1, 1: 2, 2: 1, 5: 1}
    assert tool.read_share(hist, 70, 16) == pytest.approx(
        100.0 * (0 + 2 * 16 + 32 + 70) / (5 * 70))
    assert tool.read_share({}, 70, 16) == 0.0
