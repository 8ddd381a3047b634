"""Work-efficiency ledger smoke tests (fast, `pytest -m ledger`).

The ledger splits every relaxation sweep the device executed into
useful (improved some distance) and wasted (fixpoint discovery); the
invariant useful + wasted == total must hold exactly — the device
measures both sides of the split in the same while_loop carry, so a
mismatch means a dispatch path dropped its stats.

Also wires tools/ledger_report.py --check into the suite: the checker
must accept the registry dump of a real route and reject a dump whose
invariant is broken.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.obs import get_metrics
from parallel_eda_tpu.route import Router, RouterOpts

LEDGER_TOOL = Path(__file__).resolve().parent.parent / "tools" / \
    "ledger_report.py"


@pytest.fixture(scope="module")
def routed():
    """One tiny CPU route shared by the module: RouteResult + the
    registry dump taken right after it."""
    reg = get_metrics()
    reg.reset()
    reg.enabled = True
    try:
        f = synth_flow(num_luts=15, chan_width=10, seed=0)
        res = Router(f.rr, RouterOpts(batch_size=16)).route(f.term)
        values = reg.values("route.")
        snapshots = [s for s in reg.snapshots]
        doc = {"values": reg.values(), "snapshots": snapshots}
    finally:
        reg.enabled = False
    return res, values, doc


@pytest.mark.ledger
def test_ledger_invariant(routed):
    res, _, _ = routed
    assert res.success
    assert res.total_relax_steps > 0
    assert res.total_relax_steps_useful > 0
    assert (res.total_relax_steps_useful + res.total_relax_steps_wasted
            == res.total_relax_steps)


@pytest.mark.ledger
def test_registry_counters_match_result(routed):
    res, values, _ = routed
    assert values.get("route.relax_steps") == res.total_relax_steps
    assert values.get("route.relax_steps_useful") == \
        res.total_relax_steps_useful
    assert values.get("route.relax_steps_wasted") == \
        res.total_relax_steps_wasted
    wf = values.get("route.relax_wasted_frac")
    assert wf is not None and abs(
        wf - res.total_relax_steps_wasted / res.total_relax_steps) < 1e-3


@pytest.mark.ledger
def test_early_exit_beats_ceiling(routed):
    """The on-device convergence exit must actually fire: on this tiny
    fixture the fixpoint lands well before the static sweep ceiling, so
    some executed sweeps are wasted (exactly one fixpoint-discovery
    sweep per relax call) but far fewer than the old fixed-trip-count
    program would have burned."""
    res, _, _ = routed
    assert res.total_relax_steps_wasted > 0
    assert res.total_relax_steps_wasted < res.total_relax_steps


@pytest.mark.ledger
def test_ledger_report_check_accepts_real_dump(routed, tmp_path):
    _, _, doc = routed
    p = tmp_path / "metrics.json"
    p.write_text(json.dumps(doc))
    r = subprocess.run([sys.executable, str(LEDGER_TOOL), str(p),
                        "--check"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


@pytest.mark.ledger
def test_ledger_report_summarize_runs(routed, tmp_path):
    _, _, doc = routed
    p = tmp_path / "metrics.json"
    p.write_text(json.dumps(doc))
    r = subprocess.run([sys.executable, str(LEDGER_TOOL), str(p)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "work-efficiency ledger" in r.stdout
    assert "useful" in r.stdout


@pytest.mark.ledger
def test_ledger_report_check_rejects_broken_invariant(tmp_path):
    doc = {"values": {"route.relax_steps": 100,
                      "route.relax_steps_useful": 90,
                      "route.relax_steps_wasted": 20},
           "snapshots": []}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(doc))
    r = subprocess.run([sys.executable, str(LEDGER_TOOL), str(p),
                        "--check"], capture_output=True, text=True)
    assert r.returncode == 1
    assert "invariant" in r.stderr


@pytest.mark.ledger
def test_ledger_report_check_rejects_missing_and_garbage(tmp_path):
    p = tmp_path / "missing.json"
    p.write_text(json.dumps({"values": {}}))
    r = subprocess.run([sys.executable, str(LEDGER_TOOL), str(p),
                        "--check"], capture_output=True, text=True)
    assert r.returncode == 1

    g = tmp_path / "garbage.json"
    g.write_text("{not json")
    r = subprocess.run([sys.executable, str(LEDGER_TOOL), str(g),
                        "--check"], capture_output=True, text=True)
    assert r.returncode == 2


# ---- the traceback walk's half of the ledger ----

def _route_tiny():
    f = synth_flow(num_luts=15, chan_width=10, seed=0)
    return Router(f.rr, RouterOpts(batch_size=16)).route(f.term)


@pytest.mark.ledger
def test_walk_ledger_counts_and_repeats(routed):
    """The walks ran some of the steps they were budgeted, never more,
    and a second route counts the same."""
    res, _, _ = routed
    assert 0 < res.total_walk_steps <= res.total_walk_budget
    again = _route_tiny()
    assert again.total_walk_steps == res.total_walk_steps
    assert again.total_walk_budget == res.total_walk_budget


def test_wave_ledger_counts_and_repeats(routed):
    """Executed waves are counted beside the sweeps they ran (the
    fifth entry of the step's ledger vector): every wave is one
    relaxation, so at least one sweep each, every net routed took at
    least one, and a second route counts the same."""
    res, _, _ = routed
    assert 0 < res.total_waves <= res.total_relax_steps
    assert res.total_walk_budget >= res.total_waves
    assert _route_tiny().total_waves == res.total_waves


@pytest.mark.ledger
def test_walk_slot_ledger_counts_and_repeats(routed):
    """The eighth entry of the step's ledger vector: the walk slots the
    waves' two scatters read, each wave the steps of its longest KEPT
    walk in whole chunks of planes.WALK_CHUNK and at most its budget; a
    wave that picked only direct connections walked and read nothing."""
    from parallel_eda_tpu.route.planes import (SCAL_LEN, SCAL_WALK_SLOTS,
                                               STEP_LEDGER_LEN, WALK_CHUNK)

    res, _, _ = routed
    # (the colouring's form rides behind the ledger, PR 46)
    assert (STEP_LEDGER_LEN, SCAL_WALK_SLOTS, SCAL_LEN) == (8, 12, 14)
    assert 0 < res.total_walk_slots_read < res.total_walk_budget
    # no wave read a chunk's worth more than it walked (it reads less
    # where its longest walk overran and was not kept)
    assert res.total_walk_slots_read - res.total_walk_steps \
        < WALK_CHUNK * res.total_waves
    assert _route_tiny().total_walk_slots_read == res.total_walk_slots_read


def _full_budget_walk(pred, wenter, noc_p1, pick_cell, done0, Kw):
    """The walk as it was before it could end early: a fixed-trip loop
    of Kw steps, each a scatter at one position of [B, G, Kw]."""
    import jax.numpy as jnp
    from jax import lax

    B, G = pick_cell.shape
    ncells = pred.shape[1]
    N = noc_p1[ncells]
    ar_b = jnp.arange(B)[:, None]
    ar_g = jnp.arange(G)[None, :]

    def walk_step(pos, ws):
        cur, done, cells_w, nodes_w, wst = ws
        nd = jnp.take(noc_p1, cur)
        cells_w = cells_w.at[ar_b, ar_g, pos].set(
            jnp.where(done, ncells, cur))
        nodes_w = nodes_w.at[ar_b, ar_g, pos].set(jnp.where(done, N, nd))
        w = jnp.take_along_axis(
            wenter, jnp.clip(cur, 0, ncells - 1), axis=1)
        wst = wst.at[ar_b, ar_g, pos].set(jnp.where(done, 0.0, w))
        nxt = jnp.take_along_axis(
            pred, jnp.clip(cur, 0, ncells - 1), axis=1)
        stop = done | (nxt == cur)
        return jnp.where(stop, cur, nxt), stop, cells_w, nodes_w, wst

    return lax.fori_loop(
        0, Kw, walk_step,
        (pick_cell, done0, jnp.full((B, G, Kw), ncells, jnp.int32),
         jnp.broadcast_to(N, (B, G, Kw)),
         jnp.zeros((B, G, Kw), jnp.float32))) + (jnp.int32(Kw),)


@pytest.mark.ledger
def test_early_ending_walk_routes_as_the_full_budget_walk(
        routed, monkeypatch):
    """The route under the early-ending walk is the route under the
    fixed 'Kw steps whatever the paths' walk, node for node: same
    iterations, wirelength, sweeps, paths, delays and occupancy.  Only
    the steps differ: the full-budget walk runs all it was given."""
    from parallel_eda_tpu.route import planes

    programs = (planes.route_window_planes,
                planes.route_batch_resident_planes)

    def forget():
        # the walk is traced into jitted programs: drop what they hold
        for prog in programs:
            prog.clear_cache()

    res, _, _ = routed
    monkeypatch.setattr(planes, "traceback_walk", _full_budget_walk)
    forget()
    try:
        full = _route_tiny()
    finally:
        monkeypatch.undo()
        forget()
    assert full.total_walk_steps == full.total_walk_budget \
        == res.total_walk_budget
    # the scatters follow the records the walks kept, not the trips
    # the walk's loop ran
    assert full.total_walk_slots_read == res.total_walk_slots_read
    assert res.total_walk_steps < full.total_walk_steps
    assert (res.success, res.iterations, res.wirelength,
            res.total_relax_steps, res.total_relax_steps_useful) == (
        full.success, full.iterations, full.wirelength,
        full.total_relax_steps, full.total_relax_steps_useful)
    assert np.array_equal(np.asarray(res.paths), np.asarray(full.paths))
    assert np.array_equal(np.asarray(res.sink_delay),
                          np.asarray(full.sink_delay))
    assert np.array_equal(np.asarray(res.occ), np.asarray(full.occ))
