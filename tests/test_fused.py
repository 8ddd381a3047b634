"""Continuous batching (parallel_eda_tpu/serve/fused.py).

Three layers, matching the subsystem:

* units — the batched queue loop (``JobQueue.run_batch``: verdict
  application through the shared state machine, a raised batch runner
  failing every member, the missing-verdict contract, backoff gating)
  and the rebatch bookkeeping (``diff_packs`` cause taxonomy, pack
  ``signature()`` independence from job identity) against fake
  runners/clocks — no jax;
* parity — the hard invariant: a seeded join/leave schedule through
  the fused service (staggered admission mid-drain, a tiny
  net-subset job fusing with full-size ones) finishes every job with
  wirelength/occ/paths BIT-identical to routing it alone, while the
  rebatch log records machine-readable join/finish causes;
* crash parity — a REAL ``--fused`` daemon subprocess SIGKILLed
  mid-fused-slice once a durable checkpoint exists, restarted on the
  same inbox: per-job wirelengths identical to an uninterrupted
  interleaved reference daemon, and flow_doctor's rebatch rules sign
  off on the summary.

    python -m pytest tests/ -m serve
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.serve.batcher import (REBATCH_CAUSES, CrossJobPlan,
                                            RungPlan, diff_packs)
from parallel_eda_tpu.serve.queue import JobQueue, JobState, RouteJob

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOW_DOCTOR = os.path.join(REPO, "tools", "flow_doctor.py")


@pytest.fixture(autouse=True)
def _clean_obs():
    set_metrics(MetricsRegistry())
    yield
    set_metrics(MetricsRegistry())


def _job(tenant="t", priority=0, **kw):
    return RouteJob(tenant=tenant, payload=None, priority=priority, **kw)


# ---- rebatch bookkeeping (no jax) ----------------------------------

def test_diff_packs_cause_taxonomy():
    """Every membership change at a rebatch boundary classifies to one
    machine-readable cause: entries are join (or failover when the
    scheduler says the job arrived via lease fencing), exits are
    finish (terminal DONE) or evict (everything else)."""
    causes = diff_packs(["a", "b", "c"], ["b", "d", "e"],
                        is_done=lambda j: j == "a",
                        is_failover=lambda j: j == "e")
    assert causes == [{"job_id": "d", "cause": "join"},
                      {"job_id": "e", "cause": "failover"},
                      {"job_id": "a", "cause": "finish"},
                      {"job_id": "c", "cause": "evict"}]
    assert all(c["cause"] in REBATCH_CAUSES for c in causes)
    # no membership change, no causes; first round is all joins
    assert diff_packs(["a"], ["a"]) == []
    assert diff_packs(None, ["x"]) == [{"job_id": "x", "cause": "join"}]


def test_pack_signature_ignores_job_identity():
    """signature() is the canonicalized pack shape: two packs with the
    same rung descriptor table share it regardless of which jobs own
    the slots — the property that lets the dispatch-variant cache and
    the AOT library survive a rebatch."""
    def rung(slots, block_nets=4):
        return RungPlan(tile=(8, 8), shape_x=(16, 8, 9),
                        shape_y=(16, 9, 8), block_nets=block_nets,
                        lane_occupancy=0.5, slots=slots)

    p1 = CrossJobPlan(rungs=[rung([("a", 0), ("a", 1), ("b", 0)])],
                      jobs=["a", "b"])
    p2 = CrossJobPlan(rungs=[rung([("x", 0), ("y", 0), ("y", 1)])],
                      jobs=["x", "y"])
    assert p1.signature() == p2.signature()
    assert p1.lane_occupancy == 0.5
    # a different block layout is a different compiled program family
    p3 = CrossJobPlan(rungs=[rung([("a", 0)], block_nets=8)],
                      jobs=["a"])
    assert p3.signature() != p1.signature()


# ---- batched queue loop (no jax) -----------------------------------

def test_run_batch_coadmits_and_applies_verdicts():
    """One round co-admits every runnable job; per-job verdicts flow
    through the same state machine as the one-at-a-time loop
    (preempted re-queues with the checkpoint, done finishes)."""
    q = JobQueue()
    a = q.admit(_job())
    b = q.admit(_job())
    rounds = []

    def br(batch):
        rounds.append(sorted(j.job_id for j in batch))
        out = {}
        for j in batch:
            assert j.state is JobState.RUNNING
            if j.job_id == a.job_id and j.checkpoint is None:
                out[j.job_id] = ("preempted", {"it": 2})
            else:
                out[j.job_id] = ("done", {"ok": True})
        return out

    jobs = q.run_batch(br)
    assert rounds == [sorted([a.job_id, b.job_id]), [a.job_id]]
    assert [j.state for j in jobs] == [JobState.DONE] * 2
    assert a.preemptions == 1 and a.slices == 2
    assert b.preemptions == 0 and b.slices == 1
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_done"] == 2
    assert v["route.serve.jobs_preempted"] == 1


def test_run_batch_missing_verdict_is_a_failure():
    """A batch runner that ghosts a member (returns no verdict for it)
    fails that member — silence is never success."""
    q = JobQueue()
    a = q.admit(_job())
    b = q.admit(_job())

    def br(batch):
        return {a.job_id: ("done", {})}

    q.run_batch(br)
    assert a.state is JobState.DONE
    assert b.state is JobState.FAILED
    assert "no verdict" in b.error


def test_run_batch_raise_fails_every_member_then_retries():
    """A raised batch runner counts as a failed attempt for EVERY
    co-admitted job; retry backoff gates the next round (the queue
    waits out the soonest gate instead of spinning)."""
    clk = {"t": 0.0}
    slept = []

    def sleep(dt):
        slept.append(dt)
        clk["t"] += dt

    q = JobQueue(clock=lambda: clk["t"], sleep=sleep)
    a = q.admit(_job(max_retries=1))
    b = q.admit(_job(max_retries=1))
    calls = {"n": 0}

    def br(batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("fused slice died")
        return {j.job_id: ("done", {}) for j in batch}

    jobs = q.run_batch(br)
    assert [j.state for j in jobs] == [JobState.DONE] * 2
    assert a.attempts == 1 and b.attempts == 1
    assert calls["n"] == 2
    assert slept and slept[0] > 0   # backoff gate was waited out
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_retried"] == 2


def test_run_batch_respects_deadline_and_tombstones():
    """_pop_runnable applies the same admission rules as run(): shed
    tombstones cost nothing, past-deadline jobs go TIMEOUT without
    ever joining a batch."""
    clk = {"t": 0.0}
    q = JobQueue(clock=lambda: clk["t"])
    a = q.admit(_job())
    dead = q.admit(_job(deadline_s=1.0))
    shed = q.admit(_job())
    q.evict(shed.job_id, error="overload")
    clk["t"] = 5.0
    seen = []

    def br(batch):
        seen.extend(j.job_id for j in batch)
        return {j.job_id: ("done", {}) for j in batch}

    q.run_batch(br)
    assert seen == [a.job_id]
    assert dead.state is JobState.TIMEOUT
    assert shed.state is JobState.SHED


# ---- fused service join/leave parity (real jax) --------------------

@pytest.mark.slow
def test_fused_service_join_leave_parity():
    """The hard invariant, over a seeded join/leave schedule: two jobs
    co-admitted upfront, a third (a tiny net-subset job — different
    topk, so it only fuses because topk rides the per-job statics)
    joining mid-drain after the first fused round; every job finishes
    with wirelength/occ/paths bit-identical to routing it alone, and
    the rebatch log records the join and the finishes with
    machine-readable causes."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.rr.terminals import subset_terminals
    from parallel_eda_tpu.serve.service import RouteService, ServeJobSpec

    base = dict(batch_size=32, sink_group=0)
    flows = [synth_flow(num_luts=10, seed=s) for s in (1, 2, 3)]
    rr = flows[0].rr
    terms = [flows[0].term, flows[1].term,
             subset_terminals(flows[2].term, 0.3, seed=5)]
    solo = []
    for t in terms:
        r = Router(rr, RouterOpts(**base)).route(t)
        assert r.success
        solo.append(r)

    set_metrics(MetricsRegistry())   # solo compiles don't count
    svc = RouteService(rr, RouterOpts(**base), slice_iters=2,
                       fused=True)
    for i in (0, 1):
        svc.admit(ServeJobSpec(term=terms[i], name=f"j{i}"),
                  tenant=f"t{i}")
    inner = svc._batch_runner
    joined = []

    def wrapped(batch):
        out = inner(batch)
        if not joined:   # the third job joins at the slice boundary
            svc.admit(ServeJobSpec(term=terms[2], name="j2"),
                      tenant="t0")
            joined.append(True)
        return out

    svc._batch_runner = wrapped
    jobs = svc.run()
    assert [j.state for j in jobs] == [JobState.DONE] * 3
    for job, ref, t in zip(jobs, solo, terms):
        assert job.result["wirelength"] == ref.wirelength
        res = job.result["result"]
        assert np.array_equal(np.asarray(res.occ), np.asarray(ref.occ))
        assert np.array_equal(np.asarray(res.paths),
                              np.asarray(ref.paths))
        check_route(rr, t, res.paths, occ=res.occ)

    v = get_metrics().values("route.serve.")
    assert v.get("route.serve.fused.dispatches", 0) > 0
    assert v.get("route.serve.fused.jobs", 0) > \
        v.get("route.serve.fused.dispatches", 0)  # real fusion, not 1-wide
    rb = svc.rebatch_summary()
    assert rb["fused"]
    assert 0 < len(rb["events"]) <= rb["rounds"]
    causes = [c["cause"] for e in rb["events"] for c in e["causes"]]
    assert "join" in causes and "finish" in causes
    assert all(c in REBATCH_CAUSES for c in causes)
    # live pack telemetry refreshed at the rebatch boundary
    assert all(0.0 <= e["lane_occupancy"] <= 1.0 for e in rb["events"])


def test_multi_window_equals_each_jobs_fused_window():
    """ONE window of route_window_planes_multi over two co-admitted
    jobs equals each job's route_window_planes_fused dispatched alone,
    element for element of the 24-tuple: every job's ladder is an
    independent subgraph of the one program.  The requests are the
    first ones each job's route_gen yields, split as
    FusedSliceRunner._dispatch_multi splits them."""
    import jax

    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.route.planes import (route_window_planes_fused,
                                               route_window_planes_multi)
    from parallel_eda_tpu.serve.fused import _shared_key, _split_request

    flows = [synth_flow(num_luts=10, seed=s) for s in (1, 2)]
    router = Router(flows[0].rr, RouterOpts(
        batch_size=32, sink_group=0, fused_dispatch=True))

    def first_request(term, prefix):
        router._staging_prefix = prefix
        return next(router.route_gen(term))

    solo = []
    for i, f in enumerate(flows):
        req = first_request(f.term, f"solo{i}:")
        solo.append(route_window_planes_fused(*req.f_args,
                                              **req.f_kwargs))
    # fresh requests: the solo dispatches donated their state
    reqs = [first_request(f.term, f"multi{i}:")
            for i, f in enumerate(flows)]
    assert _shared_key(reqs[0]) == _shared_key(reqs[1])
    states, dyns, statics = zip(*(_split_request(r) for r in reqs))
    assert statics[0] != statics[1]           # two different ladders
    kw0 = reqs[0].f_kwargs
    outs = route_window_planes_multi(
        router.pg, router.dev, tuple(states), tuple(dyns),
        job_statics=tuple(statics), n_colors=kw0["n_colors"],
        plane_dtype=kw0["plane_dtype"])
    jax.block_until_ready(outs)
    for got, want in zip(outs, solo):
        assert len(got) == len(want) == 24
        assert np.asarray(want[4]).any()      # nets were routed
        for k, (a, b) in enumerate(zip(got, want)):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True), k


# ---- flow_doctor rebatch rules (crafted summaries, no jax) ---------

def _doctor():
    spec = importlib.util.spec_from_file_location("flow_doctor",
                                                  FLOW_DOCTOR)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _summary(events, rounds, fused=True, compiles=0, counters=None):
    n = {}
    for ev in events:
        for c in ev.get("causes", ()):
            k = f"route.serve.rebatch.{c['cause']}"
            n[k] = n.get(k, 0) + 1
    n["route.serve.rebatch.events"] = len(events)
    if counters is not None:
        n = counters
    return {"dispatch_compiles": compiles,
            "rebatch": {"fused": fused, "rounds": rounds,
                        "events": events, "counters": n}}


def test_doctor_rebatch_healthy_and_warm():
    fd = _doctor()
    ev = [{"round": 1, "jobs": ["a", "b"], "lane_occupancy": 0.4,
           "causes": [{"job_id": "a", "cause": "join"},
                      {"job_id": "b", "cause": "join"}]},
          {"round": 3, "jobs": ["b"], "lane_occupancy": 0.4,
           "causes": [{"job_id": "a", "cause": "finish"}]}]
    errs, _ = fd.check_rebatch(_summary(ev, rounds=4), warm=True)
    assert errs == []


def test_doctor_rebatch_rules_fire():
    fd = _doctor()
    # unknown cause outside the taxonomy
    ev = [{"round": 1, "jobs": ["a"],
           "causes": [{"job_id": "a", "cause": "vibes"}]}]
    errs, _ = fd.check_rebatch(_summary(ev, rounds=2))
    assert any("unknown cause" in e for e in errs)
    # more rebatch events than rounds: a mid-slice repack
    ev = [{"round": 1, "jobs": ["a"],
           "causes": [{"job_id": "a", "cause": "join"}]}] * 3
    errs, _ = fd.check_rebatch(_summary(ev, rounds=1))
    assert any("slice boundary" in e for e in errs)
    # fused rounds ran but the event log is mute
    errs, _ = fd.check_rebatch(_summary([], rounds=3, counters={}))
    assert any("without recording" in e for e in errs)
    # warm gate: any compile is a failure
    errs, _ = fd.check_rebatch(_summary([], rounds=0, compiles=2),
                               warm=True)
    assert any("dispatch_compiles==0" in e for e in errs)
    # counter/event-log disagreement
    ev = [{"round": 1, "jobs": ["a"],
           "causes": [{"job_id": "a", "cause": "join"}]}]
    errs, _ = fd.check_rebatch(_summary(
        ev, rounds=2,
        counters={"route.serve.rebatch.events": 5,
                  "route.serve.rebatch.join": 1}))
    assert any("event log holds" in e for e in errs)


# ---- kill-and-restart parity (real jax, fresh processes) -----------

_LUTS = 6


def _daemon_cmd(box, extra=()):
    return [sys.executable, os.path.join(REPO, "tools",
                                         "route_daemon.py"),
            "run", "--inbox", box, "--luts", str(_LUTS),
            "--slice", "2", "--heartbeat_s", "2.0",
            "--exit_when_idle", "2",
            "--summary", os.path.join(box, "summary.json"), *extra]


def _submit(box, seed, job_id):
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "route_daemon.py"),
         "submit", "--inbox", box, "--luts", str(_LUTS),
         "--seed", str(seed), "--job_id", job_id],
        check=True, capture_output=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _wirelengths(box):
    doc = json.load(open(os.path.join(box, "summary.json")))
    return ({j["job_id"]: (j["state"], j.get("wirelength"))
             for j in doc["jobs"]}, doc)


@pytest.mark.slow
def test_fused_daemon_sigkill_midslice_restart_parity(tmp_path):
    """A --fused daemon SIGKILLed mid-fused-slice (after a durable
    per-job checkpoint exists), restarted on the same inbox: every
    job DONE with wirelengths bit-identical to an uninterrupted
    INTERLEAVED reference daemon — fused scheduling, the crash, and
    the per-job checkpoint resume all preserved solo QoR.  The doctor
    (daemon + rebatch rule sets) signs off."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # reference: an uninterrupted interleaved daemon, same two jobs —
    # doubles as the fused-vs-solo QoR oracle
    ref_box = str(tmp_path / "ref")
    os.makedirs(ref_box)
    _submit(ref_box, 3, "jobA")
    _submit(ref_box, 4, "jobB")
    subprocess.run(_daemon_cmd(ref_box), check=True, env=env,
                   capture_output=True, timeout=420)
    ref, _ = _wirelengths(ref_box)
    assert all(state == "done" for state, _ in ref.values())

    box = str(tmp_path / "box")
    os.makedirs(box)
    _submit(box, 3, "jobA")
    _submit(box, 4, "jobB")
    proc = subprocess.Popen(_daemon_cmd(box, ("--fused",)), env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    ckpt = os.path.join(box, "ckpt")
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if (os.path.isdir(ckpt)
                    and any(n.endswith(".ck")
                            for n in os.listdir(ckpt))):
                break
            if proc.poll() is not None:
                pytest.fail("fused daemon exited before any durable "
                            "checkpoint was written")
            time.sleep(0.2)
        else:
            pytest.fail("no durable checkpoint appeared in time")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert not os.path.exists(os.path.join(box, "summary.json"))

    # restart fused on the same inbox: journal recovery + per-job
    # checkpoint resume inside the re-packed batch
    subprocess.run(_daemon_cmd(box, ("--fused",)), check=True, env=env,
                   capture_output=True, timeout=420)
    got, doc = _wirelengths(box)
    assert got == ref, (f"post-SIGKILL fused recovery changed QoR: "
                        f"{got} vs interleaved {ref}")
    assert doc["daemon"]["metrics"].get("route.daemon.recovered", 0) > 0
    assert doc["rebatch"]["fused"]
    assert doc["rebatch"]["events"], "fused daemon never rebatched"
    r = subprocess.run([sys.executable, FLOW_DOCTOR, "--daemon-summary",
                        os.path.join(box, "summary.json")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
