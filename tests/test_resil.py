"""Resilience layer (parallel_eda_tpu/resil/): seeded fault plans,
durable checkpoints, the dispatch watchdog, and the degradation
ladder — plus the flow_doctor resil rule set and the service-level
crash/chaos recovery paths.

Unit layers run against fakes (no jax, fake clocks/sleeps); the two
service tests route a real 15-LUT circuit and assert the recovery
paths are BIT-identical in QoR to the undisturbed run:

* kill-and-resume — a "crashed" process's durable checkpoint resumes
  in a fresh service to the same wirelength as a solo route;
* chaos parity — a seeded multi-site fault plan (>= 4 kinds fired)
  perturbs timing only.

    python -m pytest tests/ -m resil
"""

import hashlib
import importlib.util
import json
import os

import pytest

from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics
from parallel_eda_tpu.resil import (CheckpointStore, DispatchGuard,
                                    FaultPlan, ResilOpts)
from parallel_eda_tpu.resil.faults import (SITES, BackendLostError,
                                           FaultInjected)
from parallel_eda_tpu.resil.ladder import DIMS, DegradationLadder
from parallel_eda_tpu.resil.watchdog import DispatchPoisonedError, Rung
from parallel_eda_tpu.serve.queue import JobQueue, JobState, RouteJob

pytestmark = pytest.mark.resil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOW_DOCTOR = os.path.join(REPO, "tools", "flow_doctor.py")


@pytest.fixture(autouse=True)
def _clean_obs():
    set_metrics(MetricsRegistry())
    yield
    set_metrics(MetricsRegistry())


def _vals(prefix="route.resil."):
    return get_metrics().values(prefix)


# ---- fault plan (no jax) -------------------------------------------

def test_fault_plan_replays_across_instances():
    spec = "dispatch.hang:2:6,backend.loss:1:3"
    a = FaultPlan.parse(7, spec)
    b = FaultPlan.parse(7, spec)
    assert a._fire_at == b._fire_at
    fires_a = [a.fire("dispatch.hang") is not None for _ in range(6)]
    fires_b = [b.fire("dispatch.hang") is not None for _ in range(6)]
    assert fires_a == fires_b
    assert sum(fires_a) == 2
    # past the horizon the site never fires again
    assert a.fire("dispatch.hang") is None


def test_fault_plan_unknown_site_fails_fast():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan(1, {"dispatch.typo": 1})
    assert "dispatch.hang" in SITES


def test_fault_plan_raise_summary_and_metrics():
    p = FaultPlan(3, {"backend.loss": (1, 1), "dispatch.error": (1, 1)})
    with pytest.raises(BackendLostError):
        p.raise_if("backend.loss")
    with pytest.raises(FaultInjected) as ei:
        p.raise_if("dispatch.error", detail="jit")
    assert not isinstance(ei.value, BackendLostError)
    assert ei.value.fault.site == "dispatch.error"
    p.raise_if("dispatch.error")          # seq 1: not scheduled
    assert p.fire("corpus.torn") is None  # site not in the plan
    s = p.summary()
    assert s["kinds_fired"] == 2
    assert s["fired"]["backend.loss"] == [0]
    assert p.fired_sites() == ["backend.loss", "dispatch.error"]
    assert _vals()["route.resil.injections"] == 2


# ---- durable checkpoints (no jax; any picklable state) -------------

def test_checkpoint_roundtrip_prev_fallback_and_drop(tmp_path):
    st = CheckpointStore(str(tmp_path))
    st.save("j1", {"it": 1})
    st.save("j1", {"it": 2})
    assert st.load("j1") == {"it": 2}
    # tear the current generation: load must fall back to prev
    p = st._path("j1")
    with open(p, "r+b") as f:
        f.truncate(20)
    set_metrics(MetricsRegistry())
    assert st.load("j1") == {"it": 1}
    v = _vals()
    assert v["route.resil.checkpoint_fallbacks"] == 1
    assert v["route.resil.checkpoint_recoveries"] == 1
    # corrupt both generations: restart-from-scratch (None)
    with open(p + ".prev", "r+b") as f:
        f.write(b"not a checkpoint")
    assert st.load("j1") is None
    st.drop("j1")
    assert not os.path.exists(p)
    assert st.load("j1") is None


def test_checkpoint_corrupt_injection_detected(tmp_path):
    plan = FaultPlan(5, {"checkpoint.corrupt": (1, 1)})
    st = CheckpointStore(str(tmp_path), plan=plan)
    st.save("j", {"it": 9})            # injected: file torn after write
    assert st.load("j") is None        # no prev generation yet
    assert _vals()["route.resil.injections"] == 1
    # a later (clean) save recovers normally
    st.save("j", {"it": 10})
    assert st.load("j") == {"it": 10}


def test_checkpoint_gc_orphaned_tmp_on_startup(tmp_path):
    # a SIGKILL between the tmp write and the rename leaks <id>.ck.tmp;
    # a crash loop leaks them without bound.  Startup GC removes ONLY
    # the store's own orphans, never live checkpoints or foreign files.
    st = CheckpointStore(str(tmp_path))
    st.save("live", {"it": 7})
    for name in ("dead1.ck.tmp", "dead2.ck.tmp"):
        with open(os.path.join(str(tmp_path), name), "wb") as f:
            f.write(b"torn mid-write")
    with open(os.path.join(str(tmp_path), "notes.txt"), "w") as f:
        f.write("keep me")
    set_metrics(MetricsRegistry())
    st2 = CheckpointStore(str(tmp_path))
    left = sorted(os.listdir(str(tmp_path)))
    assert "dead1.ck.tmp" not in left and "dead2.ck.tmp" not in left
    assert "notes.txt" in left
    assert st2.load("live") == {"it": 7}
    assert _vals()["route.resil.checkpoint_gc"] == 2
    # idempotent: a clean startup GCs nothing and counts nothing
    set_metrics(MetricsRegistry())
    CheckpointStore(str(tmp_path))
    assert "route.resil.checkpoint_gc" not in _vals()


# ---- dispatch guard (fake clock + recorded sleeps; no jax) ---------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_guard_retry_backoff_exponential_capped():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise RuntimeError("boom")
        return "ok"

    g = DispatchGuard(max_attempts=4, timeout_s=10.0, backoff_s=0.1,
                      backoff_mult=4.0, backoff_max_s=0.9,
                      clock=_Clock(), sleep=sleeps.append)
    assert g.run(("k",), [Rung("jit", flaky)]) == "ok"
    assert sleeps == [0.1, 0.4, 0.9]   # exponential, capped at the max
    v = _vals()
    assert v["route.resil.retries"] == 3
    assert v["route.resil.dispatch_errors"] == 3
    assert v["route.resil.retry_cap"] == 4
    assert v["route.resil.backoff_ms"] == pytest.approx(1400.0)
    assert "route.resil.quarantined_variants" not in v


def test_guard_quarantine_steps_down_and_sticks():
    evictions = []

    def bad():
        raise RuntimeError("dead rung")

    g = DispatchGuard(max_attempts=2, backoff_s=0.0,
                      clock=_Clock(), sleep=lambda s: None,
                      ladder=DegradationLadder())
    out = g.run("k1", [Rung("aot", bad,
                            on_quarantine=evictions.append),
                       Rung("jit", lambda: 42)])
    assert out == 42
    assert g.quarantined("k1") == {"aot"}
    assert evictions and "dead rung" in evictions[0]
    # the same variant skips the quarantined rung on later dispatches
    assert g.run("k1", [Rung("aot", bad), Rung("jit", lambda: 7)]) == 7
    v = _vals()
    assert v["route.resil.dispatch_errors"] == 2   # only the first run
    assert v["route.resil.quarantined_variants"] == 1
    assert v["route.resil.degradation_steps"] == 1
    # quarantine is per-variant: a different key still tries "aot"
    assert g.quarantined("k2") == set()


def test_guard_poison_after_all_rungs_exhausted():
    def bad():
        raise RuntimeError("x")

    g = DispatchGuard(max_attempts=2, backoff_s=0.0,
                      clock=_Clock(), sleep=lambda s: None)
    with pytest.raises(DispatchPoisonedError) as ei:
        g.run("k", [Rung("aot", bad), Rung("jit", bad)])
    assert ei.value.key == "k"
    v = _vals()
    assert v["route.resil.poisoned_dispatches"] == 1
    assert v["route.resil.quarantined_variants"] == 2
    # everything quarantined: the most conservative rung still gets
    # one more chance instead of wedging the dispatch forever
    assert g.run("k", [Rung("aot", bad), Rung("jit", lambda: "ok")]) \
        == "ok"


def test_guard_watchdog_quarantines_slow_rung():
    clock = _Clock()

    def slow():
        clock.t += 5.0
        return "late"

    g = DispatchGuard(max_attempts=2, timeout_s=1.0, clock=clock,
                      sleep=lambda s: None)
    # a completed-but-overbudget dispatch keeps its result...
    assert g.run("k", [Rung("aot", slow), Rung("jit", lambda: "f")]) \
        == "late"
    assert g.quarantined("k") == {"aot"}
    assert _vals()["route.resil.watchdog_timeouts"] == 1
    # ...but later dispatches of the variant skip the slow rung
    assert g.run("k", [Rung("aot", slow),
                       Rung("jit", lambda: "fast")]) == "fast"


def test_guard_injected_hang_counts_as_timeout_then_retries():
    plan = FaultPlan(1, {"dispatch.hang": (1, 1)})
    g = DispatchGuard(max_attempts=2, backoff_s=0.0, plan=plan,
                      clock=_Clock(), sleep=lambda s: None)
    assert g.run("k", [Rung("jit", lambda: 3)]) == 3
    v = _vals()
    assert v["route.resil.watchdog_timeouts"] == 1
    assert v["route.resil.injections"] == 1
    assert v["route.resil.retries"] == 1
    assert "route.resil.dispatch_errors" not in v


def test_ladder_levels_records_and_floor():
    lad = DegradationLadder()
    assert lad.snapshot() == {"pipeline": "pipelined",
                              "program": "aot",
                              "mesh": "pallas_halo"}
    assert lad.step("pipeline", reason="poisoned dispatch")
    assert lad.level("pipeline") == 1
    assert lad.name("pipeline") == "sync"
    assert not lad.step("pipeline", reason="again")   # at the floor
    lad.record("aot", reason="quarantined")
    v = _vals()
    assert v["route.resil.level.pipeline"] == 1
    assert v["route.resil.level.program"] == 0
    assert v["route.resil.degradation_steps"] == 2
    assert set(DIMS) == {"pipeline", "program", "mesh"}


# ---- queue backoff vs deadline (fake clock; no jax) ----------------

def test_queue_retry_backoff_past_deadline_times_out():
    now = [0.0]
    q = JobQueue(clock=lambda: now[0], sleep=lambda s: None)
    j = q.admit(RouteJob(tenant="t", payload=None, deadline_s=1.0,
                         max_retries=5, backoff_s=64.0))

    def runner(job):
        now[0] += 0.1
        raise RuntimeError("flaky backend")

    q.run(runner)
    # the capped backoff (2s) still lands past the 1s deadline: the
    # queue fails fast instead of sleeping into a TIMEOUT
    assert j.state == JobState.TIMEOUT
    assert "retry backoff 2.000s lands past deadline" in j.error
    assert j.failure_reason.startswith("timeout:")
    assert "attempts=1" in j.failure_reason
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_timeout"] == 1
    assert "route.serve.jobs_retried" not in v


def test_queue_backoff_capped_and_terminal_reason():
    now = [0.0]
    waits = []

    def sleep(s):
        waits.append(s)
        now[0] += s

    q = JobQueue(clock=lambda: now[0], sleep=sleep)
    j = q.admit(RouteJob(tenant="t", payload=None, max_retries=2,
                         backoff_s=1.0, backoff_mult=10.0,
                         backoff_max_s=3.0))

    def runner(job):
        raise RuntimeError("boom")

    q.run(runner)
    assert j.state == JobState.FAILED
    assert j.attempts == 3
    assert waits == [1.0, 3.0]   # 10.0 uncapped -> backoff_max_s
    assert j.failure_reason == "failed: RuntimeError: boom (attempts=3)"
    # a non-terminal job has no failure reason
    ok = JobQueue().admit(RouteJob(tenant="t", payload=None))
    assert ok.failure_reason is None


# ---- AOT library degrade paths (jax import, no export) -------------

def _fake_library(tmp_path, key, blob):
    from parallel_eda_tpu.serve import library as lib_mod
    kid = lib_mod.key_id(key)
    d = tmp_path / "lib"
    d.mkdir(exist_ok=True)
    (d / f"{kid}.jexp").write_bytes(blob)
    idx = {"provenance": lib_mod._provenance(),
           "entries": {kid: {"key": list(key), "file": f"{kid}.jexp",
                             "sig": None, "bytes": len(blob),
                             "sha256": hashlib.sha256(blob).hexdigest()}}}
    (d / lib_mod.INDEX_NAME).write_text(json.dumps(idx, default=str))
    return lib_mod.ProgramLibrary(str(d)), kid


def test_library_checksum_mismatch_degrades_to_jit(tmp_path):
    from parallel_eda_tpu.serve import library as lib_mod
    key = ("v", 1)
    lib, kid = _fake_library(tmp_path, key, b"torn blob bytes")
    # break the recorded checksum: load() must drop the entry with a
    # counted error, NOT refuse the library or raise later
    p = tmp_path / "lib" / lib_mod.INDEX_NAME
    idx = json.loads(p.read_text())
    idx["entries"][kid]["sha256"] = "00" * 32
    p.write_text(json.dumps(idx))
    lib = lib_mod.ProgramLibrary(str(tmp_path / "lib"))
    assert lib.load() == 0
    assert lib.stale_reason is None
    assert lib.dropped and "checksum" in lib.dropped[0][1]
    assert get_metrics().counter("route.serve.aot_errors").value == 1
    assert lib.dispatch(key, lambda x: x + 1, (41,), {}) == 42
    assert get_metrics().counter(
        "route.serve.jit_fallbacks").value == 1


def test_library_corrupt_injection_evicts_to_jit(tmp_path):
    key = ("v", 2)
    lib, kid = _fake_library(tmp_path, key, b"healthy-looking blob")
    assert lib.load() == 1
    lib.fault_plan = FaultPlan(3, {"library.corrupt": (1, 1)})
    # the injected stale-entry fault fires inside dispatch(): the
    # entry is evicted and the call degrades to the live path
    assert lib.dispatch(key, lambda x: x * 2, (21,), {}) == 42
    assert kid in lib._dead
    v = get_metrics().values()
    assert v["route.serve.aot_errors"] == 1
    assert v["route.serve.jit_fallbacks"] == 1
    assert v["route.resil.injections"] == 1


def test_library_evict_rewrites_disk_index(tmp_path):
    from parallel_eda_tpu.serve import library as lib_mod
    key = ("v", 3)
    lib, kid = _fake_library(tmp_path, key, b"blob")
    assert lib.load() == 1
    lib.evict(key, reason="quarantined by watchdog")
    assert lib.keys() == []
    assert get_metrics().counter(
        "route.serve.library_evictions").value == 1
    # a later process never serves the entry either
    on_disk = json.loads(
        (tmp_path / "lib" / lib_mod.INDEX_NAME).read_text())
    assert kid not in on_disk["entries"]


# ---- flow_doctor resil rule set (no jax) ---------------------------

def _fd():
    spec = importlib.util.spec_from_file_location("flow_doctor_resil",
                                                  FLOW_DOCTOR)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _summary(metrics=None, jobs=None):
    return {"jobs": jobs or [],
            "resil": {"metrics": {f"route.resil.{k}": v
                                  for k, v in (metrics or {}).items()},
                      "ladder": {}, "faults": {"kinds_fired": 2}}}


def test_doctor_resil_healthy_recovery_passes():
    errs, notes = _fd().check_resil(_summary(
        metrics=dict(injections=3, watchdog_timeouts=1, retries=2,
                     retry_cap=2, backoff_ms=150.0,
                     quarantined_variants=1, degradation_steps=1),
        jobs=[{"job_id": "j0", "state": "done",
               "failure_reason": None}]))
    assert errs == []
    assert notes and "injections=3" in notes[0]


def test_doctor_quarantine_without_cause_fails():
    errs, _ = _fd().check_resil(_summary(
        metrics=dict(quarantined_variants=1)))
    assert any("quarantined" in e and "without" in e for e in errs)


def test_doctor_unbounded_or_uncapped_retries_fail():
    fd = _fd()
    errs, _ = fd.check_resil(_summary(
        metrics=dict(injections=1, retries=5, retry_cap=2,
                     backoff_ms=10.0)))
    assert any("unbounded retries" in e for e in errs)
    errs, _ = fd.check_resil(_summary(
        metrics=dict(injections=2, retries=2, backoff_ms=10.0)))
    assert any("retry_cap" in e for e in errs)
    errs, _ = fd.check_resil(_summary(
        metrics=dict(injections=3, retries=3, retry_cap=2)))
    assert any("backoff" in e for e in errs)


def test_doctor_terminal_job_without_reason_fails():
    fd = _fd()
    errs, _ = fd.check_resil(_summary(
        jobs=[{"job_id": "j1", "state": "failed"}]))
    assert any("failure_reason" in e for e in errs)
    errs, _ = fd.check_resil(_summary(
        jobs=[{"job_id": "j1", "state": "failed",
               "failure_reason": "failed: boom (attempts=2)"}]))
    assert errs == []
    errs, _ = fd.check_resil({})
    assert any("no resil section" in e for e in errs)


# ---- service-level recovery (real routing, 15 LUTs) ----------------

def _mini_service(rr, tmp_path, **resil_kw):
    from parallel_eda_tpu.route.router import RouterOpts
    from parallel_eda_tpu.serve.service import RouteService
    return RouteService(
        rr, RouterOpts(batch_size=32, sink_group=0),
        slice_iters=2, runs_dir=str(tmp_path / "runs"),
        scenario="resil_test",
        resil=ResilOpts(checkpoint_dir=str(tmp_path / "ck"),
                        **resil_kw))


def test_crash_and_fresh_process_resume_parity(tmp_path):
    """Tentpole gate: run one slice, "crash" (abandon the service),
    then resume the SAME job id in a fresh service from the durable
    checkpoint — final wirelength bit-identical to a solo route."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.route import Router, RouterOpts
    from parallel_eda_tpu.serve.service import ServeJobSpec

    f = synth_flow(num_luts=15, seed=1)
    ref = Router(f.rr, RouterOpts(batch_size=32,
                                  sink_group=0)).route(f.term)
    assert ref.success

    svc1 = _mini_service(f.rr, tmp_path)
    svc1.admit(ServeJobSpec(term=f.term, name="s1"), job_id="jobA")
    svc1.queue.run(svc1._runner, max_slices=1)   # one slice, then die
    ck_file = svc1.resil.store._path("jobA")
    assert os.path.exists(ck_file), "durable checkpoint not flushed"

    # fresh process: new metrics registry, new service, same dirs
    set_metrics(MetricsRegistry())
    svc2 = _mini_service(f.rr, tmp_path)
    j = svc2.admit(ServeJobSpec(term=f.term, name="s1"), job_id="jobA")
    svc2.run()
    assert j.state == JobState.DONE
    assert j.result["wirelength"] == ref.wirelength
    assert j.result["iterations"] == ref.iterations
    v = _vals()
    assert v["route.resil.checkpoint_recoveries"] >= 1
    assert not os.path.exists(ck_file)   # dropped after success


def test_service_chaos_parity_multi_site(tmp_path):
    """Chaos gate in miniature: two jobs under a seeded multi-site
    fault plan — everything completes, >= 4 distinct fault kinds
    fired, per-job wirelength bit-identical to the fault-free run."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.serve.service import ServeJobSpec

    flows = [synth_flow(num_luts=15, seed=s) for s in (1, 2)]
    ref = _mini_service(flows[0].rr, tmp_path / "ref")
    for i, fl in enumerate(flows):
        ref.admit(ServeJobSpec(term=fl.term, name=f"s{i}"),
                  tenant=f"t{i}")
    ref_jobs = ref.run()
    assert all(j.state == JobState.DONE for j in ref_jobs)

    set_metrics(MetricsRegistry())
    plan = FaultPlan.parse(
        7, "dispatch.hang:2:4,dispatch.error:1:4,"
           "checkpoint.corrupt:1:2,corpus.torn:1:2,backend.loss:1:3")
    # nonzero backoff: the doctor's hot-retry-loop rule (rightly)
    # rejects a retry policy with zero total backoff
    svc = _mini_service(flows[0].rr, tmp_path / "chaos",
                        fault_plan=plan, backoff_s=0.01)
    for i, fl in enumerate(flows):
        svc.admit(ServeJobSpec(term=fl.term, name=f"s{i}"),
                  tenant=f"t{i}", max_retries=3)
    jobs = svc.run()
    assert all(j.state == JobState.DONE for j in jobs)
    assert len(plan.fired_sites()) >= 4, plan.summary()
    for jc, jr in zip(jobs, ref_jobs):
        assert jc.result["wirelength"] == jr.result["wirelength"]
        assert jc.result["iterations"] == jr.result["iterations"]
    v = _vals()
    assert v["route.resil.injections"] >= 4
    # every recovery is observable, and the doctor's gate agrees
    errs, _ = _fd().check_resil({
        "jobs": [{"job_id": j.job_id, "state": j.state.value,
                  "failure_reason": j.failure_reason} for j in jobs],
        "resil": {"metrics": v, "ladder": svc.resil.ladder.snapshot(),
                  "faults": plan.summary()}})
    assert errs == []
