"""Multi-tenant route service (parallel_eda_tpu/serve/).

Four layers, matching the subsystem:

* library — AOT export/reload round trip: a "fresh process" (variant
  seen-set + metrics cleared) serves every window from deserialized
  executables with ``route.dispatch.compiles == 0`` and BIT-identical
  results vs the jit path; provenance mismatch degrades gracefully to
  jit.
* queue — priorities, deadlines, retry-with-backoff, preemption
  round-robin, all against fake runners/clocks (no jax).
* batcher — strict per-job demux of the shared packed plan, and the
  cross-job claim itself: a packed relaxation batch mixing two jobs'
  nets equals each job's solo batch bit-for-bit (interpret mode).
* service — two tenants through the queue with preemption slices:
  per-job wirelength identical to solo, legal, tenant-stamped corpus
  rows and route.serve.* telemetry.

    python -m pytest tests/ -m serve
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.route import router as router_mod
from parallel_eda_tpu.serve.batcher import pack_jobs
from parallel_eda_tpu.serve.queue import JobQueue, JobState, RouteJob

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    set_metrics(MetricsRegistry())
    yield
    set_metrics(MetricsRegistry())


# ---- queue (no jax) ------------------------------------------------

def _job(tenant="t", priority=0, **kw):
    return RouteJob(tenant=tenant, payload=None, priority=priority, **kw)


def test_queue_priority_order():
    q = JobQueue()
    lo = q.admit(_job(priority=0))
    hi = q.admit(_job(priority=5))
    mid = q.admit(_job(priority=2))
    ran = []

    def runner(job):
        ran.append(job.job_id)
        return "done", None

    q.run(runner)
    assert ran == [hi.job_id, mid.job_id, lo.job_id]
    assert all(j.state == JobState.DONE for j in (lo, mid, hi))
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_admitted"] == 3
    assert v["route.serve.jobs_done"] == 3


def test_queue_deadline_timeout():
    now = [0.0]
    q = JobQueue(clock=lambda: now[0])
    ok = q.admit(_job(deadline_s=10.0))
    late = q.admit(_job(deadline_s=1.0))

    def runner(job):
        if job.preemptions == 0:
            now[0] += 2.0       # each first slice costs 2s of fake wall
            return "preempted", f"ck-{job.job_id}"
        return "done", None

    q.run(runner)
    # `late` blows its 1s deadline at the re-slice check; `ok` finishes
    assert ok.state == JobState.DONE
    assert late.state == JobState.TIMEOUT
    assert "deadline" in late.error
    assert get_metrics().values(
        "route.serve.")["route.serve.jobs_timeout"] == 1


def test_queue_retry_backoff_then_failed():
    q = JobQueue()
    job = q.admit(_job(max_retries=2, backoff_s=0.001))
    attempts = []

    def runner(j):
        attempts.append(j.checkpoint)   # retries restart clean
        raise RuntimeError("device fell over")

    q.run(runner)
    assert job.state == JobState.FAILED
    assert job.attempts == 3            # initial + 2 retries
    assert attempts == [None, None, None]
    assert "device fell over" in job.error
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_retried"] == 2
    assert v["route.serve.jobs_failed"] == 1


def test_queue_runner_without_a_verdict_is_a_failure():
    """A runner that returns no (verdict, value) for a job fails that
    job -- silence is never success -- and the queue goes on to the
    next; a verdict the state machine does not know is the caller's
    bug and raises out of run()."""
    q = JobQueue()
    ghost = q.admit(_job(priority=1))
    ok = q.admit(_job())
    q.run(lambda j: None if j is ghost else ("done", {}))
    assert ghost.state == JobState.FAILED and ghost.error
    assert ok.state == JobState.DONE
    assert get_metrics().values(
        "route.serve.")["route.serve.jobs_failed"] == 1

    q.admit(_job())
    with pytest.raises(ValueError, match="finished"):
        q.run(lambda j: ("finished", None))


def test_queue_run_skips_tombstones_without_spending_a_slice():
    """A job evicted while queued (overload shedding) is a tombstone
    in the heap: run() never hands it to the runner, it costs no slice
    of ``max_slices``, and it stays SHED."""
    q = JobQueue()
    shed = q.admit(_job(priority=9))        # would have run first
    a = q.admit(_job())
    b = q.admit(_job())
    assert q.evict(shed.job_id, error="overload") is shed
    assert q.depth() == 2
    seen = []
    q.run(lambda j: (seen.append(j.job_id), ("done", None))[1],
          max_slices=2)
    assert seen == [a.job_id, b.job_id]
    assert shed.state == JobState.SHED and shed.slices == 0
    assert shed.error == "overload"


def test_queue_preemption_round_robin():
    q = JobQueue()
    a = q.admit(_job())
    b = q.admit(_job())
    trace = []

    def runner(job):
        trace.append(job.job_id)
        if job.preemptions < 2:
            return "preempted", f"ck{len(trace)}"
        return "done", None

    q.run(runner)
    # equal priority: slices interleave instead of one job hogging
    assert trace == [a.job_id, b.job_id] * 3
    assert a.preemptions == b.preemptions == 2
    assert a.checkpoint is not None     # last checkpoint retained
    assert get_metrics().values(
        "route.serve.")["route.serve.jobs_preempted"] == 4


def test_queue_aging_prevents_starvation():
    # a steady stream of priority-5 work must not starve an old
    # priority-0 job: with aging_rate=1 the old job's effective
    # priority overtakes any high-priority job admitted >5s later
    # (static heap key r*t_admit - p keeps the order time-invariant)
    now = [0.0]
    q = JobQueue(clock=lambda: now[0], aging_rate=1.0)
    old = q.admit(_job(priority=0))
    fresh = []
    for _ in range(4):
        now[0] += 2.0
        fresh.append(q.admit(_job(priority=5)))
    assert q.effective_priority(old) == pytest.approx(8.0)
    ran = []
    q.run(lambda j: (ran.append(j.job_id), ("done", None))[1])
    # hi jobs admitted at t=2,4 still beat it; the t=6,8 ones don't
    assert ran.index(old.job_id) == 2
    assert ran == [fresh[0].job_id, fresh[1].job_id, old.job_id,
                   fresh[2].job_id, fresh[3].job_id]

    # aging_rate=0 (the default) is exactly the old strict-priority
    # behavior: the low-priority job starves to the back of the line
    q0 = JobQueue(clock=lambda: now[0], aging_rate=0.0)
    old0 = q0.admit(_job(priority=0))
    for _ in range(4):
        now[0] += 2.0
        q0.admit(_job(priority=5))
    ran0 = []
    q0.run(lambda j: (ran0.append(j.job_id), ("done", None))[1])
    assert ran0.index(old0.job_id) == 4


def test_queue_idempotent_resubmission():
    q = JobQueue()
    a = q.admit(_job(job_id="jobA", priority=3))
    assert q.depth() == 1
    # replaying the same submission while queued returns the SAME job
    # and adds no heap entry
    dup = q.admit(_job(job_id="jobA", priority=0))
    assert dup is a and dup.priority == 3
    assert q.depth() == 1
    q.run(lambda j: ("done", None))
    assert a.state is JobState.DONE
    # replaying after completion must not resurrect or re-run it
    dup2 = q.admit(_job(job_id="jobA"))
    assert dup2 is a and a.state is JobState.DONE
    assert q.depth() == 0
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_admitted"] == 1
    assert v["route.serve.jobs_deduped"] == 2


# ---- batcher -------------------------------------------------------

def test_batcher_strict_demux():
    rng = np.random.default_rng(0)
    job_nets = {
        "jobA": (rng.integers(4, 12, 9), rng.integers(4, 12, 9)),
        "jobB": (rng.integers(4, 30, 5), rng.integers(4, 30, 5)),
    }
    plan = pack_jobs(job_nets, (6, 20, 17), (6, 21, 16))
    # every (job, net) lands in exactly one packed slot
    seen = {}
    for ri, rung in enumerate(plan.rungs):
        assert rung.block_nets >= 1
        for slot, (job, idx) in enumerate(rung.slots):
            assert (job, idx) not in seen
            seen[(job, idx)] = (ri, slot)
    assert len(seen) == 14 == plan.total_nets
    # demux agrees with the forward map, job by job
    for job, n in (("jobA", 9), ("jobB", 5)):
        slots = plan.job_slots(job)
        assert sorted(idx for _, _, idx in slots) == list(range(n))
        for ri, s, idx in slots:
            assert seen[(job, idx)] == (ri, s)
    v = get_metrics().values("route.serve.pack.")
    assert v["route.serve.pack.jobs"] == 2
    assert v["route.serve.pack.nets"] == 14
    assert v["route.serve.pack.shared_rungs"] == len(plan.rungs)


def test_batcher_cross_job_relax_parity():
    """Folding two jobs' nets into ONE relaxation batch changes
    nothing, net for net: canvases are per-net, so the relaxation is
    job-agnostic — the property that makes cross-job packing (and the
    multi-job window program) QoR-neutral by construction."""
    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route.planes import planes_relax
    from tests.test_kernel_pack import _assert_identical, _instance

    arch = minimal_arch(chan_width=6)
    _, pg, d0, cc, crit, w0 = _instance(arch, 4, 4, 7, seed=11)
    # nets 0..2 belong to job A, 3..6 to job B (same device graph)
    slA, slB = slice(0, 3), slice(3, 7)
    soloA = planes_relax(pg, d0[slA], cc[slA], crit[slA], w0[slA], 12)
    soloB = planes_relax(pg, d0[slB], cc[slB], crit[slB], w0[slB], 12)
    shared = planes_relax(pg, d0, cc, crit, w0, 12)
    # stats (index 3) are per-dispatch maxima, not per-net — compare
    # the per-net outputs (dist, pred, wenter)
    for sl, solo in ((slA, soloA), (slB, soloB)):
        _assert_identical([np.asarray(t)[sl] for t in shared[:3]],
                          solo[:3])
    assert int(shared[3][0]) == max(int(soloA[3][0]), int(soloB[3][0]))


# ---- runstore v2 + observatory tenant grouping ---------------------

def test_runstore_v2_tenant_fields(tmp_path):
    import parallel_eda_tpu.obs.runstore as rs
    rec = rs.make_record("serve_t", {"a": 1}, "nets_per_s", 10.0,
                         "nets/s", "cpu", "cpu0", tenant="acme",
                         job_id="job0001")
    assert rec["schema_version"] == rs.SCHEMA_VERSION == 2
    assert rec["tenant"] == "acme" and rec["job_id"] == "job0001"
    assert rs.validate_record(rec) == []
    # rows without tenancy (v1-era and single-tenant v2) stay valid
    legacy = {k: v for k, v in rec.items()
              if k not in ("tenant", "job_id")}
    legacy["schema_version"] = 1
    assert rs.validate_record(legacy) == []
    # present-but-mistyped tenancy is rejected
    bad = dict(rec, tenant=7)
    assert any("tenant" in e for e in rs.validate_record(bad))
    rs.append_run(str(tmp_path), rec)
    assert rs.read_runs(str(tmp_path), "serve_t")[0]["tenant"] == "acme"


def test_observatory_groups_by_tenant(tmp_path, capsys):
    import parallel_eda_tpu.obs.runstore as rs
    spec = importlib.util.spec_from_file_location(
        "observatory", os.path.join(REPO, "tools", "observatory.py"))
    obs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs)
    for tenant, job, val in (("acme", "j1", 10.0), ("beta", "j2", 9.0),
                             ("acme", "j3", 11.0)):
        rs.append_run(str(tmp_path), rs.make_record(
            "serve_t", {"a": 1}, "nets_per_s", val, "nets/s", "cpu",
            "cpu0", tenant=tenant, job_id=job,
            qor={"wirelength": 100, "iterations": 9}))
    # an untenanted scenario keeps the flat table
    rs.append_run(str(tmp_path), rs.make_record(
        "plain", {"b": 2}, "nets_per_s", 5.0, "nets/s", "cpu", "cpu0"))
    assert obs.print_report(rs, str(tmp_path)) == 0
    out = capsys.readouterr().out
    assert "### tenant acme  (2 run(s))" in out
    assert "### tenant beta  (1 run(s))" in out
    assert " j1 |" in out and " j2 |" in out
    # the flat scenario has no tenant sub-headers (bound the slice at the
    # next scenario header — sections are emitted in sorted order)
    plain = out.split("## plain")[1].split("\n## ")[0]
    assert "### tenant" not in plain


# ---- AOT program library -------------------------------------------

def test_library_static_split():
    """The exported call must receive the dynamic args ONLY — statics
    are baked in at export time (passing them is a pytree mismatch)."""
    from parallel_eda_tpu.route.planes import (WINDOW_STATIC_ARGNAMES,
                                               route_window_planes)
    from parallel_eda_tpu.serve import library as lib

    names = lib._positional_names(route_window_planes)
    # the constant matches the live signature
    assert set(WINDOW_STATIC_ARGNAMES) <= set(names)
    args = tuple(f"v_{n}" for n in names)
    kwargs = {"use_sdc": True, "crop_tile": (8, 8), "bb0_all": "bb0"}
    dyn_args, dyn_kwargs = lib._split_dynamic(
        route_window_planes, args, kwargs)
    assert len(dyn_args) == len(names) - sum(
        1 for n in names if n in WINDOW_STATIC_ARGNAMES)
    assert not any(f"v_{s}" in dyn_args for s in WINDOW_STATIC_ARGNAMES)
    assert dyn_kwargs == {"bb0_all": "bb0"}   # statics dropped


def test_library_provenance_mismatch_degrades_to_jit(tmp_path):
    import jax

    from parallel_eda_tpu.serve.library import (INDEX_NAME,
                                                ProgramLibrary,
                                                _provenance)
    lib_dir = tmp_path / "lib"
    lib_dir.mkdir()
    prov = _provenance()
    prov["jaxlib"] = "0.0.0-other"
    (lib_dir / "deadbeef.jexp").write_bytes(b"not a real module")
    (lib_dir / INDEX_NAME).write_text(json.dumps({
        "provenance": prov,
        "entries": {"deadbeef": {"key": [1], "file": "deadbeef.jexp"}},
    }))
    lib = ProgramLibrary(str(lib_dir))
    assert lib.load() == 0
    assert "provenance_mismatch:jaxlib" in lib.stale_reason
    # dispatch falls through to the live function (counted as fallback)
    fn = jax.jit(lambda x: x + 1)
    out = lib.dispatch(("k",), fn, (jax.numpy.ones(3),), {})
    assert np.allclose(np.asarray(out), 2.0)
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jit_fallbacks"] == 1
    assert "route.serve.aot_hits" not in v


def test_library_roundtrip_zero_compiles(tmp_path):
    """Satellite: export -> new-process-style reload -> serve.  The
    reloaded library must route the whole circuit with ZERO dispatch
    compiles and results bit-identical to the plain jit path."""
    from parallel_eda_tpu.flow import synth_flow

    f = synth_flow(num_luts=15, seed=1)
    base = dict(batch_size=32, sink_group=0)
    ref = Router(f.rr, RouterOpts(**base)).route(f.term)
    assert ref.success

    lib_dir = str(tmp_path / "lib")
    warm = Router(f.rr, RouterOpts(**base,
                                   program_library_dir=lib_dir))
    res_w = warm.route(f.term)
    assert res_w.success and res_w.wirelength == ref.wirelength
    assert warm.export_program_library() > 0

    # "fresh process": forget every seen variant and all counters; the
    # only warm state left is the library directory on disk
    saved = set(router_mod._DISPATCH_VARIANTS)
    router_mod._DISPATCH_VARIANTS.clear()
    set_metrics(MetricsRegistry())
    try:
        serve = Router(f.rr, RouterOpts(**base,
                                        program_library_dir=lib_dir))
        assert serve._library.stale_reason is None
        assert len(serve._library.keys()) > 0
        res = serve.route(f.term)
        v = get_metrics().values()
        # zero compiles means the counter was never even created
        assert v.get("route.dispatch.compiles", 0) == 0
        assert v["route.dispatch.cache_hits"] > 0
        assert v["route.serve.aot_hits"] > 0
        assert "route.serve.jit_fallbacks" not in v
        assert "route.serve.aot_errors" not in v
    finally:
        router_mod._DISPATCH_VARIANTS |= saved
    # bit-identical to the jit path
    assert res.success
    assert res.wirelength == ref.wirelength
    assert res.iterations == ref.iterations
    assert np.array_equal(res.paths, ref.paths)
    assert np.array_equal(res.occ, ref.occ)
    check_route(f.rr, f.term, res.paths, occ=res.occ)


# ---- service + satellite-1 multi-route safety ----------------------

def test_service_two_tenants_preemption_parity(tmp_path):
    """Two tenants' jobs through the queue with preemption slices:
    each job's QoR is identical to routing it alone, results are
    legal, and the corpus rows carry the tenant."""
    import parallel_eda_tpu.obs.runstore as rs
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.serve.service import RouteService, ServeJobSpec

    flows = [synth_flow(num_luts=15, seed=s) for s in (1, 2)]
    base = dict(batch_size=32, sink_group=0)
    solo = {}
    for fl in flows:
        r = Router(fl.rr, RouterOpts(**base)).route(fl.term)
        assert r.success
        solo[id(fl)] = r

    runs = str(tmp_path / "runs")
    svc = RouteService(flows[0].rr, RouterOpts(**base), slice_iters=2,
                       runs_dir=runs, scenario="serve_test",
                       cfg={"luts": 15})
    for i, fl in enumerate(flows):
        svc.admit(ServeJobSpec(term=fl.term, name=f"s{i + 1}"),
                  tenant=f"t{i}")
    jobs = svc.run()
    assert [j.state for j in jobs] == [JobState.DONE] * 2
    assert all(j.preemptions > 0 for j in jobs)
    for job, fl in zip(jobs, flows):
        assert job.result["wirelength"] == solo[id(fl)].wirelength
        res = job.result["result"]
        check_route(fl.rr, fl.term, res.paths, occ=res.occ)
    v = get_metrics().values("route.serve.")
    assert v["route.serve.jobs_done"] == 2
    assert v["route.serve.tenant.t0.jobs_done"] == 1
    assert v["route.serve.tenant.t1.wirelength"] == \
        solo[id(flows[1])].wirelength
    assert v["route.serve.pack.jobs"] == 2
    recs = rs.read_runs(runs, "serve_test")
    assert sorted(r["tenant"] for r in recs) == ["t0", "t1"]
    assert all(r["job_id"] for r in recs)


@pytest.fixture
def _restore_compile_cache():
    import jax

    was_dir = jax.config.jax_compilation_cache_dir
    was_state = router_mod._COMPILE_CACHE_DIR
    yield
    jax.config.update("jax_compilation_cache_dir", was_dir)
    router_mod._COMPILE_CACHE_DIR = was_state


@pytest.mark.parametrize("case", ["variable", "option", "neither",
                                  "worker_fence", "variable_no_fence"])
def test_compile_cache_rule(case, tmp_path, monkeypatch,
                            _restore_compile_cache):
    """THE cache rule (router.enable_persistent_compile_cache): the
    environment variable wins, else the explicit option, else the fixed
    <checkout>/.jax_cache; fleet workers are fenced into <base>/<worker>
    only when the variable is unset."""
    import jax

    env_dir = str(tmp_path / "from_env")
    opt_dir = str(tmp_path / "from_option")
    default = str(tmp_path / "checkout" / ".jax_cache")
    # the real default is inside the checkout, under a fixed name
    assert router_mod._DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        REPO, ".jax_cache")
    monkeypatch.setattr(router_mod, "_DEFAULT_COMPILE_CACHE_DIR", default)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if case.startswith("variable"):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = {
        "variable": lambda: router_mod.enable_persistent_compile_cache(
            opt_dir),
        "option": lambda: router_mod.enable_persistent_compile_cache(
            opt_dir),
        "neither": lambda: router_mod.enable_persistent_compile_cache(),
        "worker_fence": lambda: router_mod.enable_persistent_compile_cache(
            opt_dir, worker="w1"),
        "variable_no_fence":
            lambda: router_mod.enable_persistent_compile_cache(
                opt_dir, worker="w1"),
    }[case]()
    want = {"variable": env_dir, "option": opt_dir, "neither": default,
            "worker_fence": os.path.join(opt_dir, "w1"),
            "variable_no_fence": env_dir}[case]
    assert got == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    # and no other directory was made
    assert sorted(os.listdir(tmp_path)) == [
        os.path.relpath(want, tmp_path).split(os.sep)[0]]


def test_cache_lands_where_the_variable_says_and_nowhere_else(tmp_path):
    """A tiny CPU route through the CLI with JAX_COMPILATION_CACHE_DIR
    set AND a --compile_cache_dir given: the entries are under the
    variable's directory, the option's directory is never made and the
    checkout's default is untouched."""
    import subprocess
    import sys

    env_dir = tmp_path / "from_env"
    opt_dir = tmp_path / "from_option"
    default = os.path.join(REPO, ".jax_cache")

    def listing(d):
        # missing == empty: a concurrent cache-free test may make the
        # (empty) default directory, but nothing writes entries there
        return sorted(os.listdir(d)) if os.path.isdir(d) else []

    before = listing(default)
    r = subprocess.run(
        [sys.executable, "-m", "parallel_eda_tpu", "--luts", "10",
         "--arch", "minimal", "--route_chan_width", "12", "--no_place",
         "--no_timing", "--batch_size", "16",
         "--compile_cache_dir", str(opt_dir),
         "--out_dir", str(tmp_path / "out")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_ENABLE_COMPILATION_CACHE": "true",
             "JAX_COMPILATION_CACHE_DIR": str(env_dir)})
    assert r.returncode == 0, r.stdout + r.stderr
    assert listing(env_dir), "no cache entry under the variable's dir"
    assert not opt_dir.exists()
    assert listing(default) == before


def test_router_reuse_zeroes_pipeline_gauges():
    """Satellite: the serve loop calls route() many times on one
    process — route() zeroes the per-route pipeline gauges at entry so
    a job never inherits the previous job's value."""
    from parallel_eda_tpu.flow import synth_flow

    f = synth_flow(num_luts=10, seed=1)
    ra = Router(f.rr, RouterOpts(batch_size=16, sink_group=0))
    # leak a previous job's pipeline gauge; route() zeroes it at entry
    get_metrics().gauge("route.pipeline.stall_ms_total").set(1e9)
    res = ra.route(f.term)
    assert res.success
    v = get_metrics().values("route.pipeline.")
    assert v["route.pipeline.stall_ms_total"] < 1e9
