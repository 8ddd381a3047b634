"""Replicated route-worker fleet (serve/fleet.py, serve/transport.py,
resil/journal.py LeaseStore).

Four layers:

* lease units — the atomic ownership protocol on fake clocks: link-
  acquire exclusivity, renew rotation, monotonic expiry, one-winner
  steals, terminal releases, chaos force-expiry, and the monotonic
  heartbeat age that makes wall-clock steps unable to fake (or mask)
  a dead worker;
* transport units — an in-thread HTTP listener on an ephemeral port:
  durable roundtrip, torn requests writing nothing, seeded
  ``transport.drop`` chaos vs the client's bounded idempotent retry;
* fleet loop — two RouteDaemons (fake services, shared fake clock)
  over one inbox: deterministic job partitioning, foreign parking,
  lease-expiry failover, fencing of the stolen copy, and the
  ``lease.steal`` chaos site; plus the flow_doctor --fleet-summary
  rule set over crafted summaries and the traffic generator's seeded
  determinism;
* crash failover — two REAL worker processes over one inbox, one
  SIGKILLed mid-slice: the survivor steals the expired leases and
  finishes every job with wirelengths bit-identical to an
  uninterrupted solo daemon.

    python -m pytest tests/ -m fleet
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time
import types
from urllib import error as urlerror
from urllib import request as urlrequest

import pytest

from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics
from parallel_eda_tpu.resil.faults import FaultPlan
from parallel_eda_tpu.resil.journal import Heartbeat, LeaseStore
from parallel_eda_tpu.serve.daemon import (SUBMIT_NAME, DaemonOpts,
                                           RouteDaemon, heartbeat_name,
                                           preferred_worker, submit_job)
from parallel_eda_tpu.serve.daemon import InboxReader, LEASE_DIR
from parallel_eda_tpu.serve import fleet as fleet_mod
from parallel_eda_tpu.serve.fleet import SUPERVISOR_SITES, split_chaos
from parallel_eda_tpu.serve.queue import JobQueue, JobState, RouteJob
from parallel_eda_tpu.serve.transport import (InboxHTTPServer,
                                              TransportClient,
                                              TransportError)

pytestmark = pytest.mark.fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOW_DOCTOR = os.path.join(REPO, "tools", "flow_doctor.py")
TRAFFIC_GEN = os.path.join(REPO, "tools", "traffic_gen.py")


@pytest.fixture(autouse=True)
def _clean_obs():
    set_metrics(MetricsRegistry())
    yield
    set_metrics(MetricsRegistry())


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _counter(name):
    return get_metrics().counter(name).value


# ---- lease protocol (fake clocks, no jax) --------------------------

def _stores(tmp_path, clock, *workers, ttl_s=5.0):
    d = os.path.join(str(tmp_path), "leases")
    wall = lambda: 1000.0 + clock.t   # noqa: E731
    return [LeaseStore(d, w, ttl_s=ttl_s, clock=clock, wall=wall)
            for w in workers]


def test_lease_acquire_exactly_one_winner(tmp_path):
    c = _Clock()
    w0, w1 = _stores(tmp_path, c, "w0", "w1")
    assert w0.acquire("j") is True
    assert w1.acquire("j") is False        # the link already exists
    doc = w1.read("j")
    assert doc["worker"] == "w0" and doc["generation"] == 1
    assert w0.owns("j") and not w1.owns("j")
    assert _counter("route.fleet.leases_acquired") == 1


def test_lease_renew_rotates_prev_generation(tmp_path):
    c = _Clock()
    (w0,) = _stores(tmp_path, c, "w0")
    w0.acquire("j")
    assert w0.renew("j") and w0.renew("j")
    assert w0.read("j")["renewals"] == 2
    prev = w0.path("j") + ".prev"
    assert os.path.exists(prev)
    # a torn current record falls back to the .prev generation
    with open(w0.path("j"), "wb") as f:
        f.write(b"\x00torn")
    assert w0.read("j")["renewals"] == 1
    assert _counter("route.fleet.lease_renewals") == 2


def test_lease_expiry_on_monotonic_clock_only(tmp_path):
    c = _Clock()
    (w0,) = _stores(tmp_path, c, "w0", ttl_s=5.0)
    w0.acquire("j")
    assert not w0.expired(w0.read("j"))
    c.t += 5.1
    assert w0.expired(w0.read("j"))
    # a released record NEVER expires, however old
    w0.release("j", state="done")
    c.t += 100.0
    assert not w0.expired(w0.read("j"))


def test_lease_steal_requires_expiry_one_winner_forensics(tmp_path):
    c = _Clock()
    w0, w1, w2 = _stores(tmp_path, c, "w0", "w1", "w2")
    w0.acquire("j")
    assert w1.steal("j") is False          # still live: no theft
    c.t += 5.1
    assert w1.steal("j") is True
    assert w2.steal("j") is False          # now w1's, live again
    doc = w2.read("j")
    assert doc["worker"] == "w1" and doc["generation"] == 2
    assert doc["stolen_from"] == "w0"
    # the loser's record stays behind for the post-mortem
    assert os.path.exists(w1.path("j") + ".steal.w1")
    assert _counter("route.fleet.leases_expired") == 1
    assert _counter("route.fleet.lease_steals") == 1


def test_lease_release_is_terminal(tmp_path):
    c = _Clock()
    w0, w1 = _stores(tmp_path, c, "w0", "w1")
    w0.acquire("j")
    assert w0.release("j", state="done")
    assert w1.acquire("j") is False        # the record is kept forever
    c.t += 100.0
    assert w1.steal("j") is False          # released never expires
    assert not w0.owns("j")
    assert w0.summary()["released"] == ["j"]


def test_lease_fencing_renew_refused_after_steal(tmp_path):
    c = _Clock()
    w0, w1 = _stores(tmp_path, c, "w0", "w1")
    w0.acquire("j")
    c.t += 5.1
    assert w1.steal("j")
    assert w0.renew("j") is False          # fenced: the job moved on
    assert not w0.owns("j")
    assert _counter("route.fleet.leases_lost") == 1


def test_lease_force_expire_enables_self_steal(tmp_path):
    c = _Clock()
    (w0,) = _stores(tmp_path, c, "w0")
    w0.acquire("j")
    assert w0.force_expire("j")
    c.t += 0.001                           # any instant later: expired
    assert w0.steal("j")                   # the owner wins itself back
    doc = w0.read("j")
    assert doc["worker"] == "w0" and doc["generation"] == 2


def test_heartbeat_age_prefers_monotonic_clock(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path, interval_s=1.0, clock=lambda: 100.0,
                   wall=lambda: 5000.0)
    assert hb.beat(queue_depth=3)
    # the reader's wall clock stepped 1000s (NTP); monotonic says 5s —
    # the wall jump can neither fake a dead worker nor mask one
    doc = Heartbeat.read(path, wall=lambda: 6000.0, mono=lambda: 105.0)
    assert doc["age_src"] == "mono"
    assert doc["age_s"] == pytest.approx(5.0)
    assert doc["queue_depth"] == 3
    # a negative monotonic age (reader booted after the writer's
    # stamp) falls back to the wall difference, flagged
    doc = Heartbeat.read(path, wall=lambda: 5002.0, mono=lambda: 7.0)
    assert doc["age_src"] == "wall"
    assert doc["age_s"] == pytest.approx(2.0)


# ---- transport (in-thread server, ephemeral port) ------------------

def _serve(tmp_path, plan=None):
    return InboxHTTPServer(str(tmp_path), port=0, plan=plan).start()


def test_transport_roundtrip_durable_layout(tmp_path):
    srv = _serve(tmp_path)
    try:
        cl = TransportClient(srv.url, max_attempts=2)
        jid = cl.submit({"luts": 4, "seed": 1, "name": "a"},
                        tenant="t0", priority=2, job_id="job-1")
        assert jid == "job-1"
        subs = InboxReader(os.path.join(str(tmp_path),
                                        SUBMIT_NAME)).poll()
        assert [s["job_id"] for s in subs] == ["job-1"]
        assert subs[0]["tenant"] == "t0" and subs[0]["priority"] == 2
        spec = json.load(open(os.path.join(str(tmp_path),
                                           subs[0]["spec"])))
        assert spec["seed"] == 1
        assert cl.healthz()["ok"] is True
        s = srv.summary()
        assert s["requests"] == 1 and s["drops"] == 0
        assert s["max_attempt_seen"] == 1 and s["retry_cap_seen"] == 2
    finally:
        srv.stop()


def test_transport_torn_request_writes_nothing(tmp_path):
    srv = _serve(tmp_path)
    try:
        for body in (b'{"spec": {"luts"', b'{"tenant": "t0"}'):
            req = urlrequest.Request(
                srv.url + "/submit", data=body, method="POST")
            with pytest.raises(urlerror.HTTPError) as e:
                urlrequest.urlopen(req, timeout=5)
            assert e.value.code == 400
        # nothing durable: no submit line, no spec file
        assert not os.path.exists(
            os.path.join(str(tmp_path), SUBMIT_NAME))
        assert not os.listdir(os.path.join(str(tmp_path), "specs")) \
            if os.path.isdir(os.path.join(str(tmp_path), "specs")) \
            else True
    finally:
        srv.stop()


def test_transport_drop_then_idempotent_retry(tmp_path):
    # horizon 1: invocation 0 (the first request) always drops
    plan = FaultPlan.parse(7, "transport.drop:1:1")
    srv = _serve(tmp_path, plan=plan)
    sleeps = []
    try:
        cl = TransportClient(srv.url, max_attempts=3, backoff_s=0.01,
                             sleep=sleeps.append)
        jid = cl.submit({"luts": 4, "seed": 2, "name": "b"},
                        job_id="job-2")
        assert jid == "job-2" and cl.retries == 1
        assert sleeps == [pytest.approx(0.01)]
        s = srv.summary()
        assert s["drops"] == 1 and s["retries"] == 1
        assert s["max_attempt_seen"] == 2 and s["retry_cap_seen"] == 3
        # the drop fired BEFORE any durable write: exactly one line,
        # one spec — the retry is a dedupe-able resubmission, not a
        # second job
        subs = InboxReader(os.path.join(str(tmp_path),
                                        SUBMIT_NAME)).poll()
        assert [s_["job_id"] for s_ in subs] == ["job-2"]
        assert _counter("route.fleet.transport_drops") == 1
        assert _counter("route.fleet.transport_retries") == 1
    finally:
        srv.stop()


def test_transport_exhaustion_bounded_backoff(tmp_path):
    plan = FaultPlan.parse(7, "transport.drop:4:4")   # drop everything
    srv = _serve(tmp_path, plan=plan)
    sleeps = []
    try:
        cl = TransportClient(srv.url, max_attempts=3, backoff_s=0.05,
                             backoff_mult=4.0, backoff_max_s=0.1,
                             sleep=sleeps.append)
        with pytest.raises(TransportError):
            cl.submit({"luts": 4, "seed": 3}, job_id="job-3")
        # capped exponential: 0.05, then 0.2 clipped to the 0.1 cap
        assert sleeps == [pytest.approx(0.05), pytest.approx(0.1)]
        assert cl.retries == 2 and srv.summary()["drops"] == 3
        assert not os.path.exists(
            os.path.join(str(tmp_path), SUBMIT_NAME))
    finally:
        srv.stop()


def test_transport_job_id_sanitized_consistently(tmp_path):
    # client and server sanitize identically, so the idempotency-key
    # echo check cannot false-positive on funny ids
    srv = _serve(tmp_path)
    try:
        cl = TransportClient(srv.url, max_attempts=1)
        jid = cl.submit({"luts": 4, "seed": 4}, job_id="we ird/id")
        assert jid == "we_ird_id"
    finally:
        srv.stop()


# ---- fleet partitioning + failover (fake services, shared clock) ---

def test_preferred_worker_stable_partition():
    roster = ["w1", "w0"]                  # order must not matter
    for jid in ("a", "b", "tg-1-000", "fj17"):
        assert preferred_worker(jid, roster) \
            == preferred_worker(jid, list(reversed(roster)))
    owners = {preferred_worker(f"j{i}", roster) for i in range(64)}
    assert owners == {"w0", "w1"}          # both sides get work


def test_split_chaos_partitions_supervisor_sites():
    sup, wrk = split_chaos(
        "worker.kill:1,lease.steal:2,transport.drop:3:9")
    assert sup == "worker.kill:1,transport.drop:3:9"
    assert wrk == "lease.steal:2"
    assert set(SUPERVISOR_SITES) == {"worker.kill", "transport.drop"}
    assert split_chaos("") == ("", "")


def test_heartbeat_name_solo_vs_fleet():
    assert heartbeat_name() == "heartbeat.json"
    assert heartbeat_name("w3") == "heartbeat.w3.json"


def test_count_tpu_chips_counts_only_tpu_vfio_groups(tmp_path,
                                                     monkeypatch):
    """The layout of a one-chip v5e machine (four TPU functions listed,
    one VFIO node handed over) plus what must not count: a chip without
    a node, the vendor's own NIC and another vendor's device behind
    VFIO.  No sysfs, no chips."""
    pci, vfio = tmp_path / "pci", tmp_path / "vfio"
    vfio.mkdir()
    for name, vendor, klass, group, node in (
            ("0000:00:08.0", "0x1ae0", "0xff0000", "1", True),   # TPU
            ("0000:00:09.0", "0x1ae0", "0xff0000", "0", False),  # no node
            ("0000:00:0a.0", "0x1ae0", "0x120000", "2", True),   # TPU
            ("0000:00:06.0", "0x1ae0", "0x020000", "5", True),   # NIC
            ("0000:00:07.0", "0x10de", "0x030000", "6", True)):  # GPU
        d = pci / name
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "class").write_text(klass + "\n")
        os.symlink(f"../../../kernel/iommu_groups/{group}",
                   d / "iommu_group")
        if node:
            (vfio / group).write_text("")
    (vfio / "vfio").write_text("")
    monkeypatch.setattr(fleet_mod, "PCI_DEVICES", str(pci))
    monkeypatch.setattr(fleet_mod, "VFIO_DIR", str(vfio))
    assert fleet_mod.count_tpu_chips() == 2
    monkeypatch.setattr(fleet_mod, "PCI_DEVICES", str(tmp_path / "none"))
    assert fleet_mod.count_tpu_chips() == 0


def test_fleet_pins_one_worker_per_chip(tmp_path, monkeypatch):
    """No TPU: nothing injected.  On a TPU host every worker gets a
    chip of its own, and more workers than chips are refused before
    anything is spawned."""
    assert fleet_mod.chip_env(0, 0) == {}
    envs = [fleet_mod.chip_env(i, 4) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(set(e) == set(envs[0]) for e in envs)
    monkeypatch.setattr(fleet_mod, "count_tpu_chips", lambda: 1)
    sup = fleet_mod.FleetSupervisor(
        str(tmp_path / "box"), fleet_mod.FleetOpts(n_workers=2))
    with pytest.raises(ValueError, match="one worker per chip"):
        sup.start()
    assert sup.procs == {}


class _FakeFlow:
    def __init__(self, nets):
        self.term = types.SimpleNamespace(source=list(range(nets)))


class _FakeService:
    """RouteService's daemon-facing surface: real JobQueue, fake
    runner, no jax."""

    def __init__(self, clock, runner=None):
        self.queue = JobQueue(clock=clock, sleep=lambda s: None)
        self.draining = False
        self.runs_dir = None
        self.scenario = "fleet-fake"
        self.router = types.SimpleNamespace(_library=None)
        self.resil = None
        self.diag_extra = None
        self.runner = runner or (
            lambda job: ("done", {"wirelength": 7, "iterations": 2,
                                  "nets": len(job.payload.term.source)}))

    def begin_drain(self):
        self.draining = True

    def admit(self, spec, tenant="default", priority=0,
              deadline_s=None, max_retries=0, job_id=""):
        if self.draining:
            raise RuntimeError("service is draining")
        job = RouteJob(tenant=tenant, payload=spec, job_id=job_id,
                       priority=priority, deadline_s=deadline_s,
                       max_retries=max_retries)
        return self.queue.admit(job)

    def _runner(self, job):
        return self.runner(job)


ROSTER = ("w0", "w1")


def _mk_worker(tmp_path, worker, clock, runner=None, **opts_kw):
    opts_kw.setdefault("lease_ttl_s", 5.0)
    opts_kw.setdefault("foreign_grace_s", 3.0)
    svc = _FakeService(clock, runner=runner)
    d = RouteDaemon(
        svc, str(tmp_path / "box"),
        DaemonOpts(default_nets_per_s=10.0, cold_start_factor=1.0,
                   worker=worker, workers=ROSTER, **opts_kw),
        flow_builder=lambda spec: _FakeFlow(int(spec.get("nets", 10))),
        clock=clock, wall=lambda: 1000.0 + clock.t,
        sleep=lambda s: setattr(clock, "t", clock.t + s))
    return d, svc


def _ids_for(worker, n=1, roster=ROSTER):
    out, i = [], 0
    while len(out) < n:
        jid = f"fj{i}"
        if preferred_worker(jid, list(roster)) == worker:
            out.append(jid)
        i += 1
    return out[0] if n == 1 else out


def _submit_fake(tmp_path, job_id, nets=10):
    return submit_job(str(tmp_path / "box"),
                      {"nets": nets, "name": job_id}, job_id=job_id)


def test_fleet_partition_runs_each_job_exactly_once(tmp_path):
    clock = _Clock()
    d0, s0 = _mk_worker(tmp_path, "w0", clock)
    d1, s1 = _mk_worker(tmp_path, "w1", clock)
    j0, j1 = _ids_for("w0"), _ids_for("w1")
    _submit_fake(tmp_path, j0)
    _submit_fake(tmp_path, j1)
    for _ in range(2):
        d0.cycle()
        d1.cycle()
    assert [j.job_id for j in s0.queue.jobs
            if j.state is JobState.DONE] == [j0]
    assert [j.job_id for j in s1.queue.jobs
            if j.state is JobState.DONE] == [j1]
    # every lease terminal, nothing parked as takeover backup anymore
    leases = d0.lease.scan()
    assert sorted(leases) == sorted([j0, j1])
    assert all(doc["released"] for doc in leases.values())
    # summaries carry the fleet section with worker attribution
    doc = d0.summary()
    assert doc["fleet"]["worker"] == "w0"
    assert doc["fleet"]["roster"] == ["w0", "w1"]
    assert all(r["worker"] == "w0" for r in doc["jobs"])
    assert d0.service.diag_extra()["worker"] == "w0"


def test_fleet_failover_steals_expired_lease_and_fences_owner(tmp_path):
    clock = _Clock()
    # w0 never finishes its slice (always preempted): the in-flight
    # job holds a lease that goes stale the moment w0 stops cycling
    d0, s0 = _mk_worker(tmp_path, "w0", clock,
                        runner=lambda job: ("preempted", None))
    d1, s1 = _mk_worker(tmp_path, "w1", clock)
    j0 = _ids_for("w0")
    _submit_fake(tmp_path, j0)
    d0.cycle()                             # w0 admits + leases j0
    d1.cycle()                             # w1 parks it as foreign
    assert j0 in d1._foreign
    assert s1.queue.get(j0) is None
    # w0 "dies" (no more cycles); its lease expires on the shared clock
    clock.t += 6.0
    d1.cycle()
    assert d1.failed_over_ids == [j0]
    done = s1.queue.get(j0)
    assert done is not None and done.state is JobState.DONE
    assert _counter("route.fleet.jobs_failed_over") == 1
    assert _counter("route.fleet.leases_expired") == 1
    assert _counter("route.fleet.lease_steals") == 1
    row = [r for r in d1.summary()["jobs"] if r["job_id"] == j0][0]
    assert row["failed_over"] is True and row["worker"] == "w1"
    # the zombie owner is FENCED at its next sweep: local copy evicted
    # with the lease_stolen cause, never re-run
    assert d0._lease_sweep() == 1
    zombie = s0.queue.get(j0)
    assert zombie.state is JobState.SHED
    assert d0.shed_causes[j0]["code"] == "lease_stolen"
    # ...and the doctor accepts the fencing eviction without recorded
    # overload (it is a correctness eviction, not load shedding)
    errs, _ = _doctor().check_daemon(d0.summary())
    assert errs == []


def test_fleet_foreign_grace_takeover_of_unleased_job(tmp_path):
    clock = _Clock()
    d1, s1 = _mk_worker(tmp_path, "w1", clock, foreign_grace_s=3.0)
    j0 = _ids_for("w0")                    # assigned to a worker that
    _submit_fake(tmp_path, j0)             # never comes up
    d1.cycle()
    assert j0 in d1._foreign and s1.queue.get(j0) is None
    clock.t += 3.1                         # grace elapses, still unleased
    d1.cycle()
    job = s1.queue.get(j0)
    assert job is not None and job.state is JobState.DONE
    assert d1.lease.read(j0)["released"]


@pytest.mark.parametrize("vouch_s,alive", [(30.0, True), (0.2, False)])
def test_worker_stays_live_through_a_long_slice(tmp_path, vouch_s, alive):
    """One slice that outlasts the lease and many heartbeat intervals
    (a cold compile on a chip): the slice helper thread keeps beating
    and renewing, so peers and the doctor see a live owner — but only
    for the dispatch watchdog budget; a slice stuck past it goes
    silent and its lease lapses, as for a dead process."""
    box = str(tmp_path / "box")
    seen = {}

    def runner(job):
        time.sleep(2.0)
        peer = LeaseStore(os.path.join(box, LEASE_DIR), "w1", ttl_s=1.0)
        doc = peer.read(job.job_id)
        seen.update(expired=peer.expired(doc), renewals=doc["renewals"],
                    hb_age=Heartbeat.read(os.path.join(
                        box, heartbeat_name("w0")))["age_s"])
        return ("done", {"wirelength": 7, "iterations": 2, "nets": 10})

    svc = _FakeService(time.monotonic, runner=runner)
    d = RouteDaemon(
        svc, box,
        DaemonOpts(default_nets_per_s=10.0, cold_start_factor=1.0,
                   worker="w0", workers=ROSTER, heartbeat_s=0.1,
                   lease_ttl_s=1.0),
        flow_builder=lambda spec: _FakeFlow(10))
    d._vouch_s = vouch_s
    j0 = _ids_for("w0")
    _submit_fake(tmp_path, j0)
    d.cycle()
    # either way the owner finishes and releases: nobody stole
    assert svc.queue.get(j0).state is JobState.DONE
    assert d.lease.read(j0)["released"]
    errs, _ = _doctor().check_daemon(d.summary())
    if alive:
        assert not seen["expired"] and seen["renewals"] >= 3
        assert seen["hb_age"] < 1.0
        assert d.heartbeat.max_gap_s < 1.0
        assert errs == []
    else:
        assert seen["expired"]
        assert seen["hb_age"] > 1.0
        assert any("heartbeat gap" in e for e in errs)


class _TickClock(_Clock):
    """Every read advances a hair, like a real monotonic clock — a
    chaos-forced expiry is observable before the next renewal."""

    def __call__(self):
        self.t += 1e-4
        return self.t


def test_fleet_chaos_lease_steal_self_steal_continues(tmp_path):
    clock = _TickClock()
    d0, s0 = _mk_worker(tmp_path, "w0", clock)
    s0.resil = types.SimpleNamespace(
        plan=FaultPlan.parse(3, "lease.steal:1:1"))
    j0 = _ids_for("w0")
    _submit_fake(tmp_path, j0)
    d0.cycle()
    # the chaos force-expired the held lease under its owner; with no
    # peer contesting, the sweep's self-steal won it back (generation
    # bump + forensic record) and the job still finished exactly once
    assert s0.resil.plan.fired_sites() == ["lease.steal"]
    job = s0.queue.get(j0)
    assert job is not None and job.state is JobState.DONE
    doc = d0.lease.read(j0)
    assert doc["released"] and doc["generation"] == 2
    assert _counter("route.fleet.lease_steals") == 1
    assert _counter("route.fleet.jobs_failed_over") == 0


# ---- traffic generator ---------------------------------------------

def _load_tool(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _doctor():
    return _load_tool(FLOW_DOCTOR, "flow_doctor")


def test_traffic_gen_stream_is_seed_deterministic():
    tg = _load_tool(TRAFFIC_GEN, "traffic_gen")
    argv = ["--inbox", "x", "--jobs", "5", "--tenants", "3",
            "--seed", "9"]
    a = tg.build_parser().parse_args(argv)
    s1, s2 = tg.make_stream(a), tg.make_stream(a)
    assert s1 == s2                        # replayable byte for byte
    assert [j["job_id"] for j in s1] \
        == [f"tg-9-{i:03d}" for i in range(5)]
    assert {j["tenant"] for j in s1} <= {"t0", "t1", "t2"}
    b = tg.build_parser().parse_args(argv[:-1] + ["10"])
    assert [j["spec"]["seed"] for j in tg.make_stream(b)] \
        != [j["spec"]["seed"] for j in s1]


def test_traffic_gen_inbox_delivery(tmp_path, capsys):
    tg = _load_tool(TRAFFIC_GEN, "traffic_gen")
    box = str(tmp_path / "box")
    assert tg.main(["--inbox", box, "--jobs", "3", "--tenants", "2",
                    "--seed", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert len(out["submitted"]) == 3
    assert sum(out["per_tenant"].values()) == 3
    subs = InboxReader(os.path.join(box, SUBMIT_NAME)).poll()
    assert [s["job_id"] for s in subs] == out["submitted"]


def test_traffic_gen_transport_delivery_survives_drop(tmp_path, capsys):
    tg = _load_tool(TRAFFIC_GEN, "traffic_gen")
    plan = FaultPlan.parse(7, "transport.drop:1:1")
    srv = _serve(tmp_path, plan=plan)
    try:
        assert tg.main(["--url", srv.url, "--jobs", "2", "--seed",
                        "3", "--retries", "3"]) == 0
    finally:
        srv.stop()
    out = json.loads(capsys.readouterr().out)
    assert len(out["submitted"]) == 2
    assert out["transport_retries"] >= 1   # the drop cost a retry only
    subs = InboxReader(os.path.join(str(tmp_path), SUBMIT_NAME)).poll()
    assert [s["job_id"] for s in subs] == out["submitted"]


# ---- flow_doctor --fleet-summary rule set --------------------------

def _fsummary(jobs=None, fleet=None):
    doc = {
        "jobs": [{"job_id": "a", "state": "done", "worker": "w1"},
                 {"job_id": "b", "state": "done", "worker": "w0"}]
        if jobs is None else jobs,
        "fleet": {
            "roster": ["w0", "w1"], "killed": ["w0"],
            "timed_out": False,
            "leases": {"a": {"worker": "w1", "released": True},
                       "b": {"worker": "w0", "released": True}},
            "transport": {"requests": 3, "drops": 1, "retries": 1,
                          "max_attempt_seen": 2, "retry_cap_seen": 4},
            "metrics": {"route.fleet.jobs_failed_over": 1,
                        "route.fleet.leases_expired": 1,
                        "route.fleet.lease_steals": 1},
            "aggregate": {"nets": 20, "wall_s": 2.0,
                          "nets_per_s": 10.0},
        },
    }
    doc["fleet"].update(fleet or {})
    return doc


def test_doctor_fleet_healthy():
    errs, notes = _doctor().check_fleet(_fsummary())
    assert errs == []
    assert any("failed_over=1" in n for n in notes)


def test_doctor_fleet_failover_requires_lease_expiry():
    errs, _ = _doctor().check_fleet(_fsummary(fleet={
        "metrics": {"route.fleet.jobs_failed_over": 1}}))
    assert any("no lease ever expired" in e for e in errs)


def test_doctor_fleet_transport_retry_bounds():
    d = _doctor()
    errs, _ = d.check_fleet(_fsummary(fleet={
        "transport": {"requests": 9, "drops": 1, "retries": 1,
                      "max_attempt_seen": 9, "retry_cap_seen": 4}}))
    assert any("above the client's declared cap" in e for e in errs)
    errs, _ = d.check_fleet(_fsummary(fleet={
        "transport": {"requests": 12, "drops": 1, "retries": 9,
                      "max_attempt_seen": 2, "retry_cap_seen": 4}}))
    assert any("retry storm" in e for e in errs)
    errs, _ = d.check_fleet(_fsummary(fleet={
        "transport": {"requests": 2, "drops": 2, "retries": 0,
                      "max_attempt_seen": 1, "retry_cap_seen": 4}}))
    assert any("silently lost" in e for e in errs)


def test_doctor_fleet_orphaned_leases_and_double_done():
    d = _doctor()
    errs, _ = d.check_fleet(_fsummary(fleet={
        "leases": {"a": {"worker": "w1", "released": True},
                   "b": {"worker": "w0", "released": False}}}))
    assert any("unreleased lease" in e for e in errs)
    errs, _ = d.check_fleet(_fsummary(jobs=[
        {"job_id": "a", "state": "done", "worker": "w0"},
        {"job_id": "a", "state": "done", "worker": "w1"},
        {"job_id": "b", "state": "done", "worker": "w0"}]))
    assert any("finished 2 times" in e for e in errs)
    errs, _ = d.check_fleet(_fsummary(jobs=[
        {"job_id": "a", "state": "done"}]))
    assert any("no worker attribution" in e for e in errs)


def test_doctor_fleet_timeout_and_shape():
    d = _doctor()
    errs, _ = d.check_fleet(_fsummary(fleet={"timed_out": True}))
    assert any("timed out" in e for e in errs)
    errs, _ = d.check_fleet({"jobs": []})
    assert any("no fleet section" in e for e in errs)


def test_doctor_cli_fleet_summary_flag(tmp_path):
    p = str(tmp_path / "fleet.json")
    with open(p, "w") as f:
        json.dump(_fsummary(), f)
    r = subprocess.run([sys.executable, FLOW_DOCTOR,
                        "--fleet-summary", p],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "HEALTHY" in r.stdout
    with open(p, "w") as f:
        json.dump(_fsummary(fleet={"timed_out": True}), f)
    r = subprocess.run([sys.executable, FLOW_DOCTOR,
                        "--fleet-summary", p],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "UNHEALTHY" in r.stderr


# ---- kill-one-worker failover parity (real jax, real processes) ----

_LUTS = 6
_MAX_ITERS = 12


def _cli(args, **kw):
    return [sys.executable, os.path.join(REPO, "tools",
                                         "route_daemon.py"), *args]


def _submit_real(box, seed, job_id):
    subprocess.run(
        _cli(["submit", "--inbox", box, "--luts", str(_LUTS),
              "--seed", str(seed), "--max_iterations",
              str(_MAX_ITERS), "--job_id", job_id]),
        check=True, capture_output=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _wirelengths(summary_path):
    doc = json.load(open(summary_path))
    return ({j["job_id"]: (j["state"], j.get("wirelength"))
             for j in doc["jobs"]}, doc)


def test_fleet_worker_sigkill_failover_wirelength_parity(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # both jobs deterministically assigned to w0 — the victim — so the
    # kill is guaranteed to orphan in-flight leased work
    ids = _ids_for("w0", n=2)
    # reference: an uninterrupted SOLO daemon over the same jobs
    ref_box = str(tmp_path / "ref")
    os.makedirs(ref_box)
    for seed, jid in zip((3, 4), ids):
        _submit_real(ref_box, seed, jid)
    subprocess.run(
        _cli(["run", "--inbox", ref_box, "--luts", str(_LUTS),
              "--slice", "2", "--heartbeat_s", "2.0",
              "--exit_when_idle", "2",
              "--summary", os.path.join(ref_box, "summary.json")]),
        check=True, env=env, capture_output=True, timeout=420)
    ref, _ = _wirelengths(os.path.join(ref_box, "summary.json"))
    assert all(state == "done" for state, _ in ref.values())

    # fleet: two real workers on one inbox, SIGKILL w0 mid-slice
    box = str(tmp_path / "box")
    os.makedirs(box)
    for seed, jid in zip((3, 4), ids):
        _submit_real(box, seed, jid)
    procs = {}
    for w in ROSTER:
        procs[w] = subprocess.Popen(
            _cli(["run", "--inbox", box, "--luts", str(_LUTS),
                  # a compile-heavy first slice blocks several seconds:
                  # the beat interval must absorb it (doctor's 10x gap
                  # rule) and the lease TTL must outlive it, or a LIVE
                  # worker gets stolen from mid-compile
                  "--slice", "2", "--heartbeat_s", "2.0",
                  "--poll_s", "0.1", "--worker", w,
                  "--workers", ",".join(ROSTER),
                  "--lease_ttl_s", "6.0", "--foreign_grace_s", "1.0",
                  "--summary", os.path.join(box, f"summary.{w}.json")]),
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
    leases = LeaseStore(os.path.join(box, LEASE_DIR), "observer")
    ckpt = os.path.join(box, "ckpt")
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if (os.path.isdir(ckpt)
                    and any(n.endswith(".ck")
                            for n in os.listdir(ckpt))):
                break
            if procs["w0"].poll() is not None:
                pytest.fail("victim exited before any durable "
                            "checkpoint was written")
            time.sleep(0.2)
        else:
            pytest.fail("no durable checkpoint appeared in time")
        os.kill(procs["w0"].pid, signal.SIGKILL)
        procs["w0"].wait(timeout=30)
        # the survivor must steal the expired leases and finish BOTH
        # jobs from the shared durable checkpoints
        while time.time() < deadline:
            docs = leases.scan()
            if len(docs) == len(ids) \
                    and all(d.get("released") for d in docs.values()):
                break
            if procs["w1"].poll() is not None:
                pytest.fail("survivor exited before finishing the "
                            "victim's jobs")
            time.sleep(0.2)
        else:
            pytest.fail("failover never completed: leases "
                        f"{leases.scan()}")
        # drain the survivor out and collect its summary
        drain = os.path.join(box, "DRAIN")
        with open(drain + ".tmp", "w") as f:
            f.write("test drain\n")
        os.replace(drain + ".tmp", drain)
        procs["w1"].wait(timeout=60)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()

    got, doc = _wirelengths(os.path.join(box, "summary.w1.json"))
    done = {j: wl for j, (state, wl) in got.items() if state == "done"}
    # the survivor finished the victim's work bit-identically
    for jid in ids:
        assert done.get(jid) == ref[jid][1], (
            f"failover changed QoR for {jid}: "
            f"{done.get(jid)} vs solo {ref[jid][1]}")
    fleet = doc["fleet"]
    assert fleet["worker"] == "w1"
    assert fleet["metrics"].get("route.fleet.jobs_failed_over", 0) >= 1
    assert fleet["metrics"].get("route.fleet.leases_expired", 0) >= 1
    # exactly-once: every job holds ONE released terminal lease
    docs = leases.scan()
    assert sorted(docs) == sorted(ids)
    assert all(d["released"] and d["worker"] == "w1"
               for d in docs.values())
    # and the daemon rule set signs off on the survivor's story
    r = subprocess.run([sys.executable, FLOW_DOCTOR,
                        "--daemon-summary",
                        os.path.join(box, "summary.w1.json")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
