"""Driver ``served_open_loop``: one route daemon under an open loop.

The daemon (``build_daemon`` at the configuration's sizes, the
product's own pacing and liveness settings, a fresh inbox and a fresh,
empty ``runs_dir``) cycles on the main thread, as ``daemon run`` does.
One client thread plays every user: it submits through ``submit_job``
and learns of a job's end the way a client can today, by reading the
daemon's ``telemetry.json`` and ``rejected.jsonl``.

Set-up serves the warm-up stream -- every spec of the mix's pool until
each has been served once, then the pool once more -- so the window
compiles nothing and the daemon's own capacity estimate rests on warm
jobs.  The window then offers the plan of
``generator.window_plan`` at its due times whether or not earlier jobs
have finished.  A job's latency runs from the instant it was DUE to the
instant the client saw its terminal record; jobs in flight when the
last one has been sent are waited for (``drain_s`` at most) and
counted.  A job rejected, shed, failed or unfinished counts in
``failed``.  How late the generator ran is reported.

Judged after the daemon has stopped, outside every clock:
``reference.py``'s legality and sink delays on EVERY finished job, one
done record per job counted in the daemon's job table and in the run
corpus it appends to, a solo ``Router.route`` of every distinct spec
among a seeded sample of jobs, the heartbeat's worst gap over the
window both as the daemon measured it and as the client saw the file
age, and the resilience ladder.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import statistics
import threading
import time
from dataclasses import replace


from benchmark import generator, harness, reference

TERMINAL = ("done", "failed", "timeout", "shed", "rejected")
# flow_doctor --daemon-summary's rule: no gap over this many intervals
HEARTBEAT_GAP_INTERVALS = 10.0


class Client:
    """The submitting and watching side, on one thread."""

    def __init__(self, inbox: str, poll_s: float, tracing):
        from parallel_eda_tpu.serve.daemon import (REJECT_NAME,
                                                   heartbeat_name,
                                                   telemetry_name)

        self.inbox = inbox
        self.poll_s = poll_s
        self.tracing = tracing
        self._telemetry = os.path.join(inbox, telemetry_name())
        self._rejects = os.path.join(inbox, REJECT_NAME)
        self._heartbeat = os.path.join(inbox, heartbeat_name())
        self._beat = (0, 0.0)       # (mtime_ns, when the client saw it)
        self.max_beat_gap_s = 0.0   # since watch_heartbeat()
        self._seen = (0, 0)
        self._rejects_size = 0
        self.abort = threading.Event()  # set when the daemon has died
        self.state = {}             # job_id -> terminal state
        self.seen_at = {}           # job_id -> perf_counter

    def submit(self, job: dict) -> None:
        from parallel_eda_tpu.serve.daemon import submit_job

        with self.tracing.span("bench.submit"):
            submit_job(self.inbox, job["spec"], tenant=job["tenant"],
                       priority=job["priority"], job_id=job["job_id"])

    def watch_heartbeat(self) -> None:
        """Start the heartbeat watch anew (at the window's start)."""
        self._beat = (self._beat[0], time.perf_counter())
        self.max_beat_gap_s = 0.0

    def poll(self) -> None:
        """Read what the daemon has published since the last look."""
        now = time.perf_counter()
        try:
            beat_ns = os.stat(self._heartbeat).st_mtime_ns
        except OSError:
            beat_ns = self._beat[0]
        if beat_ns != self._beat[0]:
            self._beat = (beat_ns, now)
        self.max_beat_gap_s = max(self.max_beat_gap_s,
                                  now - self._beat[1])
        try:
            st = os.stat(self._telemetry)
            stamp = (st.st_mtime_ns, st.st_size)
        except OSError:
            stamp = self._seen
        if stamp != self._seen:
            self._seen = stamp
            with self.tracing.span("bench.client_poll"):
                try:
                    with open(self._telemetry) as fh:
                        jobs = json.load(fh)["jobs"]
                except (OSError, ValueError):
                    jobs = {}
                for job_id, state in jobs.items():
                    if state in TERMINAL and job_id not in self.state:
                        self.state[job_id] = state
                        self.seen_at[job_id] = now
        try:
            size = os.stat(self._rejects).st_size
        except OSError:
            size = 0
        if size != self._rejects_size:
            self._rejects_size = size
            with open(self._rejects) as fh:
                for line in fh:
                    try:
                        job_id = json.loads(line)["job_id"]
                    except (ValueError, KeyError):
                        continue
                    if job_id not in self.state:
                        self.state[job_id] = "rejected"
                        self.seen_at[job_id] = now

    def _nap(self, seconds: float) -> None:
        if self.abort.wait(max(0.0, seconds)):
            raise RuntimeError("the daemon stopped under the client")

    def wait_all(self, job_ids, timeout_s: float) -> bool:
        t_end = time.perf_counter() + timeout_s
        while time.perf_counter() < t_end:
            self.poll()
            if all(j in self.state for j in job_ids):
                return True
            self._nap(self.poll_s)
        return False

    def offer(self, plan, t0: float) -> dict:
        """Open loop: send each job at ``t0 + due_s``; returns
        {job_id: (due, sent)} on the perf_counter clock."""
        sent = {}
        for job in plan:
            due = t0 + job["due_s"]
            while True:
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                self.poll()
                self._nap(min(self.poll_s, wait))
            self.submit(job)
            sent[job["job_id"]] = (due, time.perf_counter())
        return sent


def _judge_job(g, job) -> dict:
    term, res = job.payload.term, job.result["result"]
    out = reference.judge(g, term.source, term.sinks, term.num_sinks,
                          res.paths, res.sink_delay)
    out["wl_diff"] = abs(out["wirelength"] - int(res.wirelength))
    return out


def run(cell: harness.Cell, env: harness.Env) -> harness.Outcome:
    from parallel_eda_tpu.route.router import Router, RouterOpts
    from parallel_eda_tpu.serve.daemon import build_daemon

    cfg, traffic, tr = cell.config, cell.traffic, env.tracing
    limits = traffic["limits"]
    reg = harness.fresh_metrics()
    inbox = os.path.join(env.work_dir, "inbox")
    os.makedirs(inbox)
    daemon = build_daemon(
        inbox, luts=int(cfg["luts"]), chan_width=int(cfg["chan_width"]),
        slice_iters=int(cfg["slice_iters"]),
        batch_size=int(cfg["batch_size"]),
        max_router_iterations=int(cfg["max_router_iterations"]),
        runs_dir=harness.fresh_dir(env.work_dir, "runs"))
    if env.router_overrides:
        daemon.service.base_opts = replace(daemon.service.base_opts,
                                           **env.router_overrides)
    if tr.on:
        cycle = daemon.cycle

        def traced_cycle():
            with tr.span("bench.daemon_cycle"):
                return cycle()
        daemon.cycle = traced_cycle

    warm = generator.warmup_plan(cfg, traffic)
    plan = generator.window_plan(cfg, traffic, env.seed, env.seconds)
    client = Client(inbox, float(traffic["poll_s"]), tr)
    box = {}

    def serve_all(jobs, what):
        """Submit ``jobs`` at once and wait; whatever the daemon shed or
        refused goes again until every one of them has been SERVED."""
        for tries in range(4):
            for job in jobs:
                client.submit(job)
            if not client.wait_all([j["job_id"] for j in jobs],
                                   float(traffic["warmup_timeout_s"])):
                raise RuntimeError(f"{what} stream did not finish")
            jobs = [dict(j, job_id=f"{j['job_id']}-again{tries}")
                    for j in jobs if client.state[j["job_id"]] != "done"]
            if not jobs:
                return
        raise RuntimeError(f"{what} stream: never served {jobs}")

    def play():
        try:
            # warm-up: every spec of the pool served once.  A cold
            # process compiles through its first jobs, the daemon prices
            # its capacity from them and may shed or refuse the last of
            # the stream (my chip runs, PR 23).  Then the pool once
            # more, warm, so that the daemon's own capacity estimate
            # rests on warm jobs whether or not this process compiled
            serve_all(warm, "warm-up")
            serve_all([dict(j, job_id="settle" + j["job_id"][4:])
                       for j in warm], "settling")
            box["compiles0"] = reg.counter(
                "route.dispatch.compiles").value
            t0 = time.perf_counter()
            box["setup_s"] = t0 - env.t_start
            box["t0"] = t0
            client.watch_heartbeat()
            # the daemon's own worst gap, over the window alone (its
            # whole-life maximum holds the cold compiles of set-up)
            daemon.heartbeat.max_gap_s = 0.0
            tr.begin_slice(float(traffic["trace_offset_s"]),
                           float(traffic["trace_seconds"]))
            box["sent"] = client.offer(plan, t0)
            box["drained"] = client.wait_all(
                [j["job_id"] for j in plan], float(traffic["drain_s"]))
            box["t_end"] = time.perf_counter()
            box["beat_gap_s"] = client.max_beat_gap_s
            box["own_beat_gap_s"] = daemon.heartbeat.max_gap_s
            box["compiles1"] = reg.counter(
                "route.dispatch.compiles").value
        except BaseException as e:      # re-raised on the main thread
            box["error"] = e
        finally:
            daemon.request_stop()

    thread = threading.Thread(target=play, name="bench-client")
    thread.start()
    try:
        daemon.run()
    finally:
        client.abort.set()
        thread.join()
    if "error" in box:
        raise box["error"]
    peak_bytes = harness.memory_peak_bytes()
    tr.finish()

    # ---- judged outside every clock
    summary = json.loads(json.dumps(daemon.summary(), default=str))
    by_id = {j.job_id: j for j in daemon.service.queue.jobs}
    planned = {j["job_id"]: j for j in plan}
    lat, failed = {}, []
    for job_id, (due, sent) in box["sent"].items():
        if client.state.get(job_id) == "done":
            lat[job_id] = client.seen_at[job_id] - due
        else:
            failed.append((job_id, client.state.get(job_id, "unfinished")))
    late = [sent - due for due, sent in box["sent"].values()]
    for job_id, state in failed[:10]:
        print(f"failed: {job_id} {state}", flush=True)

    # exactly once: a done job is ONE row of the daemon's job table, in
    # state done, and ONE record of the run corpus it appends to
    done_ids = [j for j in planned if client.state.get(j) == "done"]
    rows = collections.Counter(
        (r["job_id"], r["state"]) for r in summary["jobs"])
    n_rows = collections.Counter(r["job_id"] for r in summary["jobs"])
    records = collections.Counter()
    for path in glob.glob(os.path.join(env.work_dir, "runs", "*.jsonl")):
        with open(path) as fh:
            for line in fh:
                try:
                    records[json.loads(line).get("job_id")] += 1
                except ValueError:
                    records[None] += 1
    not_once = sum(1 for j in done_ids
                   if n_rows[j] != 1 or rows[(j, "done")] != 1
                   or records[j] != 1)

    g = reference.GraphArrays.of(daemon.service.rr)
    judged = [_judge_job(g, by_id[j]) for j in done_ids if j in by_id]
    for p in [p for j in judged for p in j["problems"]][:10]:
        print(f"illegal: {p}", flush=True)

    # served = solo: every distinct spec among the done jobs through a
    # plain Router with the daemon's RouterOpts (no slicing, no
    # resilience runtime), and every done job held to its spec's
    base = daemon.service.base_opts
    solo, solo_diff = {}, 0
    for job_id in done_ids:
        spec = planned[job_id]["spec"]
        if spec["name"] not in solo:
            fl = daemon.flow_builder(spec)
            res = Router(fl.rr, RouterOpts(
                batch_size=base.batch_size, sink_group=base.sink_group,
                max_router_iterations=base.max_router_iterations,
                plane_dtype=base.plane_dtype,
                dtype_guard=base.dtype_guard)).route(fl.term)
            solo[spec["name"]] = res
            harness.say(phase="solo", spec=spec["name"],
                        solo=int(res.wirelength),
                        solo_success=bool(res.success))
            solo_diff += int(not res.success)
        if job_id in by_id:
            solo_diff += abs(int(solo[spec["name"]].wirelength)
                             - int(by_id[job_id].result["wirelength"]))

    hb = summary["daemon"]["heartbeat"]
    resil = summary["resil"]["metrics"]
    moved = sum(resil.get(f"route.resil.{k}", 0) for k in
                ("degradation_steps", "retries", "watchdog_timeouts"))
    lats = sorted(lat.values())
    checks = [
        harness.exactly("jobs_failed", len(failed), 0),
        harness.exactly("drained", bool(box["drained"]), True),
        harness.exactly("done_not_exactly_once", not_once, 0),
        harness.exactly("jobs_not_legal", sum(
            1 for j in judged if j["problems"]), 0),
        harness.exactly("wirelength_recount_diff", max(
            [j["wl_diff"] for j in judged], default=0), 0),
        harness.at_most("sink_delay_gap", max(
            [j["delay_gap"] for j in judged], default=float("inf")),
            limits["sink_delay_gap"]),
        harness.exactly("served_vs_solo_wirelength_diff", solo_diff, 0),
        # over the window: the daemon's own worst gap between beats,
        # and the file's age as a client sees it
        harness.at_most("heartbeat_gap_intervals",
                        box["own_beat_gap_s"] / float(hb["interval_s"]),
                        HEARTBEAT_GAP_INTERVALS),
        harness.at_most("heartbeat_age_seen_intervals",
                        box["beat_gap_s"] / float(hb["interval_s"]),
                        HEARTBEAT_GAP_INTERVALS),
        harness.exactly("resilience_ladder_moved", moved, 0),
        harness.exactly("compiles_in_window",
                        box["compiles1"] - box["compiles0"], 0),
    ]
    service = {kind: [by_id[j].result["route_s"] for j in done_ids
                      if planned[j]["heavy"] == heavy]
               for kind, heavy in (("heavy", True), ("tiny", False))}
    harness.say(phase="setup", setup_s=box["setup_s"],
            warmup_jobs=len(client.state) - len(plan),
            dispatch_compiles=box["compiles0"])
    harness.say(phase="window", jobs=len(plan), done=len(lat),
            service_s_mean={k: (statistics.fmean(v) if v else None)
                            for k, v in service.items()},
            service_s_sum=sum(sum(v) for v in service.values()),
            failed=len(failed), judged=len(judged),
            window_and_drain_s=box["t_end"] - box["t0"],
            latency_samples=len(lats),
            latency_p50_p90_max=[lats[len(lats) // 2],
                                 lats[int(0.9 * len(lats))],
                                 lats[-1]] if lats else None,
            gen_late_max_s=max(late, default=0.0),
            queue_depth_at_end=int(summary["daemon"]["metrics"].get(
                "route.daemon.queue_depth", 0)),
            dispatch_compiles=box["compiles1"], heartbeat=hb)
    e2e = {"job_p50_s": statistics.median(lats)} if lats else {}
    return harness.Outcome(
        attempted=len(plan), failed=len(failed),
        setup_s=box["setup_s"], end_to_end=e2e, checks=checks,
        ctx={"latencies": lats, "gen_late": late,
             "slo": summary["slo"],
             "memory_peak_bytes": peak_bytes,
             "window_job_ids": set(planned),
             "registry": reg.values("route.")})
