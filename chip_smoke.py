"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py                # one chip: route, flow, daemon
    python chip_smoke.py --four-chips   # four chips: mesh_gspmd, mesh_row, fleet4

Drives the main path once through the entry points a user calls and
checks the results by the repo's own means (route/check.py legality,
the native serial router as the wirelength reference, run-to-run
determinism, flow_doctor over the daemon summary).  One JSON object per
phase on earlier lines; the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script fails at once when JAX reports anything but a TPU: it never
sets ``jax_platforms`` and never selects the CPU.  A failed phase raises
— nothing is caught — so the exit code is non-zero and no result line
is printed.  The phase functions take their sizes as arguments so
tests/test_chip_smoke.py rehearses them on the CPU at a tiny size; only
the ``main`` functions check for the chip, and no option relaxes that.

Default mode runs everything in ONE process (the chip belongs to one
process at a time).  ``--four-chips`` needs the parent OFF the backend —
the fleet's workers each own one chip — so there the parent never
initialises JAX and runs each mesh sub-phase as a child process.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
FLOW_DOCTOR = os.path.join(REPO, "tools", "flow_doctor.py")

# the repo's at-scale config (bench.py --scale; BENCHMARKS.md)
ROUTE_LUTS, ROUTE_W = 1200, 20
# README's first quick-start line
FLOW_LUTS, FLOW_W = 100, 24
# the daemon routes UNPLACED circuits, which stop being routable well
# before 600 LUTs (BENCHMARKS.md), hence the size
DAEMON_LUTS, DAEMON_W, DAEMON_SLICE = 60, 16, 3
# --four-chips: small on purpose — this proves placement of work across
# chips, not capacity
MESH_LUTS, MESH_W = 300, 16
FLEET_LUTS, FLEET_W, FLEET_JOBS = 60, 16, 8


def say(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def _keep_lines(name: str, text: str) -> None:
    """Append a child's phase lines to a file under the output dir: the
    four-chip lines are too long for the end of a call's output."""
    with open(os.path.join(OUT_DIR, name), "a") as fh:
        fh.write(text)


def require_tpu(count: int) -> dict:
    """The chip check: JAX's own first device must be a TPU and the
    host must hold exactly ``count`` of them."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX reports "
                         f"platform {d.platform!r}")
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: needs {count} chip(s), JAX "
                         f"reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _fresh_metrics():
    from parallel_eda_tpu.obs import MetricsRegistry, set_metrics

    m = set_metrics(MetricsRegistry())
    m.enabled = True
    return m


def _fresh_dir(name: str) -> str:
    """An empty directory of this script's own under the output dir."""
    path = os.path.join(OUT_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


class _CacheTraffic:
    """Counts JAX's own persistent-cache events: a cache the machine
    came with serves hits instead of growing."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------ phases


def phase_route(luts: int, chan_width: int,
                max_wl_ratio: float = 1.10) -> dict:
    """Placed circuit -> flow.run_route (timing-driven, verified,
    default RouterOpts) twice -> native serial router on the same
    problem.  Pass = legal, wirelength within ``max_wl_ratio`` of the
    native router's, finite critical path, identical second run."""
    import math

    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.route.serial_native import NativeSerialRouter

    m = _fresh_metrics()
    f = F.run_place_native(
        F.synth_flow(num_luts=luts, chan_width=chan_width))
    walls, results = [], []
    for _ in range(2):
        # a fresh analyzer per run: the second route must start from
        # the same criticalities as the first, not from its result
        f.analyzer = None
        t0 = time.perf_counter()
        F.run_route(f, timing_driven=True, verify=True)
        walls.append(round(time.perf_counter() - t0, 3))
        results.append(f.route)
        if len(results) == 1:
            compiles = m.counter("route.dispatch.compiles").value
    r = results[0]
    cpd = f.crit_path_delay
    native = NativeSerialRouter(f.rr).route(f.term)
    out = dict(
        phase="route", luts=luts, chan_width=chan_width,
        grid=[f.grid.nx, f.grid.ny], nets=len(f.term.net_ids),
        success=bool(r.success), iterations=int(r.iterations),
        windows=len(r.stats), sweeps=int(r.total_relax_steps),
        wirelength=int(r.wirelength),
        wirelength_second_run=int(results[1].wirelength),
        native_success=bool(native.success),
        native_wirelength=int(native.wirelength),
        wl_ratio=round(r.wirelength / max(1, native.wirelength), 4),
        crit_path_ns=cpd * 1e9, dispatch_compiles=int(compiles),
        cold_route_s=walls[0], warm_route_s=walls[1],
        peak_bytes_in_use=_peak_bytes())
    say(**out)
    if not (r.success and results[1].success):
        raise RuntimeError(f"route phase: not routed legally: {out}")
    if not native.success:
        raise RuntimeError(f"route phase: native reference failed: {out}")
    if r.wirelength > max_wl_ratio * native.wirelength:
        raise RuntimeError(f"route phase: wirelength {r.wirelength} > "
                           f"{max_wl_ratio} x native {native.wirelength}")
    if not math.isfinite(cpd) or cpd <= 0:
        raise RuntimeError(f"route phase: critical path {cpd}")
    if results[1].wirelength != r.wirelength:
        raise RuntimeError(f"route phase: second run wirelength "
                           f"{results[1].wirelength} != {r.wirelength}")
    return out


def phase_flow(luts: int, chan_width: int, out_dir: str) -> dict:
    """README's first quick-start line, in process: the only phase that
    runs the device SA placer.  Pass = exit 0 (routed; run_route's
    legality oracle raised otherwise) and the three artifact files."""
    from parallel_eda_tpu.__main__ import main as cli_main

    _fresh_metrics()
    t0 = time.perf_counter()
    rc = cli_main(["--luts", str(luts),
                   "--route_chan_width", str(chan_width),
                   "--out_dir", out_dir])
    names = sorted(os.listdir(out_dir)) if os.path.isdir(out_dir) else []
    out = dict(phase="flow", luts=luts, chan_width=chan_width, rc=rc,
               artifacts=names,
               wall_s=round(time.perf_counter() - t0, 3))
    say(**out)
    if rc != 0:
        raise RuntimeError(f"flow phase: CLI exit code {rc}")
    for ext in (".net", ".place", ".route"):
        if not any(n.endswith(ext) for n in names):
            raise RuntimeError(f"flow phase: no {ext} artifact in {names}")
    return out


def _job_specs(luts: int, chan_width: int, n_jobs: int):
    """(job_id, tenant, spec): ``n_jobs`` seeds over two tenants."""
    return [(f"t{i % 2}-s{i + 1}", f"t{i % 2}",
             {"luts": luts, "chan_width": chan_width, "seed": i + 1,
              "name": f"l{luts}_s{i + 1}"}) for i in range(n_jobs)]


def _doctor(flag: str, path: str) -> None:
    """tools/flow_doctor.py (stdlib only, safe as a child) must exit 0."""
    r = subprocess.run([sys.executable, FLOW_DOCTOR, flag, path],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"flow_doctor {flag} {path} exit "
                           f"{r.returncode}: {r.stdout[-2000:]}"
                           f"{r.stderr[-2000:]}")


def _check_daemon_summary(summary: dict, path: str, n_jobs: int) -> dict:
    """Shared by the solo daemon and each fleet worker: every job done,
    the doctor agrees (its heartbeat-gap rule included, at the
    product's own interval), and the degradation ladder never moved."""
    bad = [j for j in summary["jobs"] if j.get("state") != "done"]
    if bad or len(summary["jobs"]) != n_jobs:
        raise RuntimeError(f"daemon: {len(summary['jobs'])} jobs, "
                           f"not done: {bad}")
    _doctor("--daemon-summary", path)
    resil = summary["resil"]["metrics"]
    moved = {k: resil.get(f"route.resil.{k}", 0) for k in
             ("degradation_steps", "retries", "watchdog_timeouts")}
    if any(moved.values()):
        raise RuntimeError(f"daemon: resilience ladder moved on a "
                           f"fault-free run: {moved}")
    return moved


def phase_daemon(luts: int, chan_width: int, slice_iters: int,
                 inbox: str, n_jobs: int = 4) -> dict:
    """One daemon on one chip, the serving path: jobs from two tenants
    through the durable inbox, RouteDaemon.run to idle.  Pass = every
    job done and legal, wirelength equal to a solo Router.route of the
    same spec, flow_doctor green, zero ladder steps/retries/timeouts."""
    from parallel_eda_tpu.flow import synth_flow
    from parallel_eda_tpu.route.router import Router, RouterOpts
    from parallel_eda_tpu.serve.daemon import (DaemonOpts, build_daemon,
                                               submit_job)

    m = _fresh_metrics()
    os.makedirs(inbox)           # a FRESH inbox: fail if it exists
    specs = _job_specs(luts, chan_width, n_jobs)
    for job_id, tenant, spec in specs:
        submit_job(inbox, spec, tenant=tenant, job_id=job_id)
    t0 = time.perf_counter()
    # the product's own pacing and liveness settings; only "run to idle"
    daemon = build_daemon(inbox, luts=luts, chan_width=chan_width,
                          slice_iters=slice_iters,
                          opts=DaemonOpts(exit_when_idle=2))
    daemon.run()
    wall = round(time.perf_counter() - t0, 3)
    summary = json.loads(json.dumps(daemon.summary(), default=str))
    path = os.path.join(inbox, "summary.json")
    with open(path, "w") as fh:
        json.dump(summary, fh)
    moved = _check_daemon_summary(summary, path, n_jobs)
    got = {j["job_id"]: j.get("wirelength") for j in summary["jobs"]}
    # solo reference: the same spec through a plain Router with the
    # daemon's RouterOpts (no slicing, no resilience runtime)
    base = daemon.service.base_opts
    solo = {}
    for job_id, _, spec in specs:
        fl = synth_flow(num_luts=luts, chan_width=chan_width,
                        seed=spec["seed"])
        res = Router(fl.rr, RouterOpts(
            batch_size=base.batch_size, sink_group=base.sink_group,
            max_router_iterations=base.max_router_iterations)
        ).route(fl.term)
        solo[job_id] = int(res.wirelength) if res.success else None
    out = dict(phase="daemon", luts=luts, chan_width=chan_width,
               jobs=n_jobs, wirelength=got, solo_wirelength=solo,
               slices={j["job_id"]: j.get("slices")
                       for j in summary["jobs"]},
               dispatch_compiles=int(
                   m.counter("route.dispatch.compiles").value),
               resil=moved, heartbeat=summary["daemon"]["heartbeat"],
               wall_s=wall)
    say(**out)
    if got != solo:
        raise RuntimeError(f"daemon: wirelength {got} != solo {solo}")
    return out


# ------------------------------------------------- four-chip phases


def _device_peaks(devices):
    """Each device allocator's own high-water mark in bytes, by device
    id — or None where the backend reports none (the CPU)."""
    peaks = {}
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks[d.id] = int(stats["peak_bytes_in_use"])
    return peaks


def _held_data(before: dict, after: dict, ref_id: int) -> list:
    """Device ids whose allocator says the route in between put data
    there: the high-water mark rose.  On the device the one-device
    reference ran on the mark was set already; there it need only be
    non-zero."""
    return sorted(i for i in after
                  if after[i] > (0 if i == ref_id else before[i]))


def phase_mesh(luts: int, chan_width: int, n_devices: int = 4,
               which=("mesh_gspmd", "mesh_row")) -> dict:
    """One placed circuit, routed on one device (the reference) and then
    through the sub-phases named in ``which``: ``mesh_gspmd`` is the
    CLI's ``--mesh 2x2`` path, ``mesh_row`` the row-sharded relaxation
    (``mesh_shards``) with NO resilience runtime — a failing transport
    raises instead of demoting.  Pass = each legal with the reference's
    wirelength, and the ROUTE ITSELF put data on every device: each
    device allocator's high-water mark rose across the route (where the
    backend reports one; one sub-phase per process, as the chip run
    does, or an earlier sub-phase's mark hides the later one's), and
    for ``mesh_row`` the route's own dispatch records name the shard
    count and transport and count its halo exchanges."""
    import jax

    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.obs import Tracer, set_tracer
    from parallel_eda_tpu.parallel.shard import make_mesh
    from parallel_eda_tpu.route.router import RouterOpts

    f = F.run_place_native(
        F.synth_flow(num_luts=luts, chan_width=chan_width))
    devices = jax.devices()[:n_devices]
    all_ids = sorted(d.id for d in devices)

    def route(opts=None, mesh=None):
        f.analyzer = None
        t0 = time.perf_counter()
        F.run_route(f, opts, timing_driven=True, verify=True, mesh=mesh)
        return f.route, round(time.perf_counter() - t0, 3)

    def check(name, res, wall, before, **more):
        after = _device_peaks(devices)
        held = None if after is None else _held_data(
            before, after, devices[0].id)
        say(phase=name, success=bool(res.success),
            wirelength=int(res.wirelength), reference=int(ref.wirelength),
            devices_with_data=held,
            peak_bytes={"before": before, "after": after},
            wall_s=wall, **more)
        if not res.success or res.wirelength != ref.wirelength:
            raise RuntimeError(f"{name}: wirelength {res.wirelength} "
                               f"(success={res.success}) != reference "
                               f"{ref.wirelength}")
        if held is not None and held != all_ids:
            raise RuntimeError(f"{name}: the route put data on devices "
                               f"{held} only, of {all_ids}")
        out[name] = int(res.wirelength)

    _fresh_metrics()
    ref, ref_s = route()
    if not ref.success:
        raise RuntimeError("mesh: one-device reference did not route")
    say(phase="mesh_reference", luts=luts, chan_width=chan_width,
        wirelength=int(ref.wirelength), iterations=int(ref.iterations),
        wall_s=ref_s)
    out = {"reference": int(ref.wirelength)}

    if "mesh_gspmd" in which:       # __main__.py's --mesh 2x2
        side = int(round(n_devices ** 0.5))
        mesh = make_mesh(n_devices, shape=(side, n_devices // side))
        _fresh_metrics()
        before = _device_peaks(devices)
        res, wall = route(mesh=mesh)
        check("mesh_gspmd", res, wall, before,
              mesh=list(mesh.devices.shape))

    if "mesh_row" in which:         # RouterOpts(mesh_shards=n), no resil
        m = _fresh_metrics()
        tracer = Tracer()
        set_tracer(tracer)
        before = _device_peaks(devices)
        try:
            res, wall = route(opts=RouterOpts(mesh_shards=n_devices))
        finally:
            set_tracer(None)
        # what the route's own dispatches ran under (router.py kplans)
        plans = [e["args"] for e in tracer.events
                 if e["name"] == "route.kernel"]
        ran = sorted({(p.get("mesh_shards"), p.get("mesh_impl"))
                      for p in plans}, key=str)
        halo = {k: m.counter(f"route.mesh.{k}").value
                for k in ("halo_exchanges", "halo_bytes",
                          "mesh_demotions")}
        check("mesh_row", res, wall, before, dispatches=len(plans),
              ran_under=ran, **halo)
        if len(ran) != 1 or ran[0][0] != n_devices:
            raise RuntimeError(f"mesh_row: dispatches ran under {ran}, "
                               f"not all on {n_devices} shards")
        if not halo["halo_exchanges"] or not halo["halo_bytes"] \
                or halo["mesh_demotions"]:
            raise RuntimeError(f"mesh_row: no halo traffic, or a "
                               f"demotion: {halo}")
        out["transport"] = ran[0][1]
    return out


def _run_daemon_cli(argv, log_path: str, env=None, timeout_s=900):
    """``python -m parallel_eda_tpu daemon ...`` as a child (the parent
    stays off the backend); stderr kept in ``log_path``."""
    with open(log_path, "ab") as log:
        r = subprocess.run(
            [sys.executable, "-m", "parallel_eda_tpu", "daemon", *argv],
            cwd=REPO, stdout=subprocess.DEVNULL, stderr=log, env=env,
            timeout=timeout_s)
    if r.returncode != 0:
        with open(log_path, "rb") as log:
            tail = log.read()[-4000:].decode("utf-8", "replace")
        raise RuntimeError(f"daemon {argv[0]} exit {r.returncode}:\n{tail}")


def _fleet_jobs(luts: int, chan_width: int, n_workers: int, n_jobs: int):
    """(specs, jobs per worker): job ids chosen so the fleet's static
    assignment gives every worker work (preferred_worker is a pure hash
    of id and roster)."""
    from parallel_eda_tpu.serve.daemon import preferred_worker

    roster = [f"w{i}" for i in range(n_workers)]
    specs, per_worker, seed = [], {w: 0 for w in roster}, 0
    share = -(-n_jobs // n_workers)
    while len(specs) < n_jobs:
        seed += 1
        # four tenants: the admission controller's fair-share cap holds
        # one tenant to 2 of the first 5 queued jobs
        tenant = f"t{len(specs) % 4}"
        job_id = f"{tenant}-s{seed}"
        w = preferred_worker(job_id, roster)
        if per_worker[w] < share:
            per_worker[w] += 1
            specs.append((job_id, tenant,
                          {"luts": luts, "chan_width": chan_width,
                           "seed": seed, "name": f"l{luts}_s{seed}"}))
    return specs, per_worker


def _fill_inbox(box: str, specs) -> None:
    from parallel_eda_tpu.serve.daemon import submit_job

    os.makedirs(box)
    for job_id, tenant, spec in specs:
        submit_job(box, spec, tenant=tenant, job_id=job_id)


def _run_fleet(box: str, specs, per_worker: dict, common) -> dict:
    """``daemon fleet --workers N`` at the product's own liveness and
    lease settings over a filled inbox, then everything the fleet must
    show by itself: every job done exactly once, both doctors green for
    the fleet and for each worker, no lease lost, expired or stolen and
    no job failed over (nothing was killed: an expiry would be a live
    worker gone silent inside a compile).  Each worker's device is in
    the result for ``_check_own_chips``."""
    n_jobs = len(specs)
    fleet_path = os.path.join(box, "fleet_summary.json")
    _run_daemon_cli(["fleet", "--inbox", box,
                     "--workers", str(len(per_worker)),
                     "--expect_jobs", str(n_jobs), "--no_transport",
                     "--summary", fleet_path, *common],
                    os.path.join(box, "supervisor.log"))
    with open(fleet_path) as fh:
        doc = json.load(fh)
    done = [j for j in doc["jobs"] if j.get("state") == "done"]
    got = {j["job_id"]: j.get("wirelength") for j in done}
    workers = {}
    for w, n_own in per_worker.items():
        path = os.path.join(box, f"summary.{w}.json")
        with open(path) as fh:
            wdoc = json.load(fh)
        _check_daemon_summary(wdoc, path, n_own)
        workers[w] = {"device": wdoc.get("device"), "jobs_done": n_own,
                      "heartbeat": wdoc["daemon"]["heartbeat"]}
    fm = doc["fleet"]["metrics"]
    leases = {k: fm.get(f"route.fleet.{k}", 0) for k in
              ("leases_acquired", "lease_renewals", "leases_lost",
               "leases_expired", "lease_steals", "jobs_failed_over")}
    out = dict(wirelength=got, worker_devices=workers, leases=leases)
    say(phase="fleet", **out)
    if sorted(j["job_id"] for j in done) != sorted(s[0] for s in specs):
        raise RuntimeError(f"fleet4: done jobs {sorted(got)} — not "
                           f"every job exactly once")
    _doctor("--fleet-summary", fleet_path)
    if any(leases[k] for k in ("leases_lost", "leases_expired",
                               "lease_steals", "jobs_failed_over")):
        raise RuntimeError(f"fleet4: leases moved between live workers "
                           f"on a fault-free run: {leases}")
    return out


def _check_own_chips(workers: dict) -> None:
    """Every worker ran its jobs on a TPU it held alone (it saw exactly
    one device — two live processes cannot hold one chip) and was
    pinned to a host chip of its own."""
    devs = [w["device"] for w in workers.values()]
    if any(d["platform"] != "tpu" or d["count"] != 1 or d["chip"] is None
           for d in devs) or len({d["chip"] for d in devs}) != len(devs):
        raise RuntimeError(f"fleet4: workers did not each hold their "
                           f"own TPU chip, alone: {workers}")


def phase_fleet(luts: int, chan_width: int, n_workers: int, n_jobs: int,
                base_dir: str, slice_iters: int = 3) -> dict:
    """``daemon fleet --workers N`` over one inbox, no chaos, against a
    solo daemon on the same jobs.  Pass = what ``_run_fleet`` checks,
    wirelengths equal to the solo daemon's, and every worker alone on
    a TPU chip of its own."""
    from parallel_eda_tpu.serve.fleet import chip_env

    specs, per_worker = _fleet_jobs(luts, chan_width, n_workers, n_jobs)
    common = ["--luts", str(luts), "--chan_width", str(chan_width),
              "--slice", str(slice_iters), "--exit_when_idle", "2"]

    # solo daemon: same jobs, one process pinned to the first chip
    solo_box = os.path.join(base_dir, "solo")
    _fill_inbox(solo_box, specs)
    solo_path = os.path.join(solo_box, "summary.json")
    t0 = time.perf_counter()
    _run_daemon_cli(["run", "--inbox", solo_box, "--summary", solo_path,
                     *common], os.path.join(solo_box, "stderr.log"),
                    env={**os.environ, **chip_env(0, 1)})
    solo_s = round(time.perf_counter() - t0, 3)
    with open(solo_path) as fh:
        solo_doc = json.load(fh)
    _check_daemon_summary(solo_doc, solo_path, n_jobs)
    solo = {j["job_id"]: j.get("wirelength") for j in solo_doc["jobs"]}

    box = os.path.join(base_dir, "fleet")
    _fill_inbox(box, specs)
    t0 = time.perf_counter()
    fleet = _run_fleet(box, specs, per_worker, common)
    out = dict(phase="fleet4", workers=n_workers, jobs=n_jobs,
               wirelength=fleet["wirelength"], solo_wirelength=solo,
               solo_device=solo_doc.get("device"),
               worker_devices=fleet["worker_devices"],
               leases=fleet["leases"], solo_wall_s=solo_s,
               fleet_wall_s=round(time.perf_counter() - t0, 3))
    say(**out)
    if fleet["wirelength"] != solo:
        raise RuntimeError(f"fleet4: wirelength {fleet['wirelength']} "
                           f"!= solo {solo}")
    _check_own_chips(fleet["worker_devices"])
    return out


# -------------------------------------------------------------- mains


def _mesh_child(which: str) -> int:
    """Child of ``--four-chips``: holds all four chips, runs one mesh
    sub-phase against its one-device reference, prints the device as
    its last line."""
    from parallel_eda_tpu.route.router import (
        enable_persistent_compile_cache)

    device = require_tpu(4)
    enable_persistent_compile_cache()
    phase_mesh(MESH_LUTS, MESH_W, n_devices=4, which=(which,))
    print(json.dumps({"device": device}), flush=True)
    return 0


def _run_mesh_child(which: str, timeout_s: int) -> dict:
    """One mesh sub-phase in a process of its own, under a time limit:
    a transport that hangs the chips is killed, not waited for."""
    child = subprocess.run(
        [sys.executable, "-c", "import sys, chip_smoke; "
         f"sys.exit(chip_smoke._mesh_child({which!r}))"],
        cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=timeout_s)
    sys.stdout.write(child.stdout)
    sys.stdout.flush()
    _keep_lines("four/phases.jsonl", child.stdout)
    if child.returncode != 0:
        raise SystemExit(f"chip_smoke: {which} child exit "
                         f"{child.returncode}")
    device = json.loads(child.stdout.strip().splitlines()[-1])["device"]
    if device["platform"] != "tpu" or device["count"] != 4:
        raise SystemExit(f"chip_smoke: {which} child ran on {device}")
    return device


def main_four_chips() -> int:
    """Parent never initialises a JAX backend: each mesh sub-phase runs
    in a child that holds all four chips; between them, once no process
    holds a chip, the fleet's workers take one each."""
    from parallel_eda_tpu.serve import fleet

    n_chips = fleet.count_tpu_chips()
    if n_chips != 4:
        raise SystemExit(f"chip_smoke --four-chips: needs a host with 4 "
                         f"TPU chips, found {n_chips}")
    base = _fresh_dir("four")
    device = _run_mesh_child("mesh_gspmd", timeout_s=900)
    fleet4 = phase_fleet(FLEET_LUTS, FLEET_W, 4, FLEET_JOBS, base)
    _keep_lines("four/phases.jsonl", json.dumps(fleet4) + "\n")
    _run_mesh_child("mesh_row", timeout_s=600)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the paths that exist only across chips "
                    "(mesh_gspmd, mesh_row, fleet4) and their one-chip "
                    "references instead of the default phases")
    args = ap.parse_args(argv)
    # fail before touching the chip when the program is not next to us
    import parallel_eda_tpu  # noqa: F401
    if args.four_chips:
        return main_four_chips()

    from parallel_eda_tpu.route.router import (
        enable_persistent_compile_cache)

    device = require_tpu(1)
    cache_dir = enable_persistent_compile_cache()
    before = _cache_entries(cache_dir)
    traffic = _CacheTraffic()
    say(phase="start", device=device, compile_cache_dir=cache_dir,
        cache_entries_before=before)
    base = _fresh_dir("one")
    phase_route(ROUTE_LUTS, ROUTE_W)
    phase_flow(FLOW_LUTS, FLOW_W, os.path.join(base, "flow"))
    phase_daemon(DAEMON_LUTS, DAEMON_W, DAEMON_SLICE,
                 os.path.join(base, "inbox"))
    after = _cache_entries(cache_dir)
    say(phase="cache", compile_cache_dir=cache_dir,
        cache_entries_before=before, cache_entries_after=after,
        cache_hits=traffic.hits, cache_misses=traffic.misses)
    # a cold machine must have written its programs there; one that
    # came with the cache must have been served from it
    if after <= before and not traffic.hits:
        raise RuntimeError(f"compile cache {cache_dir} neither grew "
                           f"({before} -> {after}) nor served a hit")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
