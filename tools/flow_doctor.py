#!/usr/bin/env python3
"""Flow doctor: one health gate over every observability artifact the
flow leaves behind — run it after a bench (or in CI) and a nonzero exit
means the flow regressed or an instrument broke.

Stdlib-only like its siblings (trace_report.py / ledger_report.py,
whose --check rule sets it reuses by import): it must run anywhere the
artifacts land, without jax or the repo on the path.

    python tools/flow_doctor.py --row row.json --against previous_row.json
    python tools/flow_doctor.py --trace out.json --metrics metrics.json \
                                --devprof devprof.json

Checks, each skipped (with a note) when its artifact is not given:

  trace    trace_report validate + pipeline-shape + counter-track rules
  metrics  ledger_report validate (work-ledger invariants + devcost
           gauge sanity)
  devprof  the device-truth ledger (stats_dir/devprof.json): at least
           one captured variant; every measured record has positive
           measured bytes and a measured-vs-modeled delta inside the
           declared band; all-unavailable (backend exposes no cost
           analysis) passes with a note — absence of the instrument is
           not a flow regression
  row      the fresh bench row against the previous BENCH_*.json (or
           --against FILE): nets/s must not drop more than --nets-tol
           (default 10%), wirelength must not increase at all, the
           pipeline fill factor keeps a floor, the wasted-sweep
           fraction must not jump; keys missing from either row are
           tolerated (older rows predate some riders).  Rows from
           DIFFERENT backends are never compared: the gate is skipped
           with a warning (exit 0) — the r04/r05 CPU-fallback rows
           were silently diffed against TPU rows once; never again
  corpus   (--corpus [--scenario S] --runs-dir runs) gate the most
           recent corpus row of each scenario against the MEDIAN of
           the last --corpus-k same-backend rows of its trajectory
           (runs/<scenario>.jsonl, see obs/runstore.py): the metric of
           record keeps the --nets-tol floor and wirelength must not
           exceed the trajectory median.  Cross-backend rows and
           pre_pr2 imports never enter the median; a scenario with no
           same-backend history skips with a note
  daemon   (--daemon-summary FILE) the route daemon's exit summary
           (serve/daemon_cli.py run --summary): every rejection and
           every shed job must carry a machine-readable reason/cause,
           shedding must coincide with recorded overload cycles, the
           heartbeat must have no gap beyond its declared interval
           band, and recovered jobs must be backed by a journal that
           actually wrote — a daemon that drops work silently or
           claims recovery without durable state is UNHEALTHY
  fleet    (--fleet-summary FILE) the fleet supervisor's aggregate
           summary (daemon fleet --summary, serve/fleet.py): failover
           implies a measured lease expiry, transport retries stay
           inside the client's declared budget, every lease is
           released at shutdown (no orphaned work), no job completes
           twice across workers, and every job row names its worker —
           a fleet that fakes failover or leaks work is UNHEALTHY
  fleet-trace  (--fleet-trace FILE) the MERGED fleet trace
           (tools/trace_merge.py output): the clock-alignment residual
           skew stays under the declared bound; every done job's
           lifecycle is one contiguous chain (submit/admit -> slice
           spans -> terminal instant, in order); slice spans with no
           closing terminal/reject/shed instant are orphans; a job
           whose slices cross >= 2 worker tracks must carry the
           lease-steal (or failover) instant that links the break —
           a failover the trace cannot connect never happened; every
           reject/shed verdict instant names a machine-readable code
  lint     (--lint [--lint-root DIR]) the graft-lint static rule set
           (parallel_eda_tpu/analysis): donation safety, jit-signature
           drift, determinism, durable-write atomicity, metric-name
           registry.  Any live finding (or a baseline entry missing
           its justification) is UNHEALTHY

Exit codes: 0 healthy, 1 regression / broken invariant, 2 usage or
unreadable artifact.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import math
import os
import statistics
import sys

# mirrors obs/devprof.py DELTA_BAND_LOG10 (stdlib-only: no repo import)
DEVCOST_DELTA_BAND_LOG10 = 2.0

# bench-row tolerances (the CLI can override the first)
NETS_PER_SEC_TOL = 0.10        # fresh value >= (1 - tol) * previous
OVERLAP_FRAC_FLOOR = 0.5       # pipeline fill factor, when present
RELAX_WASTED_FRAC_SLACK = 0.15  # fresh <= previous + slack, when both


def _load_sibling(name: str):
    """Import a sibling tool module by file path, so the doctor works
    when invoked as a script (tools/ is not a package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def check_trace(path: str) -> list:
    tr = _load_sibling("trace_report")
    doc = _read_json(path)
    return (tr.validate(doc) + tr.check_pipeline(doc)
            + tr.check_counters(doc) + tr.check_lifecycle(doc))


def check_metrics(path: str) -> list:
    lr = _load_sibling("ledger_report")
    return lr.validate(_read_json(path))


def check_devprof(path: str) -> tuple:
    """Returns (errors, notes)."""
    doc = _read_json(path)
    errs, notes = [], []
    recs = doc.get("records")
    if not isinstance(recs, list) or not recs:
        return (["devprof ledger has no captured dispatch variants "
                 "(the profiler was enabled but note_variant never "
                 "fired — dispatch-site instrumentation is broken)"],
                notes)
    measured = [r for r in recs if isinstance(r, dict)
                and "unavailable" not in r]
    if not measured:
        # graceful-degradation contract: a backend without cost
        # analysis is not a flow regression
        notes.append(f"devprof: all {len(recs)} variant(s) unavailable "
                     f"({recs[0].get('unavailable', '?')}) — backend "
                     f"exposes no cost analysis; skipping devcost gates")
        return errs, notes
    band = doc.get("delta_band_log10", DEVCOST_DELTA_BAND_LOG10)

    def _in_band(bd):
        return (isinstance(bd, (int, float)) and bd > 0
                and abs(math.log10(bd)) <= band)

    # the band gates the DOMINANT (most-nets) variant — the one the
    # gauges and bench rows quote.  Endgame windows routing a handful
    # of nets sit structurally off the per-net traffic model (fixed
    # window overhead dominates), so their excursions are notes
    dominant = max(measured,
                   key=lambda r: (r.get("meta") or {}).get("nets", 0))
    for r in measured:
        key = r.get("key")
        ba = r.get("bytes_accessed", r.get("temp_bytes"))
        if not (isinstance(ba, (int, float)) and ba > 0):
            errs.append(f"devprof variant {key}: measured bytes not "
                        f"positive ({ba!r})")
        bd = r.get("bytes_delta")
        if bd is None or _in_band(bd):
            continue
        if r is dominant:
            errs.append(f"devprof dominant variant {key}: measured/"
                        f"modeled bytes {bd!r} outside the declared "
                        f"1e±{band} band")
        else:
            notes.append(f"devprof: small variant {key} "
                         f"({(r.get('meta') or {}).get('nets', '?')} "
                         f"nets) off-model (delta {bd}); fixed window "
                         f"overhead dominates below the band's scope")
    notes.append(f"devprof: {len(measured)}/{len(recs)} variant(s) "
                 f"measured, dominant delta "
                 f"{dominant.get('bytes_delta', 'n/a')}")
    return errs, notes


def _load_runstore():
    """obs/runstore.py by file path (same pattern as _load_sibling;
    the corpus module is deliberately stdlib-only so the doctor stays
    runnable without jax or the repo on sys.path)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "parallel_eda_tpu", "obs", "runstore.py")
    spec = importlib.util.spec_from_file_location(
        "runstore", os.path.normpath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row_of(doc):
    """Accept either a driver capture ({"parsed": row, ...}) or a bare
    bench row ({"metric": ..., "value": ...})."""
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc if isinstance(doc, dict) else None


def _row_backend(row) -> str:
    """Backend a bench row ran on: the stamped top-level field (new
    rows) falling back to detail.platform (older rows).  "" when the
    row predates both — unknown backends are treated as comparable, so
    the legacy history keeps gating itself."""
    if not isinstance(row, dict):
        return ""
    be = row.get("backend")
    if isinstance(be, str) and be:
        return be
    pl = (row.get("detail") or {}).get("platform")
    return pl if isinstance(pl, str) else ""


def latest_bench_rows(bench_dir: str, exclude: str = None) -> list:
    """BENCH_*.json paths in name order (the driver numbers them), the
    excluded path (the fresh row itself) removed."""
    paths = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")))
    if exclude:
        ex = os.path.abspath(exclude)
        paths = [p for p in paths if os.path.abspath(p) != ex]
    return paths


def check_row(fresh: dict, prev: dict, nets_tol: float) -> tuple:
    """Compare a fresh bench row against the previous one.  Returns
    (errors, notes); keys missing from either side are tolerated
    (older rows predate some detail riders)."""
    errs, notes = [], []
    fv, pv = fresh.get("value"), prev.get("value")
    if isinstance(fv, (int, float)) and isinstance(pv, (int, float)):
        floor = (1.0 - nets_tol) * pv
        if fv < floor:
            errs.append(
                f"{fresh.get('metric', 'value')} regressed: {fv} < "
                f"{floor:.4g} (= previous {pv} - {nets_tol:.0%})")
        else:
            notes.append(f"{fresh.get('metric', 'value')}: {fv} vs "
                         f"previous {pv} (floor {floor:.4g}) ok")
    else:
        notes.append("value missing from a row; throughput gate skipped")
    fd = fresh.get("detail") or {}
    pd = prev.get("detail") or {}
    fw, pw = fd.get("wirelength"), pd.get("wirelength")
    if isinstance(fw, (int, float)) and isinstance(pw, (int, float)):
        if fw > pw:
            errs.append(f"wirelength regressed: {fw} > previous {pw} "
                        f"(any increase fails)")
        else:
            notes.append(f"wirelength: {fw} vs previous {pw} ok")
    else:
        notes.append("wirelength missing from a row; gate skipped")
    of = (fd.get("pipeline") or {}).get("overlap_frac")
    if isinstance(of, (int, float)):
        if of < OVERLAP_FRAC_FLOOR:
            errs.append(f"pipeline overlap_frac {of} below the "
                        f"{OVERLAP_FRAC_FLOOR} floor: the async "
                        f"pipeline is not filling the device")
        else:
            notes.append(f"pipeline overlap_frac: {of} ok")
    wf = (fd.get("ledger") or {}).get("relax_wasted_frac")
    pwf = (pd.get("ledger") or {}).get("relax_wasted_frac")
    if isinstance(wf, (int, float)) and isinstance(pwf, (int, float)):
        if wf > pwf + RELAX_WASTED_FRAC_SLACK:
            errs.append(f"relax_wasted_frac jumped: {wf} > previous "
                        f"{pwf} + {RELAX_WASTED_FRAC_SLACK}")
        else:
            notes.append(f"relax_wasted_frac: {wf} vs previous {pwf} ok")
    dc = fd.get("devcost")
    if isinstance(dc, dict):
        if "unavailable" in dc:
            notes.append(f"row devcost: unavailable "
                         f"({dc['unavailable']})")
        else:
            ba = dc.get("bytes_accessed")
            if not (isinstance(ba, (int, float)) and ba > 0):
                errs.append(f"row devcost.bytes_accessed not positive: "
                            f"{ba!r}")
            if dc.get("delta_in_band") is False:
                errs.append(
                    f"row devcost measured/modeled bytes "
                    f"{dc.get('bytes_delta')} outside the declared "
                    f"1e±{dc.get('delta_band_log10')} band")
    me, mn = check_mesh_row(fresh)
    errs += me
    notes += mn
    return errs, notes


def check_mesh_row(row) -> tuple:
    """Mesh-consistency rule: a row whose metric snapshot claims halo
    traffic (route.mesh.halo_bytes > 0) must also record a multi-shard
    mesh — the SCHEMA v2 optional ``n_shards`` field or the
    ``route.mesh.n_shards`` gauge, > 1.  Halo bytes on a
    single-device run means the byte ledger is lying (or the mesh
    demoted and the booking didn't follow)."""
    errs, notes = [], []
    if not isinstance(row, dict):
        return errs, notes
    g = row.get("gauges") or {}
    hb = g.get("route.mesh.halo_bytes") or 0
    ns = row.get("n_shards") or g.get("route.mesh.n_shards") or 1
    if hb > 0:
        if ns <= 1:
            errs.append(f"mesh: route.mesh.halo_bytes {hb} > 0 but "
                        f"n_shards {ns} — halo traffic recorded on a "
                        f"single-device run")
        else:
            notes.append(f"mesh: halo_bytes {hb} with n_shards {ns} ok")
    return errs, notes


def check_corpus_scenario(rs, records: list, nets_tol: float,
                          k: int) -> tuple:
    """Gate a scenario's most recent corpus record against the median
    of the last ``k`` SAME-BACKEND rows of its trajectory.  Returns
    (errors, notes).  No same-backend history (first run on this
    backend, or only cross-backend / pre_pr2 rows behind it) is a
    skip-note, not a failure — the corpus has to be allowed to grow."""
    errs, notes = [], []
    fresh = records[-1]
    # consistency rules on the fresh row itself run even when there is
    # no trajectory yet (a first mesh run must already be coherent)
    me, mn = check_mesh_row(fresh)
    errs += me
    notes += mn
    backend = _row_backend(fresh)
    hist = rs.latest_same_backend(records[:-1], backend, k)
    hist = [r for r in hist if r.get("metric") == fresh.get("metric")]
    if not hist:
        notes.append(f"no same-backend ({backend or '?'}) history; "
                     f"corpus gate skipped")
        return errs, notes
    med = statistics.median(r["value"] for r in hist)
    floor = (1.0 - nets_tol) * med
    fv = fresh.get("value")
    if fv < floor:
        errs.append(f"{fresh.get('metric')} regressed: {fv} < "
                    f"{floor:.4g} (= median of last {len(hist)} "
                    f"{backend} row(s) {med:.4g} - {nets_tol:.0%})")
    else:
        notes.append(f"{fresh.get('metric')}: {fv} vs {backend} "
                     f"trajectory median {med:.4g} "
                     f"(floor {floor:.4g}) ok")
    wls = [(r.get("qor") or {}).get("wirelength") for r in hist]
    wls = [w for w in wls if isinstance(w, (int, float))]
    fw = (fresh.get("qor") or {}).get("wirelength")
    if isinstance(fw, (int, float)) and wls:
        wmed = statistics.median(wls)
        if fw > wmed:
            errs.append(f"wirelength regressed: {fw} > trajectory "
                        f"median {wmed:.4g} (any increase fails)")
        else:
            notes.append(f"wirelength: {fw} vs trajectory median "
                         f"{wmed:.4g} ok")
    else:
        notes.append("wirelength missing from trajectory; gate skipped")
    return errs, notes


def check_corpus(runs_dir: str, scenario, nets_tol: float,
                 k: int) -> tuple:
    """Corpus-mode entry: gate one scenario (or, with scenario=None,
    every scenario in the corpus).  Returns (errors, notes)."""
    rs = _load_runstore()
    names = [scenario] if scenario else rs.scenarios(runs_dir)
    if not names:
        return ([f"corpus: no scenarios under {runs_dir}/ (did the "
                 f"bench append its row?)"], [])
    errs, notes = [], []
    for name in names:
        reader = getattr(rs, "read_runs_ex", None)
        if reader is not None:
            import warnings
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                records, skipped = reader(runs_dir, name)
            if skipped:
                notes.append(f"corpus[{name}]: skipped {skipped} "
                             f"corrupted/torn JSONL line(s) (counted, "
                             f"non-fatal — see obs/runstore.py)")
        else:
            records = rs.read_runs(runs_dir, name)
        if not records:
            errs.append(f"corpus[{name}]: no records "
                        f"(missing or all-invalid "
                        f"{rs.run_path(runs_dir, name)})")
            continue
        # multi-tenant scenarios (serve rows, runstore schema v2) carry
        # one row PER JOB: gate each (tenant, job_id) sub-trajectory on
        # its own history — jobs route different circuits, so comparing
        # one job's wirelength against another's median is noise
        if any(r.get("tenant") or r.get("job_id") for r in records):
            groups = {}
            for r in records:
                groups.setdefault(
                    (r.get("tenant"), r.get("job_id")), []).append(r)
            for (ten, jid), recs in sorted(
                    groups.items(), key=lambda kv: str(kv[0])):
                tag = f"{name}:{ten or '-'}/{jid or '-'}"
                se, sn = check_corpus_scenario(rs, recs, nets_tol, k)
                errs += [f"corpus[{tag}]: {e}" for e in se]
                notes += [f"corpus[{tag}]: {n}" for n in sn]
            continue
        se, sn = check_corpus_scenario(rs, records, nets_tol, k)
        errs += [f"corpus[{name}]: {e}" for e in se]
        notes += [f"corpus[{name}]: {n}" for n in sn]
    return errs, notes


def check_resil(doc: dict) -> tuple:
    """Resil rule set over a serve summary JSON (serve/cli.py with the
    resilience layer armed).  Returns (errors, notes).  The rules
    catch a recovery layer that is lying or unbounded:

      * quarantine without a matching cause (no injection, watchdog
        timeout, or dispatch error) — a healthy variant was
        blacklisted;
      * degradation steps without a cause — the ladder moved on its
        own;
      * retries above the published retry budget (retry_cap x
        observed causes) — unbounded retry loop;
      * retries without any backoff — a hot retry loop;
      * a terminal failed/timeout job with no failure_reason — the
        poison-job contract (diagnosable terminal states) broke.
    """
    errs, notes = [], []
    resil = doc.get("resil")
    if not isinstance(resil, dict):
        return (["serve-summary: no resil section (summary predates "
                 "the resilience layer, or it was not armed)"], notes)
    vals = resil.get("metrics") or {}

    def g(k):
        return vals.get("route.resil." + k) or 0

    inj = g("injections")
    wdt = g("watchdog_timeouts")
    derr = g("dispatch_errors")
    # a lost mesh member demotes the mesh ladder dimension to
    # single_chip (router._mesh_demote) — a legitimate, counted cause
    # for quarantine/degradation steps
    meshd = vals.get("route.mesh.mesh_demotions") or 0
    causes = inj + wdt + derr + meshd
    q = g("quarantined_variants")
    ret = g("retries")
    cap = g("retry_cap")
    deg = g("degradation_steps")
    if q and not causes:
        errs.append(f"resil: {q} quarantined variant(s) without any "
                    f"matching injection, watchdog timeout, or "
                    f"dispatch error — a healthy variant was "
                    f"blacklisted")
    if deg and not causes:
        errs.append(f"resil: {deg} degradation step(s) without any "
                    f"recorded cause")
    if ret:
        if not cap:
            errs.append(f"resil: {ret} retries recorded but no "
                        f"retry_cap gauge published — the retry "
                        f"policy is unbounded")
        elif ret > causes * cap:
            errs.append(f"resil: unbounded retries: {ret} > "
                        f"{causes} cause(s) x retry_cap {cap}")
        if ret > 1 and g("backoff_ms") <= 0:
            errs.append(f"resil: {ret} retries with zero total "
                        f"backoff — hot retry loop")
    for j in doc.get("jobs") or []:
        if (j.get("state") in ("failed", "timeout")
                and not j.get("failure_reason")):
            errs.append(f"resil: job {j.get('job_id')} is terminal "
                        f"{j.get('state')} without a failure_reason")
    faults = resil.get("faults") or {}
    notes.append(f"resil: injections={inj} timeouts={wdt} "
                 f"errors={derr} retries={ret} quarantined={q} "
                 f"degradations={deg} "
                 f"kinds_fired={faults.get('kinds_fired', 0)} "
                 f"checkpoints w/r={g('checkpoint_writes')}/"
                 f"{g('checkpoint_recoveries')}")
    return errs, notes


def check_warm(doc: dict) -> tuple:
    """``--warm`` over a serve/daemon summary: route.dispatch.compiles
    must be 0 -- a warm AOT library serves every window without a
    single window-program compile.  Returns (errors, notes)."""
    compiles = doc.get("dispatch_compiles")
    if compiles is None:
        return ["warm: --warm given but the summary has no "
                "dispatch_compiles field"], []
    if compiles:
        return [f"warm: warm run compiled {compiles} window "
                f"program(s); a warm library must serve with "
                f"dispatch_compiles==0"], []
    return [], ["warm: gate ok (dispatch_compiles=0)"]


# a beat may be late by this factor x interval before the doctor calls
# the daemon's liveness claim a lie (scheduling jitter is real; a 10x
# stall under a 1s interval is not jitter)
HEARTBEAT_GAP_FACTOR = 10.0


def check_daemon(doc: dict) -> tuple:
    """Daemon rule set over a daemon summary JSON (serve/daemon_cli.py
    ``run --summary``).  Returns (errors, notes).  The rules catch a
    daemon that drops or invents work silently:

      * a REJECTED submission without a machine-readable reason
        ({"code": ...}) — the admission controller must never ghost a
        client;
      * a SHED job without an overload cause, or any OVERLOAD shedding
        while the daemon never recorded an overloaded cycle — eviction
        must be traceable to measured overload, not mood (the
        "lease_stolen" cause is exempt: that is fleet lease fencing,
        a correctness eviction, not load shedding);
      * a heartbeat gap beyond HEARTBEAT_GAP_FACTOR x the declared
        interval (or an uptime with no beats at all) — the daemon
        claimed liveness it did not have;
      * recovered jobs without a journal that exists and wrote — a
        recovery story with no durable state behind it.
    """
    errs, notes = [], []
    d = doc.get("daemon")
    if not isinstance(d, dict):
        return (["daemon-summary: no daemon section (not a daemon "
                 "summary JSON?)"], notes)
    vals = d.get("metrics") or {}

    def g(k):
        return vals.get("route.daemon." + k) or 0

    jobs = doc.get("jobs") or []
    rejected = [j for j in jobs if j.get("state") == "rejected"]
    for j in rejected:
        reason = j.get("reject_reason")
        if not (isinstance(reason, dict) and reason.get("code")):
            errs.append(f"daemon: job {j.get('job_id')} rejected "
                        f"without a machine-readable reason "
                        f"(got {reason!r})")
    shed = [j for j in jobs if j.get("state") == "shed"]
    for j in shed:
        cause = j.get("shed_cause")
        if not (isinstance(cause, dict) and cause.get("code")):
            errs.append(f"daemon: job {j.get('job_id')} shed without "
                        f"an overload cause (got {cause!r})")
    # lease fencing (a peer holds the live lease: "lease_stolen") is a
    # correctness eviction, not load shedding — it needs no measured
    # overload behind it
    overload_shed = [j for j in shed
                     if (j.get("shed_cause") or {}).get("code")
                     != "lease_stolen"]
    if overload_shed and not g("overloaded_cycles"):
        errs.append(f"daemon: {len(overload_shed)} job(s) shed but "
                    f"the daemon never recorded an overloaded cycle — "
                    f"load was dropped without measured overload")
    hb = d.get("heartbeat") or {}
    interval = hb.get("interval_s")
    beats = hb.get("beats", 0)
    gap = hb.get("max_gap_s", 0)
    uptime = d.get("uptime_s", 0)
    if isinstance(interval, (int, float)) and interval > 0:
        if (not beats and isinstance(uptime, (int, float))
                and uptime > interval):
            errs.append(f"daemon: {uptime}s of uptime with zero "
                        f"heartbeats (interval {interval}s) — the "
                        f"liveness file never existed")
        elif (isinstance(gap, (int, float))
                and gap > HEARTBEAT_GAP_FACTOR * interval):
            errs.append(f"daemon: worst heartbeat gap {gap}s exceeds "
                        f"{HEARTBEAT_GAP_FACTOR:.0f}x the declared "
                        f"{interval}s interval — the daemon claimed "
                        f"liveness it did not have")
    recovered = [j for j in jobs if j.get("recovered")]
    n_rec = max(len(recovered), int(g("recovered")))
    if n_rec:
        jr = d.get("journal") or {}
        if not (jr.get("file") and (jr.get("writes") or 0) > 0
                and (jr.get("entries") or 0) > 0):
            errs.append(f"daemon: {n_rec} job(s) claim recovery but "
                        f"the journal section shows no durable state "
                        f"(file={jr.get('file')!r} "
                        f"writes={jr.get('writes')} "
                        f"entries={jr.get('entries')})")
    inbox = d.get("inbox") or {}
    notes.append(f"daemon: cycles={d.get('cycles')} "
                 f"uptime={uptime}s beats={beats} max_gap={gap}s "
                 f"admitted={g('admitted')} rejected={len(rejected)} "
                 f"shed={len(shed)} recovered={n_rec} "
                 f"torn_inbox_lines={inbox.get('torn_lines', 0)}")
    return errs, notes


def check_fleet(doc: dict) -> tuple:
    """Fleet rule set over a fleet summary JSON (``daemon fleet
    --summary``, serve/fleet.py).  Returns (errors, notes).  The rules
    catch a fleet that fakes failover or leaks work:

      * failover implies lease expiry — a job cannot "fail over" to a
        peer unless its old lease measurably expired first
        (jobs_failed_over > 0 requires leases_expired > 0);
      * transport retries bounded — the server must never observe a
        client attempt number above the client's own declared cap, and
        total retries must fit inside drops x (cap - 1): retry storms
        are a bug, not resilience;
      * no orphaned leases — when the fleet is done, every lease
        record is terminal (released); a held lease with no worker
        behind it is leaked work;
      * no job finishes twice — exactly-once execution is the entire
        point of the lease protocol;
      * worker attribution — every job row names the worker that
        produced it, or the failover story is unauditable.
    """
    errs, notes = [], []
    fl = doc.get("fleet")
    if not isinstance(fl, dict):
        return (["fleet-summary: no fleet section (not a fleet "
                 "summary JSON?)"], notes)
    vals = fl.get("metrics") or {}

    def g(k):
        return vals.get("route.fleet." + k) or 0

    if fl.get("timed_out"):
        errs.append("fleet: the supervisor timed out before the fleet "
                    "finished — completion was never observed")

    # -- failover implies lease expiry
    if g("jobs_failed_over") and not g("leases_expired"):
        errs.append(f"fleet: {g('jobs_failed_over')} job(s) claim "
                    f"failover but no lease ever expired — a peer "
                    f"took work from a live owner")

    # -- transport retries bounded
    tr = fl.get("transport")
    if isinstance(tr, dict):
        cap = tr.get("retry_cap_seen") or 0
        seen = tr.get("max_attempt_seen") or 0
        drops = tr.get("drops") or 0
        retries = tr.get("retries") or 0
        if cap and seen > cap:
            errs.append(f"fleet: transport observed attempt #{seen} "
                        f"above the client's declared cap of {cap} — "
                        f"the retry budget is a lie")
        if drops and cap and retries > drops * max(cap - 1, 1):
            errs.append(f"fleet: {retries} transport retries exceed "
                        f"the budget for {drops} drop(s) at cap {cap} "
                        f"({drops * max(cap - 1, 1)}) — retry storm")
        if drops and not retries:
            errs.append(f"fleet: transport dropped {drops} "
                        f"request(s) but no client ever retried — "
                        f"submissions were silently lost")

    # -- no orphaned leases
    leases = fl.get("leases") or {}
    orphans = sorted(j for j, d in leases.items()
                     if isinstance(d, dict) and not d.get("released"))
    if orphans:
        errs.append(f"fleet: {len(orphans)} unreleased lease(s) after "
                    f"shutdown ({', '.join(orphans[:5])}"
                    f"{', ...' if len(orphans) > 5 else ''}) — "
                    f"leaked work nobody will finish")

    # -- no job finishes twice; worker attribution
    jobs = doc.get("jobs") or []
    done_by: dict = {}
    for j in jobs:
        jid = j.get("job_id")
        if j.get("state") == "done":
            done_by.setdefault(jid, []).append(j.get("worker"))
        if not j.get("worker"):
            errs.append(f"fleet: job {jid} row carries no worker "
                        f"attribution — failover is unauditable")
    for jid, workers in sorted(done_by.items()):
        if len(workers) > 1:
            errs.append(f"fleet: job {jid} finished {len(workers)} "
                        f"times (workers {', '.join(map(str, workers))})"
                        f" — the lease protocol failed exactly-once")

    killed = fl.get("killed") or []
    agg = fl.get("aggregate") or {}
    notes.append(f"fleet: workers={len(fl.get('roster') or [])} "
                 f"killed={len(killed)} jobs={len(jobs)} "
                 f"done={len(done_by)} "
                 f"failed_over={int(g('jobs_failed_over'))} "
                 f"lease_steals={int(g('lease_steals'))} "
                 f"transport_retries={int(g('transport_retries'))} "
                 f"nets_per_s={agg.get('nets_per_s')}")
    return errs, notes


def check_fleet_trace(doc: dict) -> tuple:
    """Fleet-trace rule set over a MERGED trace (trace_merge.py
    output).  Returns (errors, notes).  The rules hold the trace to
    the story the fleet tells:

      * residual clock skew (the spread of each shard's beacon-origin
        estimates) stays under the bound the merge declared — beyond
        it, cross-worker event ordering is untrustworthy and every
        ordering rule below would be noise;
      * every DONE job is one contiguous lifecycle chain: a
        submit/admit origin, at least one slice span, a terminal
        instant, in timeline order (modulo the skew bound);
      * slice spans whose job never reached terminal/reject/shed are
        orphans — work the trace shows starting but never accounts
        for;
      * a job whose slice spans sit on >= 2 worker tracks (a
        failover) must carry the lease-steal or failover instant that
        links the break — without it the chain is visibly
        disconnected in Perfetto and unauditable here;
      * reject/shed verdict instants must name a machine-readable
        code, mirroring the daemon-summary rule at trace level.
    """
    errs, notes = [], []
    meta = doc.get("traceMergeMeta")
    if not isinstance(meta, dict):
        return (["fleet-trace: no traceMergeMeta — not a merged "
                 "fleet trace (run tools/trace_merge.py over the "
                 "worker shards first)"], notes)
    skew = meta.get("residual_skew_ms")
    bound = meta.get("skew_bound_ms")
    if not isinstance(skew, (int, float)):
        errs.append("fleet-trace: traceMergeMeta.residual_skew_ms "
                    "missing — the merge cannot vouch for cross-"
                    "worker ordering")
    elif isinstance(bound, (int, float)) and skew > bound:
        errs.append(f"fleet-trace: residual clock skew {skew}ms "
                    f"exceeds the declared {bound}ms bound — a wall-"
                    f"clock step mid-run; cross-worker ordering is "
                    f"untrustworthy")
    slack_us = (bound if isinstance(bound, (int, float))
                else 250.0) * 1e3

    jobs: dict = {}

    def bucket(jid):
        return jobs.setdefault(jid, {"slices": [], "instants": {},
                                     "steals": 0})

    for e in doc.get("traceEvents", []):
        if not isinstance(e, dict):
            continue
        name = e.get("name")
        jid = (e.get("args") or {}).get("job_id")
        if not isinstance(jid, str) or not jid \
                or not isinstance(name, str):
            continue
        if e.get("ph") == "X" and name == "route.trace.slice":
            bucket(jid)["slices"].append(e)
        elif e.get("ph") == "i":
            if name.startswith("route.trace."):
                kind = name[len("route.trace."):]
                bucket(jid)["instants"].setdefault(kind, []).append(e)
            elif name == "route.fleet.lease.steal":
                bucket(jid)["steals"] += 1
    if not jobs:
        errs.append("fleet-trace: no job-lifecycle events at all — "
                    "tracing was off, or the shards predate the "
                    "lifecycle instrumentation")

    n_done = n_multi = n_linked = 0
    for jid, b in sorted(jobs.items()):
        ins = b["instants"]
        for kind in ("reject", "shed"):
            for e in ins.get(kind, []):
                if not (e.get("args") or {}).get("code"):
                    errs.append(f"fleet-trace: job {jid} {kind} "
                                f"instant carries no machine-readable "
                                f"code — a verdict with no reason")
        closed = any(k in ins for k in ("terminal", "reject", "shed"))
        if b["slices"] and not closed:
            errs.append(f"fleet-trace: job {jid} has "
                        f"{len(b['slices'])} slice span(s) but no "
                        f"terminal/reject/shed instant — an orphaned "
                        f"lifecycle the trace never closes")
        term = ins.get("terminal", [])
        done = any((e.get("args") or {}).get("state") == "done"
                   for e in term)
        if done:
            n_done += 1
            origin = [e.get("ts") for k in ("submit", "admit")
                      for e in ins.get(k, [])
                      if isinstance(e.get("ts"), (int, float))]
            if not origin:
                errs.append(f"fleet-trace: done job {jid} has no "
                            f"submit/admit instant — a chain with no "
                            f"origin")
            if not b["slices"]:
                errs.append(f"fleet-trace: done job {jid} has no "
                            f"slice spans — it finished without ever "
                            f"visibly running")
            else:
                starts = [e["ts"] for e in b["slices"]
                          if isinstance(e.get("ts"), (int, float))]
                ends = [e["ts"] + (e.get("dur") or 0.0)
                        for e in b["slices"]
                        if isinstance(e.get("ts"), (int, float))]
                t_term = max((e.get("ts") for e in term
                              if isinstance(e.get("ts"),
                                            (int, float))),
                             default=None)
                if origin and starts \
                        and min(starts) + slack_us < min(origin):
                    errs.append(f"fleet-trace: done job {jid} sliced "
                                f"before its submit/admit instant "
                                f"(beyond the {bound}ms skew bound) — "
                                f"the chain is out of order")
                if t_term is not None and ends \
                        and max(ends) > t_term + slack_us:
                    errs.append(f"fleet-trace: done job {jid} has a "
                                f"slice span ending after its "
                                f"terminal instant (beyond the "
                                f"{bound}ms skew bound) — the chain "
                                f"is out of order")
        span_pids = {e.get("pid") for e in b["slices"]} - {None}
        if len(span_pids) >= 2:
            n_multi += 1
            if b["steals"] or ins.get("failover"):
                n_linked += 1
            else:
                errs.append(f"fleet-trace: job {jid} sliced on "
                            f"{len(span_pids)} worker tracks with no "
                            f"lease-steal or failover instant linking "
                            f"the break — a disconnected failover "
                            f"chain")
        elif b["steals"] and b["slices"]:
            # the victim died before exporting a slice for this job:
            # the steal is real but only one track shows work — worth
            # eyes, not a failure
            notes.append(f"fleet-trace: job {jid} lease was stolen "
                         f"but all its slices sit on one worker track "
                         f"(victim died before exporting a slice)")
    shards = meta.get("shards") or []
    notes.append(f"fleet-trace: {len(shards)} worker track(s), "
                 f"{len(jobs)} job(s), {n_done} done, {n_multi} "
                 f"cross-worker chain(s) ({n_linked} steal/failover-"
                 f"linked), residual skew {skew}ms "
                 f"(bound {bound}ms)")
    return errs, notes


def _load_slo():
    """obs/slo.py by file path (same pattern as _load_runstore; the
    SLO plane is deliberately stdlib-only so the doctor can gate a
    summary anywhere it lands)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "parallel_eda_tpu", "obs", "slo.py")
    spec = importlib.util.spec_from_file_location(
        "slo", os.path.normpath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_slo(doc: dict) -> tuple:
    """SLO rule set over a daemon or fleet summary JSON (the document
    carries an ``slo`` section — SLOPlane.snapshot for one daemon,
    merge_slo_sections output for a fleet).  Returns (errors, notes).
    The rules hold the published SLO plane to its own arithmetic:

      * every published waterfall satisfies the telescoping identity —
        the integer stage sum (signed ``other`` residual included)
        reconstructs ``e2e_us`` EXACTLY; an off-by-anything waterfall
        means latency attribution silently lies;
      * digests are self-consistent (declared count == bin sum) and
        the e2e digest count equals ``terminal_jobs`` — one sample per
        terminal job, never more, never fewer;
      * on a daemon summary, terminal job rows (done/failed/timeout/
        shed) reconcile with ``terminal_jobs + untracked_terminals``;
      * per tenant, burn > 1.0 and membership in ``breached`` imply
        each other BOTH ways (burn is fraction-over-budget, so breach
        is definitional — disagreement means the publisher fudged one
        side); ``burn_max`` must equal the max over the burn dict;
      * on a fleet summary, the merged digest count equals the sum of
        the per-worker shard counts (the exact bin-wise merge leaves
        no room for drift), and merge errors are failures;
      * the forecast is re-derivable: ``recommended_workers`` and
        ``time_to_drain_s`` recompute exactly from the PUBLISHED
        backlog_s / horizon_s / max_workers / workers_alive.
    """
    errs, notes = [], []
    slo = doc.get("slo") if isinstance(doc, dict) else None
    if not isinstance(slo, dict):
        return (["slo: no slo section (a summary from before the SLO "
                 "plane, or a disabled one)"], notes)
    sl = _load_slo()
    fleet = isinstance(slo.get("shards"), dict)
    terminal = slo.get("terminal_jobs") or 0

    # -- digests: self-consistent, count == terminal jobs
    digests = {}
    for key in ("digest_e2e", "digest_queue_wait"):
        d = slo.get(key)
        if not isinstance(d, dict):
            if d is not None or not fleet:
                errs.append(f"slo: {key} missing/malformed")
            continue
        try:
            digests[key] = sl.QuantileDigest.from_dict(d)
        except (ValueError, TypeError) as e:
            errs.append(f"slo: {key} inconsistent: {e}")
    for key, dig in digests.items():
        if dig.count != terminal:
            errs.append(f"slo: {key} count {dig.count} != "
                        f"terminal_jobs {terminal} — a terminal job "
                        f"was sampled twice or dropped")

    # -- waterfalls: the exact telescoping identity
    wfs = slo.get("waterfalls") or []
    for wf in wfs:
        if not isinstance(wf, dict) or not sl.waterfall_exact(wf):
            jid = wf.get("job_id", "?") if isinstance(wf, dict) else "?"
            stages = wf.get("stages_us") if isinstance(wf, dict) else None
            total = sum(stages.values()) if isinstance(stages, dict) \
                and all(isinstance(v, int) for v in stages.values()) \
                else "?"
            errs.append(f"slo: waterfall {jid}: stage sum {total} != "
                        f"e2e_us {wf.get('e2e_us') if isinstance(wf, dict) else '?'}"
                        f" — latency attribution does not reconstruct "
                        f"the measured end-to-end")

    # -- daemon summary: terminal rows reconcile with the plane
    jobs = doc.get("jobs")
    if not fleet and isinstance(jobs, list) and jobs:
        n_rows = sum(1 for j in jobs if isinstance(j, dict)
                     and j.get("state") in ("done", "failed",
                                            "timeout", "shed"))
        untracked = int(slo.get("untracked_terminals") or 0)
        if terminal + untracked != n_rows:
            errs.append(f"slo: {n_rows} terminal job row(s) but the "
                        f"plane observed {terminal} (+{untracked} "
                        f"untracked) — a terminal transition escaped "
                        f"the SLO plane")
        if untracked:
            notes.append(f"slo: {untracked} untracked terminal(s) — "
                         f"jobs that reached terminal without an "
                         f"admit observation")

    # -- per-tenant burn <-> breach, both directions
    tenants = slo.get("tenants") or {}
    for t, sec in sorted(tenants.items()):
        if not isinstance(sec, dict):
            errs.append(f"slo: tenant {t} section malformed")
            continue
        burn = sec.get("burn")
        breached = set(sec.get("breached") or ())
        if isinstance(burn, dict) and burn:
            for k, v in sorted(burn.items()):
                if v > 1.0 and k not in breached:
                    errs.append(f"slo: tenant {t} objective {k} burn "
                                f"{v} > 1 but not declared breached — "
                                f"the budget is spent and the plane "
                                f"is hiding it")
                if v <= 1.0 and k in breached:
                    errs.append(f"slo: tenant {t} objective {k} "
                                f"declared breached at burn {v} <= 1 "
                                f"— a false alarm is still an "
                                f"inconsistent publisher")
            bm = sec.get("burn_max")
            if bm != max(burn.values()):
                errs.append(f"slo: tenant {t} burn_max {bm} != "
                            f"max(burn) {max(burn.values())}")
        else:
            # merged fleet sections carry worst-per-worker burn_max +
            # the breached union, not the raw burn dict: the two must
            # still imply each other across the > 1 boundary
            bm = float(sec.get("burn_max") or 0.0)
            if bm > 1.0 and not breached:
                errs.append(f"slo: tenant {t} worst burn {bm} > 1 "
                            f"with an empty breached set")
            if breached and bm <= 1.0:
                errs.append(f"slo: tenant {t} breached "
                            f"{sorted(breached)} at worst burn {bm} "
                            f"<= 1")

    # -- fleet merge: exactness + surfaced merge errors
    if fleet:
        shards = slo["shards"]
        tot = sum(int(v) for v in shards.values())
        if tot != terminal:
            errs.append(f"slo: merged terminal_jobs {terminal} != "
                        f"sum of worker shards {tot} ({shards}) — "
                        f"the bin-wise merge lost or invented samples")
        dig = digests.get("digest_e2e")
        if dig is not None and dig.count != tot:
            errs.append(f"slo: merged e2e digest count {dig.count} "
                        f"!= shard sum {tot}")
        merrs = slo.get("errors")
        if isinstance(merrs, dict):
            for k, v in sorted(merrs.items()):
                errs.append(f"slo: merge error [{k}]: {v}")

    # -- forecast: re-derive the recommendation from published inputs
    fc = slo.get("forecast")
    if isinstance(fc, dict):
        try:
            backlog_s = float(fc["backlog_s"])
            horizon = float(fc["horizon_s"])
            cap = int(fc["max_workers"])
            alive = max(1, int(fc.get("workers_alive") or 1))
            rec = fc["recommended_workers"]
            ttd = float(fc["time_to_drain_s"])
        except (KeyError, TypeError, ValueError) as e:
            errs.append(f"slo: forecast missing/malformed input: {e}")
        else:
            want = sl.recommended_workers(backlog_s, horizon, cap)
            if rec != want:
                errs.append(f"slo: recommended_workers {rec} != {want} "
                            f"re-derived from published backlog_s="
                            f"{backlog_s} horizon_s={horizon} "
                            f"max_workers={cap}")
            if ttd < 0 or backlog_s < 0:
                errs.append(f"slo: negative forecast (backlog_s="
                            f"{backlog_s}, time_to_drain_s={ttd})")
            elif round(backlog_s / alive, 6) != round(ttd, 6):
                errs.append(f"slo: time_to_drain_s {ttd} != backlog_s/"
                            f"workers_alive {round(backlog_s / alive, 6)}")

    breaches = sum(len(s.get("breached") or ()) for s in
                   tenants.values() if isinstance(s, dict))
    notes.append(
        f"slo: {'fleet' if fleet else 'daemon'} section, "
        f"{terminal} terminal job(s), {len(wfs)} waterfall(s), "
        f"{len(tenants)} tenant(s), {breaches} breached objective(s)"
        + (f", recommended_workers="
           f"{fc.get('recommended_workers')}" if isinstance(fc, dict)
           else ""))
    return errs, notes


def check_lint(root=None):
    """Run the graft-lint static rule set (parallel_eda_tpu/analysis —
    stdlib-only like this tool) over the source tree.  Every live
    finding is an error; suppressed/baselined counts land in notes."""
    errs, notes = [], []
    repo = root or os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    if not os.path.isdir(os.path.join(repo, "parallel_eda_tpu",
                                      "analysis")):
        return ([f"lint: no analysis package under {repo} — pass "
                 f"--lint-root pointing at the repo checkout"], notes)
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from parallel_eda_tpu.analysis import lint_tree
    result = lint_tree(repo)
    for f in result.findings:
        errs.append(f"lint: {f.path}:{f.line}: [{f.rule}] {f.message}")
    for e in result.baseline_errors:
        errs.append(f"lint: {e}")
    notes.append(
        f"lint: {len(result.rules_run)} rules over {repo}: "
        f"{len(result.findings)} findings, "
        f"{len(result.suppressed)} suppressed, "
        f"{len(result.baselined)} baselined")
    for e in result.unused_baseline:
        notes.append(f"lint: stale baseline entry {e.get('rule')}:"
                     f"{e.get('path')}:{e.get('key')}")
    return errs, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", help="Chrome trace-event JSON to gate")
    ap.add_argument("--metrics", help="metrics JSON (MetricsRegistry "
                                      "dump) to gate")
    ap.add_argument("--devprof", help="devprof.json (obs/devprof "
                                      "ledger) to gate")
    ap.add_argument("--row", help="fresh bench row (BENCH_*.json or "
                                  "bare row JSON) to gate against the "
                                  "previous one")
    ap.add_argument("--against", help="explicit previous row for --row "
                                      "(default: latest other "
                                      "BENCH_*.json in --bench-dir)")
    ap.add_argument("--bench-dir", default=".",
                    help="where BENCH_*.json history lives")
    ap.add_argument("--nets-tol", type=float, default=NETS_PER_SEC_TOL,
                    help="allowed fractional drop in the row's metric "
                         "of record (default %(default)s)")
    ap.add_argument("--corpus", action="store_true",
                    help="gate the freshest corpus row of each "
                         "scenario against its per-scenario "
                         "trajectory (runs/<scenario>.jsonl)")
    ap.add_argument("--runs-dir", default="runs",
                    help="corpus directory for --corpus "
                         "(default %(default)s)")
    ap.add_argument("--scenario",
                    help="restrict --corpus to one scenario "
                         "(default: all)")
    ap.add_argument("--corpus-k", type=int, default=5,
                    help="trajectory window: median of the last K "
                         "same-backend rows (default %(default)s)")
    ap.add_argument("--serve-summary", dest="serve_summary",
                    help="serve CLI summary JSON to gate with the "
                         "resil rule set (quarantine provenance, "
                         "retry bounds, failure diagnosability)")
    ap.add_argument("--warm", action="store_true",
                    help="with --serve-summary/--daemon-summary: "
                         "assert zero window-program compiles "
                         "(dispatch_compiles==0): the warm "
                         "library's acceptance gate")
    ap.add_argument("--daemon-summary", dest="daemon_summary",
                    help="route daemon summary JSON to gate with the "
                         "daemon rule set (rejection reasons, shed "
                         "causes vs measured overload, heartbeat "
                         "gaps, recovery provenance)")
    ap.add_argument("--fleet-summary", dest="fleet_summary",
                    help="fleet summary JSON (daemon fleet --summary) "
                         "to gate with the fleet rule set (failover "
                         "implies lease expiry, transport retries "
                         "bounded, no orphaned leases, exactly-once "
                         "completion, worker attribution)")
    ap.add_argument("--fleet-trace", dest="fleet_trace",
                    help="MERGED fleet trace JSON (trace_merge.py "
                         "output) to gate with the fleet-trace rule "
                         "set (skew bound, contiguous per-job "
                         "lifecycle chains, steal-linked failovers, "
                         "no orphaned slice spans, coded verdicts)")
    ap.add_argument("--slo", dest="slo",
                    help="daemon or fleet summary JSON to gate with "
                         "the SLO rule set (exact waterfall stage "
                         "sums, digest count == terminal jobs, "
                         "burn > 1 <-> breached both ways, merged "
                         "digest == sum of worker shards, forecast "
                         "re-derivable from its published inputs)")
    ap.add_argument("--lint", action="store_true",
                    help="run the graft-lint static rule set over the "
                         "source tree (donation safety, signature "
                         "drift, determinism, durable writes, metric "
                         "registry); any live finding is UNHEALTHY")
    ap.add_argument("--lint-root",
                    help="repo root for --lint (default: this "
                         "checkout)")
    args = ap.parse_args(argv)

    if not any((args.trace, args.metrics, args.devprof, args.row,
                args.corpus, args.serve_summary, args.daemon_summary,
                args.fleet_summary, args.fleet_trace, args.slo,
                args.lint)):
        ap.error("nothing to check: give at least one of --trace / "
                 "--metrics / --devprof / --row / --corpus / "
                 "--serve-summary / --daemon-summary / "
                 "--fleet-summary / --fleet-trace / --slo / --lint")

    errs, notes = [], []
    try:
        if args.trace:
            errs += [f"trace: {e}" for e in check_trace(args.trace)]
            notes.append(f"trace: checked {args.trace}")
        if args.metrics:
            errs += [f"metrics: {e}" for e in check_metrics(args.metrics)]
            notes.append(f"metrics: checked {args.metrics}")
        if args.devprof:
            de, dn = check_devprof(args.devprof)
            errs += [f"devprof: {e}" for e in de]
            notes += dn
        if args.row:
            fresh = _row_of(_read_json(args.row))
            if fresh is None:
                errs.append(f"row: {args.row} is not a bench row")
            else:
                prev_path = args.against
                if prev_path is None:
                    hist = latest_bench_rows(args.bench_dir,
                                             exclude=args.row)
                    prev_path = hist[-1] if hist else None
                if prev_path is None:
                    notes.append("row: no previous BENCH_*.json to "
                                 "compare against; gates skipped")
                else:
                    prev = _row_of(_read_json(prev_path))
                    if prev is None:
                        errs.append(f"row: previous {prev_path} is not "
                                    f"a bench row")
                    else:
                        fb, pb = _row_backend(fresh), _row_backend(prev)
                        if fb and pb and fb != pb:
                            # cross-backend rows are not comparable
                            # (the r04/r05 lesson): warn, don't gate
                            notes.append(
                                f"row: WARNING backends differ (fresh "
                                f"{fb} vs previous {pb}); comparison "
                                f"skipped — cross-backend rows are "
                                f"not a trajectory")
                        else:
                            re_, rn = check_row(fresh, prev,
                                                args.nets_tol)
                            errs += [f"row: {e}" for e in re_]
                            notes += [
                                f"row[{os.path.basename(prev_path)}]"
                                f": {n}" for n in rn]
        if args.corpus:
            ce, cn = check_corpus(args.runs_dir, args.scenario,
                                  args.nets_tol, args.corpus_k)
            errs += ce
            notes += cn
        if args.serve_summary:
            sdoc = _read_json(args.serve_summary)
            se, sn = check_resil(sdoc)
            errs += se
            notes += sn
            if args.warm:
                we, wn = check_warm(sdoc)
                errs += we
                notes += wn
        if args.daemon_summary:
            ddoc = _read_json(args.daemon_summary)
            de, dn = check_daemon(ddoc)
            errs += de
            notes += dn
            if args.warm:
                we, wn = check_warm(ddoc)
                errs += we
                notes += wn
        if args.fleet_summary:
            fe, fn = check_fleet(_read_json(args.fleet_summary))
            errs += fe
            notes += fn
        if args.fleet_trace:
            te, tn = check_fleet_trace(_read_json(args.fleet_trace))
            errs += te
            notes += tn
        if args.slo:
            se, sn = check_slo(_read_json(args.slo))
            errs += se
            notes += sn
        if args.lint:
            le, ln = check_lint(args.lint_root)
            errs += le
            notes += ln
    except (OSError, json.JSONDecodeError) as e:
        print(f"flow doctor: cannot read artifact: {e}",
              file=sys.stderr)
        return 2

    for n in notes:
        print(f"  {n}")
    if errs:
        print(f"UNHEALTHY: {len(errs)} problem(s)", file=sys.stderr)
        for e in errs:
            print(f"  {e}", file=sys.stderr)
        return 1
    print("HEALTHY")
    return 0


if __name__ == "__main__":
    sys.exit(main())
