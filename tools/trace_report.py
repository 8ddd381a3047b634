#!/usr/bin/env python3
"""Summarize (or validate) a Chrome trace-event JSON written by
``python -m parallel_eda_tpu --trace out.json`` (obs.trace.Tracer).

Stdlib-only on purpose — it must run anywhere the trace file lands
(laptop, CI) without jax or the repo on the path.

    python tools/trace_report.py out.json          # human summary
    python tools/trace_report.py out.json --check  # validate, exit != 0
                                                   # on a malformed trace

The summary shows the flow stages (pack / place / route / ...), the
per-route-iteration trajectory (wall time, overused nodes, pres_fac),
and the compile-vs-execute split reconstructed from the cat="jax.compile"
spans the tracer captures off jax.monitoring.
"""

from __future__ import annotations

import argparse
import json
import sys

REQUIRED_X_FIELDS = ("name", "ph", "ts", "pid", "tid")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def validate(doc) -> list:
    """Return a list of problems (empty = valid Chrome trace JSON in the
    shape the tracer emits)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"top level is {type(doc).__name__}, expected object"]
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing/non-list 'traceEvents'"]
    if not evs:
        errs.append("'traceEvents' is empty")
    open_begins = {}  # (pid, tid) -> stack depth, for B/E pairing
    last_ts = None
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            errs.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph is None:
            errs.append(f"event {i}: missing 'ph'")
            continue
        if ph == "M":
            if "name" not in ev:
                errs.append(f"event {i}: metadata event without name")
            continue
        for field in REQUIRED_X_FIELDS:
            if field not in ev:
                errs.append(f"event {i} ({ph}): missing '{field}'")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"event {i}: bad ts {ts!r}")
            continue
        if last_ts is not None and ts < last_ts:
            errs.append(f"event {i}: ts {ts} < previous {last_ts} "
                        f"(events must be sorted)")
        last_ts = ts
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"event {i}: X event with bad dur {dur!r}")
        elif ph == "B":
            key = (ev.get("pid"), ev.get("tid"))
            open_begins[key] = open_begins.get(key, 0) + 1
        elif ph == "E":
            key = (ev.get("pid"), ev.get("tid"))
            if open_begins.get(key, 0) <= 0:
                errs.append(f"event {i}: E without matching B on {key}")
            else:
                open_begins[key] -= 1
        elif ph in ("s", "t", "f"):
            # flow events (trace_merge connects a job's spans across
            # worker tracks); the id is what ties one flow together
            if "id" not in ev:
                errs.append(f"event {i}: flow event ({ph}) without 'id'")
        elif ph not in ("i", "I", "C"):
            errs.append(f"event {i}: unsupported phase {ph!r}")
    for key, depth in open_begins.items():
        if depth:
            errs.append(f"{depth} unclosed B event(s) on {key}")
    return errs


def _xs(doc):
    return [e for e in doc.get("traceEvents", [])
            if isinstance(e, dict) and e.get("ph") == "X"]


def _merged(intervals):
    """Merge [t0, t1) intervals (any order) into a sorted disjoint set."""
    out = []
    for b0, b1 in sorted(intervals):
        if out and b0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b1)
        else:
            out.append([b0, b1])
    return out


def _overlap_us(a0, a1, merged):
    tot = 0.0
    for b0, b1 in merged:
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            tot += hi - lo
    return tot


def pipeline_overlap(doc):
    """Host-plan vs device-exec overlap of the async negotiation
    pipeline: how much of the route.pipeline.plan span time (window
    planning + staged uploads + deferred summary bookkeeping) ran while
    a route.pipeline.exec span (device window in flight) was open.

    Returns None when the trace has no pipeline spans (pre-pipeline
    trace, or a flow that never routed)."""
    evs = _xs(doc)
    plans = [e for e in evs if e.get("name") == "route.pipeline.plan"]
    execs = [e for e in evs if e.get("name") == "route.pipeline.exec"]
    if not plans or not execs:
        return None

    def span_of(e):
        return (e["ts"], e["ts"] + e.get("dur", 0.0))

    # one trace can hold BOTH modes (e.g. the placer's delay-lookup
    # route runs with the default pipelined driver even in a --sync
    # flow), so the invariants are judged per exec-span mode
    p_execs = [e for e in execs if e.get("args", {}).get("pipelined")]
    s_execs = [e for e in execs if not e.get("args", {}).get("pipelined")]
    p_merged = _merged([span_of(e) for e in p_execs])
    s_merged = _merged([span_of(e) for e in s_execs])
    plan_us = sum(e.get("dur", 0.0) for e in plans)
    ov_p = sum(_overlap_us(*span_of(e), p_merged) for e in plans)
    ov_s = sum(_overlap_us(*span_of(e), s_merged) for e in plans)
    # window args are per-route 1-based indices: any pipelined exec
    # span with window >= 2 proves some route ran >= 2 pipelined
    # windows (the shape where overlap is structurally possible and
    # thus required)
    multi = any((e.get("args", {}).get("window") or 0) >= 2
                for e in p_execs)
    windows = {e.get("args", {}).get("window") for e in execs}
    return {"plan_spans": len(plans), "exec_spans": len(execs),
            "windows": len(windows), "pipelined": bool(p_execs),
            "multi_window_pipelined": multi,
            "plan_us": plan_us, "overlap_us": ov_p + ov_s,
            "pipelined_overlap_us": ov_p, "sync_overlap_us": ov_s,
            "overlap_frac": ((ov_p + ov_s) / plan_us) if plan_us
            else 0.0}


def check_pipeline(doc) -> list:
    """Pipeline-shape invariants for --check (judged per exec-span
    mode, since one trace can mix both drivers):

    - some route ran >= 2 pipelined windows (a pipelined exec span
      with window >= 1 exists): plan-span time MUST overlap pipelined
      exec spans — the whole point of the async pipeline; zero overlap
      means the driver silently serialized (e.g. a hidden blocking
      sync).
    - plan spans must NEVER overlap --sync (pipelined=false) exec
      spans — the escape hatch drains every dispatch before further
      host work by construction.
    """
    ov = pipeline_overlap(doc)
    if ov is None:
        return []
    errs = []
    if ov["multi_window_pipelined"] and ov["pipelined_overlap_us"] <= 0.0:
        errs.append(
            "pipelined route (>= 2 windows) with ZERO plan/exec "
            "overlap: the async pipeline is serialized")
    # 1us epsilon: a plan span ending at the same perf_counter instant
    # an exec span begins can round into a sub-nanosecond sliver (the
    # two us conversions differ in float arithmetic); a genuine leak is
    # host work measured in milliseconds
    if ov["sync_overlap_us"] > 1.0:
        errs.append(
            f"{ov['sync_overlap_us'] / 1e3:.3f}ms of plan spans overlap "
            f"--sync exec spans (the escape hatch drains every dispatch "
            f"before further host work; overlap there means it leaked)")
    return errs


def _lifecycle(doc):
    return [e for e in doc.get("traceEvents", [])
            if isinstance(e, dict) and e.get("cat") == "lifecycle"]


def lifecycle_coverage(doc):
    """Lifecycle-chain coverage: of the jobs that reached a terminal
    instant (route.trace.terminal), how many carry a complete chain —
    an origin instant (route.trace.submit or route.trace.admit, the
    two ways work enters a daemon) under the SAME job_id.

    Returns None when the trace declares no lifecycle tracking (no
    cat="lifecycle" event at all: a plain flow trace, not a serve
    run).  Otherwise a dict with terminal/complete counts, coverage
    in [0, 1], and the orphaned job_ids (terminal but origin-less)."""
    evs = _lifecycle(doc)
    if not evs:
        return None

    def _jid(e):
        a = e.get("args")
        return a.get("job_id") if isinstance(a, dict) else None

    origins, terminals = set(), set()
    for e in evs:
        jid = _jid(e)
        if jid is None:
            continue
        name = e.get("name")
        if name in ("route.trace.submit", "route.trace.admit"):
            origins.add(jid)
        elif name == "route.trace.terminal":
            terminals.add(jid)
    orphans = sorted(str(j) for j in terminals - origins)
    n_term = len(terminals)
    return {"terminal_jobs": n_term,
            "complete_chains": n_term - len(orphans),
            "coverage": ((n_term - len(orphans)) / n_term)
            if n_term else 1.0,
            "orphans": orphans}


def check_lifecycle(doc) -> list:
    """Lifecycle-coverage invariant for --check: a trace that declares
    lifecycle tracking (any cat="lifecycle" event) must show coverage
    == 1.0 — every job with a terminal instant also carries its
    submit/admit origin.  An orphaned terminal means the chain was
    torn (a dropped submit instant, a trace started mid-run, or a
    merge that lost a worker's shard) and per-job latency attribution
    silently undercounts."""
    cov = lifecycle_coverage(doc)
    if cov is None or cov["coverage"] >= 1.0:
        return []
    head = ", ".join(cov["orphans"][:5])
    more = "" if len(cov["orphans"]) <= 5 else \
        f" (+{len(cov['orphans']) - 5} more)"
    return [
        f"lifecycle coverage {cov['coverage']:.3f} < 1.0: "
        f"{len(cov['orphans'])} of {cov['terminal_jobs']} terminal "
        f"job(s) have no submit/admit origin instant: {head}{more}"]


def _counters(doc):
    return [e for e in doc.get("traceEvents", [])
            if isinstance(e, dict) and e.get("ph") == "C"]


def _pid_names(doc):
    """pid -> process_name from "M" metadata (one per worker in a
    merged fleet trace)."""
    out = {}
    for e in doc.get("traceEvents", []):
        if isinstance(e, dict) and e.get("ph") == "M" \
                and e.get("name") == "process_name":
            name = (e.get("args") or {}).get("name")
            if isinstance(name, str):
                out[e.get("pid")] = name
    return out


def check_counters(doc) -> list:
    """Counter-track ("C" event) invariants for --check:

    - args.value must be a plain number (Perfetto drops non-numeric
      counter samples silently; we fail loudly instead).
    - samples share the span clock origin: ts must sit inside the
      [0, last span end + slack] envelope of the X events.  A counter
      stamped from a different perf_counter origin lands far outside
      and would render as a detached track.
    - per-track ts must be non-decreasing — counters are appended from
      metrics snapshots in wall order; a regression means two tracers'
      events were merged or the clock origin moved mid-run.  Tracks
      are keyed per (pid, name): a merged fleet trace carries one
      track per worker process, each independently monotone.
    """
    cs = _counters(doc)
    if not cs:
        return []
    errs = []
    span_end = max((e["ts"] + e.get("dur", 0.0) for e in _xs(doc)),
                   default=None)
    envelope = None if span_end is None else span_end + 1e4  # 10ms slack
    last_by_name = {}
    for i, ev in enumerate(cs):
        name = ev.get("name", "?")
        v = ev.get("args", {}).get("value") \
            if isinstance(ev.get("args"), dict) else None
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            errs.append(f"counter '{name}': non-numeric value {v!r}")
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            continue  # validate() already flags the bad ts
        if ts < 0 or (envelope is not None and ts > envelope):
            errs.append(
                f"counter '{name}': ts {ts:.0f}us outside the span "
                f"clock envelope [0, {envelope:.0f}]us — sample is off "
                f"the tracer's clock origin")
        key = (ev.get("pid"), name)
        prev = last_by_name.get(key)
        if prev is not None and ts < prev:
            errs.append(f"counter '{name}' (pid {ev.get('pid')}): ts "
                        f"{ts:.0f}us < previous sample {prev:.0f}us "
                        f"(track not monotone)")
        last_by_name[key] = ts
    return errs


def summarize(doc) -> str:
    evs = _xs(doc)
    lines = []
    us = 1e6

    stages = [e for e in evs if e.get("cat") == "stage"]
    if stages:
        lines.append("flow stages:")
        for e in stages:
            args = e.get("args", {})
            extra = "".join(f" {k}={v}" for k, v in sorted(args.items()))
            lines.append(f"  {e['name']:<14} {e['dur'] / us:8.3f}s{extra}")

    iters = [e for e in evs if e.get("name") == "route.iter"]
    if iters:
        lines.append(f"route iterations: {len(iters)}")
        lines.append("  iter    wall_s  overused  pres_fac")
        for e in iters:
            a = e.get("args", {})
            approx = " ~" if a.get("approx") else ""
            lines.append(f"  {a.get('it', '?'):>4}  {e['dur'] / us:8.3f}"
                         f"  {a.get('overused', '?'):>8}"
                         f"  {a.get('pres_fac', '?'):>8}{approx}")
        if any(e.get("args", {}).get("approx") for e in iters):
            lines.append("  (~ = iteration inside a fused K>1 device "
                         "window; wall time evenly attributed)")

    windows = [e for e in evs if e.get("name") == "route.window"]
    if windows:
        # a K>1 window is one device dispatch: its iterations have no
        # spans of their own, the window carries first/last
        lines.append(f"route windows: {len(windows)}")
        lines.append("  window      iters    wall_s  overused")
        for e in windows:
            a = e.get("args", {})
            its = f"{a.get('first_iter', '?')}-{a.get('last_iter', '?')}"
            lines.append(f"  {a.get('window', '?'):>6}  {its:>9}"
                         f"  {e['dur'] / us:8.3f}"
                         f"  {a.get('overused_nodes', '?'):>8}")
    w_tot = sum(e.get("args", {}).get("relax_steps", 0)
                for e in windows)
    w_use = sum(e.get("args", {}).get("relax_steps_useful", 0)
                for e in windows)
    w_was = sum(e.get("args", {}).get("relax_steps_wasted", 0)
                for e in windows)
    if w_tot and (w_use or w_was):
        lines.append(f"relax-sweep ledger: {w_tot} executed = "
                     f"{w_use} useful + {w_was} wasted "
                     f"({w_was / w_tot:.1%} wasted)")

    kernels = [e for e in evs if e.get("name") == "route.kernel"]
    if kernels:
        occs = [e["args"]["lane_occupancy"] for e in kernels
                if isinstance(e.get("args", {}).get("lane_occupancy"),
                              (int, float))]
        variants = sorted({e.get("args", {}).get("variant", "?")
                           for e in kernels})
        line = (f"kernel layout: {len(kernels)} window plan(s), "
                f"variants {'/'.join(variants)}")
        if occs:
            line += (f", lane occupancy {min(occs):.3f}"
                     f"..{max(occs):.3f} "
                     f"(mean {sum(occs) / len(occs):.3f})")
        lines.append(line)

    ov = pipeline_overlap(doc)
    if ov is not None:
        mode = "async" if ov["pipelined"] else "sync"
        lines.append(
            f"pipeline overlap [{mode}]: {ov['overlap_us'] / us:.3f}s "
            f"of {ov['plan_us'] / us:.3f}s host plan time ran under "
            f"device exec spans ({ov['overlap_frac']:.1%}; "
            f"{ov['windows']} windows, {ov['exec_spans']} exec / "
            f"{ov['plan_spans']} plan spans)")

    cov = lifecycle_coverage(doc)
    if cov is not None:
        orphan = "" if not cov["orphans"] else \
            f" ({len(cov['orphans'])} orphaned)"
        lines.append(
            f"lifecycle coverage: {cov['complete_chains']}/"
            f"{cov['terminal_jobs']} terminal job(s) with a complete "
            f"submit->terminal chain ({cov['coverage']:.1%}){orphan}")

    cs = _counters(doc)
    declared = doc.get("declaredCounterTracks")
    if cs:
        by_pid = {}
        for e in cs:
            v = e.get("args", {}).get("value")
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                by_pid.setdefault(e.get("pid"), {}) \
                    .setdefault(e.get("name", "?"), []).append(v)
        pid_names = _pid_names(doc)
        # a merged fleet trace has one process (pid) per worker: group
        # the tracks per worker so same-named counters don't interleave
        for pid in sorted(by_pid, key=lambda p: (str(type(p)), str(p))):
            by_name = by_pid[pid]
            n_samp = sum(len(vs) for vs in by_name.values())
            who = f" [{pid_names.get(pid, f'pid {pid}')}]" \
                if len(by_pid) > 1 else ""
            parts = [f"{n} [{min(vs):g}..{max(vs):g}] x{len(vs)}"
                     for n, vs in sorted(by_name.items())]
            lines.append(f"counter tracks{who}: {len(by_name)} "
                         f"track(s), {n_samp} samples: "
                         + ", ".join(parts))
    if isinstance(declared, list) and declared:
        sampled = set()
        for e in cs:
            sampled.add(e.get("name"))
        empty = sorted(str(n) for n in declared if n not in sampled)
        if empty:
            # declared-but-unsampled is informational, not an error:
            # the counter simply never moved during this run
            lines.append(f"  note: {len(empty)} declared counter "
                         f"track(s) with no samples (empty track): "
                         + ", ".join(empty))

    compile_us = sum(e["dur"] for e in evs
                     if e.get("cat") == "jax.compile")
    total_us = max((e["ts"] + e["dur"] for e in evs), default=0)
    lines.append(f"compile vs execute: {compile_us / us:.3f}s jax "
                 f"compile / {max(0.0, total_us - compile_us) / us:.3f}s "
                 f"everything else ({total_us / us:.3f}s total)")

    by_cat = {}
    for e in evs:
        by_cat.setdefault(e.get("cat", "?"), [0, 0.0])
        by_cat[e.get("cat", "?")][0] += 1
        by_cat[e.get("cat", "?")][1] += e["dur"] / us
    lines.append("span totals by category:")
    for cat in sorted(by_cat):
        n, s = by_cat[cat]
        lines.append(f"  {cat:<12} {n:>5} spans  {s:8.3f}s")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace-event JSON file")
    ap.add_argument("--check", action="store_true",
                    help="validate only; exit nonzero if malformed")
    args = ap.parse_args(argv)

    try:
        doc = load(args.trace)
    except (OSError, json.JSONDecodeError) as e:
        print(f"MALFORMED: {e}", file=sys.stderr)
        return 2

    errs = (validate(doc) + check_pipeline(doc) + check_counters(doc)
            + check_lifecycle(doc))
    if args.check:
        if errs:
            print("MALFORMED trace:", file=sys.stderr)
            for e in errs[:20]:
                print(f"  {e}", file=sys.stderr)
            return 1
        print(f"OK: {len(doc['traceEvents'])} events")
        return 0

    if errs:
        print(f"warning: {len(errs)} validation problem(s); "
              f"run with --check for details", file=sys.stderr)
    print(summarize(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
