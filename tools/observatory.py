#!/usr/bin/env python3
"""Observatory: the analysis layer over the scenario-keyed run corpus
(runs/<scenario>.jsonl, written by bench.py / scale_bench.py via
obs/runstore.py).

Answers the questions no single-run tool can:

    python tools/observatory.py report [--scenario S] [--last K]
        per-scenario trend tables across runs, plus a stage-level
        REGRESSION ATTRIBUTION of the nets/s delta between the two
        most recent same-backend rows: the delta is decomposed into
        negotiation length (net routes + useful sweeps), wasted relax
        sweeps, per-sweep kernel cost, compile time, pipeline stall,
        and residual host time — stages sum to the total delta exactly
        (telescoping substitution), so a flow_doctor failure can say
        WHICH stage regressed, not just "-12%".

    python tools/observatory.py --import-legacy [--bench-dir .]
        one-shot migration of the pre-corpus BENCH_r0*.json /
        MULTICHIP_r0*.json rows, tagged pre_pr2=true so trend reports
        stop mixing eras.  Idempotent (keyed on tags.legacy_file).

    python tools/observatory.py --export-congestion [--out F] [--bins N]
        emit the accumulated congestion-heatmap corpus (per-window
        overuse points + per-run rasters) — the training substrate for
        the ROADMAP's congestion-predictive planner (RoutePlacer,
        arXiv:2406.02651).

Stdlib-only like its tool siblings: loads obs/runstore.py by file path,
so it runs anywhere the corpus lands, without jax or the repo on
sys.path.  Exit codes: 0 ok, 2 usage or unreadable artifact.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import statistics
import sys

# the attribution's waterfall order: each stage substitutes the "after"
# row's parameters for these keys, and its contribution is the rate
# change that substitution causes.  Telescoping makes the stage sum
# EXACTLY the total modeled delta, whatever the order; the order below
# puts workload terms before cost-rate terms so each reads naturally.
ATTRIBUTION_STAGES = (
    ("iterations", ("net_routes", "useful_sweeps"),
     "negotiation length (net routes + useful sweeps)"),
    ("wasted_sweeps", ("wasted_sweeps",), "wasted relax sweeps"),
    ("kernel_per_sweep", ("per_sweep_s",), "per-sweep kernel cost"),
    ("compile", ("compile_s",), "compile time (measured route)"),
    ("stall", ("stall_s",), "pipeline stall"),
    ("other_host", ("other_s",), "other host-serialized time"),
)


def load_runstore():
    """obs/runstore.py by file path (tools/ is not a package and the
    repo may not be importable where the corpus lives)."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "parallel_eda_tpu", "obs",
                        "runstore.py")
    spec = importlib.util.spec_from_file_location(
        "runstore", os.path.normpath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- regression attribution ----------------------------------------

def stage_params(rec: dict):
    """Decompose one corpus record into the attribution's wall-time
    model:

        T = compile_s + stall_s + (useful + wasted) * per_sweep_s
            + other_s          (other_s defined as the exact residual)
        rate = net_routes / T

    so rate reconstructs the recorded nets/s and every parameter is a
    nameable stage.  Rows missing riders (older eras) degrade: absent
    ledger -> all sweeps useful, absent pipeline -> sweep cost from the
    non-compile wall.  Returns None when not even (net routes, wall)
    can be recovered."""
    det = rec.get("detail") or {}
    value = rec.get("value")
    n = det.get("total_net_routes")
    T = det.get("route_time_s")
    if not n or not T:
        if n and isinstance(value, (int, float)) and value > 0:
            T = n / value
        elif T and isinstance(value, (int, float)):
            n = value * T
        else:
            return None
    led = det.get("ledger") or {}
    useful = led.get("relax_steps_useful")
    wasted = led.get("relax_steps_wasted") or 0
    if useful is None:
        useful = det.get("total_relax_steps") or 0
    steps = useful + wasted
    obs = det.get("obs") or {}
    compile_s = obs.get("compile_s_measured") or 0.0
    pl = det.get("pipeline") or {}
    stall_s = (pl.get("stall_ms") or 0.0) / 1e3
    exec_ms = pl.get("exec_ms")
    if isinstance(exec_ms, (int, float)) and exec_ms > 0 and steps:
        per_sweep = exec_ms / 1e3 / steps
    elif steps:
        per_sweep = max(0.0, T - compile_s - stall_s) / steps
    else:
        per_sweep = 0.0
    other = T - (compile_s + stall_s + steps * per_sweep)
    return {"net_routes": float(n), "useful_sweeps": float(useful),
            "wasted_sweeps": float(wasted),
            "per_sweep_s": float(per_sweep),
            "compile_s": float(compile_s), "stall_s": float(stall_s),
            "other_s": float(other)}


def model_rate(p: dict) -> float:
    T = (p["compile_s"] + p["stall_s"] + p["other_s"]
         + (p["useful_sweeps"] + p["wasted_sweeps"]) * p["per_sweep_s"])
    return p["net_routes"] / T if T > 0 else 0.0


def attribute(rec_a: dict, rec_b: dict):
    """Stage-level attribution of the nets/s delta between record A
    (before) and B (after).  Returns None when either row lacks the
    fields to model; otherwise a dict whose stages sum EXACTLY to
    rate(B) - rate(A) by telescoping."""
    pa, pb = stage_params(rec_a), stage_params(rec_b)
    if pa is None or pb is None:
        return None
    cur = dict(pa)
    rate_before = prev = model_rate(cur)
    stages = []
    for name, keys, desc in ATTRIBUTION_STAGES:
        for k in keys:
            cur[k] = pb[k]
        r = model_rate(cur)
        stages.append({"stage": name, "desc": desc,
                       "delta": r - prev,
                       "before": {k: pa[k] for k in keys},
                       "after": {k: pb[k] for k in keys}})
        prev = r
    va, vb = rec_a.get("value"), rec_b.get("value")
    measured = (vb - va
                if isinstance(va, (int, float))
                and isinstance(vb, (int, float)) else None)
    return {"rate_before": rate_before, "rate_after": prev,
            "total_delta": prev - rate_before, "stages": stages,
            "measured_delta": measured}


def pick_attribution_pair(records: list):
    """The two most recent same-backend rows of a scenario (the most
    recent row's backend decides the side).  Pre-era imports are
    excluded unless they are all there is.  Returns (A, B) oldest
    first, or None."""
    recs = [r for r in records
            if not (r.get("tags") or {}).get("pre_pr2")]
    if len(recs) < 2:
        recs = records
    if len(recs) < 2:
        return None
    latest = recs[-1]
    for prev in reversed(recs[:-1]):
        if prev.get("backend") == latest.get("backend"):
            return prev, latest
    return None


# ---- report --------------------------------------------------------

def _fmt(v, width=0):
    if v is None:
        s = "-"
    elif isinstance(v, float):
        s = f"{v:+.2f}" if width < 0 else f"{v:.2f}"
    else:
        s = str(v)
    return s


def print_report(rs, runs_dir: str, scenario=None, last: int = 10,
                 out=sys.stdout) -> int:
    names = [scenario] if scenario else rs.scenarios(runs_dir)
    if not names:
        print(f"observatory: no scenarios under {runs_dir}/",
              file=sys.stderr)
        return 2
    shown = 0
    for name in names:
        recs = rs.read_runs(runs_dir, name)
        if not recs:
            continue
        shown += 1
        print(f"\n## {name}  ({len(recs)} run(s))", file=out)
        # multi-tenant scenarios (schema v2 route-service rows) trend
        # per tenant — one table per tenant so a noisy neighbour's rows
        # don't interleave into another tenant's trajectory; scenarios
        # with no tenant field keep the flat single table
        if any(r.get("tenant") for r in recs):
            by_tenant = {}
            for r in recs:
                by_tenant.setdefault(r.get("tenant") or "-",
                                     []).append(r)
            groups = sorted(by_tenant.items())
        else:
            groups = [(None, recs)]
        for tenant, grecs in groups:
            if tenant is not None:
                print(f"\n### tenant {tenant}  ({len(grecs)} run(s))",
                      file=out)
            jobs = tenant is not None
            # the latency columns are the runstore's OPTIONAL v2 SLO
            # fields (absent => unknown, rendered "-"): old rows keep
            # their width so a corpus spanning eras still tables
            print("| ts | git | backend | device | metric | value | "
                  "wirelength | iters | era |"
                  + (" q_wait_s | e2e_s | job |" if jobs else ""),
                  file=out)
            print("|---|---|---|---|---|---|---|---|---|"
                  + ("---|---|---|" if jobs else ""), file=out)
            for r in grecs[-last:]:
                qor = r.get("qor") or {}
                era = "pre_pr2" if (r.get("tags") or {}).get("pre_pr2") \
                    else ""
                line = (f"| {r.get('ts')} | {r.get('git_rev')} "
                        f"| {r.get('backend')} | {r.get('device_kind')} "
                        f"| {r.get('metric')} | {_fmt(r.get('value'))} "
                        f"| {_fmt(qor.get('wirelength'))} "
                        f"| {_fmt(qor.get('iterations'))} | {era} |")
                if jobs:
                    line += (f" {_fmt(r.get('queue_wait_s'))} "
                             f"| {_fmt(r.get('e2e_s'))} "
                             f"| {r.get('job_id') or '-'} |")
                print(line, file=out)
        pair = pick_attribution_pair(recs)
        if pair is None:
            print("\n(attribution: no same-backend pair yet)", file=out)
            continue
        a, b = pair
        att = attribute(a, b)
        if att is None:
            print("\n(attribution: rows lack stage fields)", file=out)
            continue
        print(f"\nattribution {a.get('ts')} ({a.get('git_rev')}) -> "
              f"{b.get('ts')} ({b.get('git_rev')}), backend "
              f"{b.get('backend')}:", file=out)
        print(f"  modeled {att['rate_before']:.2f} -> "
              f"{att['rate_after']:.2f} nets/s "
              f"(total {att['total_delta']:+.2f})", file=out)
        for st in att["stages"]:
            print(f"    {st['stage']:<17} {st['delta']:+8.2f}   "
                  f"{st['desc']}", file=out)
        ssum = sum(st["delta"] for st in att["stages"])
        line = f"  stage sum {ssum:+.2f}"
        if att["measured_delta"] is not None:
            line += f" vs measured delta {att['measured_delta']:+.2f}"
            denom = max(abs(att["measured_delta"]), 1e-9)
            if abs(ssum - att["measured_delta"]) <= 0.05 * max(
                    denom, abs(att["rate_before"]) * 0.01):
                line += "  (within 5%)"
        print(line, file=out)
    if not shown:
        print(f"observatory: no records under {runs_dir}/",
              file=sys.stderr)
        return 2
    return 0


# ---- legacy import -------------------------------------------------

_MC_TAIL = re.compile(r"mesh \((\d+), (\d+)\), (\d+) iters, "
                      r"wirelength (\d+)")


def _legacy_bench_record(rs, path: str, doc: dict):
    n = doc.get("n", 0)
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"),
                                             dict) else None
    det = (parsed or {}).get("detail") or {}
    # legacy rows all ran bench.py defaults; the scenario id mirrors
    # bench._config_key so the old trajectory joins the fresh one
    luts = det.get("luts", 60)
    scale = 1 if det.get("scale_config") else 0
    scenario = f"scale{scale}_l{luts}_w12_planes_b64"
    tags = {"pre_pr2": True, "legacy_file": os.path.basename(path),
            "round": n}
    if doc.get("rc", 0) != 0 or parsed is None:
        tags["error"] = True
    qor = {}
    if det.get("wirelength") is not None:
        qor["wirelength"] = det["wirelength"]
    if det.get("routed") is not None:
        qor["routed"] = det["routed"]
    if det.get("iterations") is not None:
        qor["iterations"] = det["iterations"]
    return rs.make_record(
        scenario, {"legacy_file": os.path.basename(path)},
        (parsed or {}).get("metric") or "error",
        (parsed or {}).get("value", -1.0),
        (parsed or {}).get("unit") or "none",
        det.get("platform") or "unknown", "unknown",
        qor=qor or None, detail=det or None, tags=tags,
        ts=f"0000-legacy-r{n:02d}", rev="unknown")


def _legacy_multichip_record(rs, path: str, doc: dict):
    base = os.path.basename(path)
    n = int(re.search(r"r(\d+)", base).group(1)) \
        if re.search(r"r(\d+)", base) else 0
    ok = bool(doc.get("ok"))
    skipped = bool(doc.get("skipped"))
    tags = {"pre_pr2": True, "legacy_file": base, "round": n}
    if skipped:
        tags["skipped"] = True
    qor = {}
    m = _MC_TAIL.search(doc.get("tail") or "")
    if m:
        qor = {"mesh": [int(m.group(1)), int(m.group(2))],
               "iterations": int(m.group(3)),
               "wirelength": int(m.group(4))}
    nd = doc.get("n_devices", 0)
    return rs.make_record(
        f"multichip_dryrun_d{nd}", {"legacy_file": base},
        "dryrun_ok", 1.0 if ok else 0.0, "bool",
        "tpu" if ok and not skipped else "unknown", "unknown",
        qor=qor or None, tags=tags,
        ts=f"0000-legacy-r{n:02d}", rev="unknown")


def import_legacy(rs, runs_dir: str, bench_dir: str = ".") -> int:
    """One-shot migration of the pre-corpus row files.  Idempotent:
    a record whose tags.legacy_file is already present in its scenario
    file is skipped."""
    paths = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")))
    paths += sorted(glob.glob(os.path.join(bench_dir,
                                           "MULTICHIP_*.json")))
    if not paths:
        print(f"observatory: no legacy BENCH_*/MULTICHIP_* rows in "
              f"{bench_dir}", file=sys.stderr)
        return 2
    seen = {}      # scenario -> set of already-imported legacy files
    imported = skipped = 0
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"observatory: cannot read {path}: {e}",
                  file=sys.stderr)
            return 2
        if os.path.basename(path).startswith("MULTICHIP"):
            rec = _legacy_multichip_record(rs, path, doc)
        else:
            rec = _legacy_bench_record(rs, path, doc)
        scen = rec["scenario"]
        if scen not in seen:
            seen[scen] = {(r.get("tags") or {}).get("legacy_file")
                          for r in rs.read_runs(runs_dir, scen)}
        if (rec["tags"] or {}).get("legacy_file") in seen[scen]:
            skipped += 1
            continue
        rs.append_run(runs_dir, rec)
        seen[scen].add(rec["tags"]["legacy_file"])
        imported += 1
        print(f"  imported {os.path.basename(path)} -> "
              f"{scen}.jsonl (pre_pr2)")
    print(f"observatory: imported {imported} legacy row(s), "
          f"{skipped} already present")
    return 0


# ---- congestion export ---------------------------------------------

def export_congestion(rs, runs_dir: str, out_path=None,
                      bins: int = 0) -> int:
    """Emit the accumulated congestion corpus: for every run that
    recorded congestion, its per-window overuse points and a raster
    (re-binned to --bins when given, else the stored one)."""
    doc = {"schema_version": rs.SCHEMA_VERSION,
           "generated": rs.now_iso(), "scenarios": {}}
    nruns = 0
    for scen in rs.scenarios(runs_dir):
        items = []
        for rec in rs.read_runs(runs_dir, scen):
            cong = rec.get("congestion")
            if not isinstance(cong, dict) or not cong.get("windows"):
                continue
            ex, ey = cong.get("extent") or [1, 1]
            heatmap, nb = cong.get("heatmap"), cong.get("bins")
            if bins:
                pts = [p for w in cong["windows"]
                       for p in (w.get("points") or [])]
                heatmap, nb = rs.rasterize(pts, ex, ey, bins), bins
            items.append({
                "ts": rec.get("ts"), "git_rev": rec.get("git_rev"),
                "backend": rec.get("backend"),
                "config_hash": rec.get("config_hash"),
                "extent": [ex, ey], "bins": nb, "heatmap": heatmap,
                "windows": cong["windows"],
            })
        if items:
            doc["scenarios"][scen] = items
            nruns += len(items)
    if not nruns:
        print(f"observatory: no congestion records under {runs_dir}/",
              file=sys.stderr)
        return 2
    blob = json.dumps(doc, sort_keys=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(blob)
        print(f"observatory: wrote {nruns} congestion run(s) across "
              f"{len(doc['scenarios'])} scenario(s) to {out_path}")
    else:
        print(blob)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", nargs="?",
                    choices=["report", "import-legacy",
                             "export-congestion"],
                    help="default: report")
    ap.add_argument("--import-legacy", action="store_true",
                    dest="import_legacy_flag",
                    help="alias for the import-legacy command")
    ap.add_argument("--export-congestion", action="store_true",
                    dest="export_congestion_flag",
                    help="alias for the export-congestion command")
    ap.add_argument("--runs", "--runs-dir", dest="runs",
                    default="runs", help="corpus directory "
                                         "(default %(default)s)")
    ap.add_argument("--scenario", help="restrict to one scenario")
    ap.add_argument("--last", type=int, default=10,
                    help="trend-table rows per scenario")
    ap.add_argument("--bench-dir", default=".",
                    help="where the legacy BENCH_*/MULTICHIP_* rows "
                         "live (import-legacy)")
    ap.add_argument("--out", help="output file for export-congestion "
                                  "(default: stdout)")
    ap.add_argument("--bins", type=int, default=0,
                    help="re-rasterize exported heatmaps to this many "
                         "bins (0 = as stored)")
    args = ap.parse_args(argv)

    cmd = args.command or "report"
    if args.import_legacy_flag:
        cmd = "import-legacy"
    if args.export_congestion_flag:
        cmd = "export-congestion"

    rs = load_runstore()
    try:
        if cmd == "import-legacy":
            return import_legacy(rs, args.runs, args.bench_dir)
        if cmd == "export-congestion":
            return export_congestion(rs, args.runs, args.out,
                                     args.bins)
        return print_report(rs, args.runs, args.scenario, args.last)
    except (OSError, ValueError) as e:
        print(f"observatory: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
