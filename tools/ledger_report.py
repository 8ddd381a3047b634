#!/usr/bin/env python3
"""Summarize (or validate) the work-efficiency ledger inside a
metrics JSON written by the obs registry (stats_dir/metrics.json, or
any MetricsRegistry.dump output).

Stdlib-only on purpose — like trace_report.py it must run anywhere the
file lands (laptop, CI) without jax or the repo on the path.

    python tools/ledger_report.py metrics.json          # human summary
    python tools/ledger_report.py metrics.json --check  # validate,
                                                        # exit != 0 on a
                                                        # malformed ledger

The ledger splits every relaxation sweep the device executed into
useful (improved some distance) and wasted (fixpoint discovery /
ceiling overhead), and records the batch-plan shape per window:

    route.relax_steps          counter  executed sweeps (total)
    route.relax_steps_useful   counter  sweeps that improved a distance
    route.relax_steps_wasted   counter  the rest
    route.bucket_occupancy     histogram  filled / (rows * width) per
                                          size-class dispatch
    route.compaction_ratio     gauge    compacted plan width / full B
    route.relax_wasted_frac    gauge    end-of-route wasted fraction

Invariant checked: useful + wasted == total, occupancy and compaction
in (0, 1], and the wasted fraction consistent with the counters.  When
the route.kernel dispatch-shape gauge is present, --check also
enforces dispatches_per_window >= 1 (one dispatch per populated rung).
"""

from __future__ import annotations

import argparse
import json
import sys

LEDGER_KEYS = ("route.relax_steps", "route.relax_steps_useful",
               "route.relax_steps_wasted")

# mirrors obs/devprof.py DELTA_BAND_LOG10 (stdlib-only tool: no import)
DEVCOST_DELTA_BAND_LOG10 = 2.0


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _ledger(values: dict):
    return tuple(values.get(k) for k in LEDGER_KEYS)


def validate(doc) -> list:
    """Return a list of problems (empty = the ledger is well-formed)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"top level is {type(doc).__name__}, expected object"]
    values = doc.get("values")
    if not isinstance(values, dict):
        return ["missing/non-object 'values'"]
    total, useful, wasted = _ledger(values)
    for k, v in zip(LEDGER_KEYS, (total, useful, wasted)):
        if v is None:
            errs.append(f"missing ledger counter '{k}'")
        elif not isinstance(v, (int, float)) or v < 0:
            errs.append(f"bad ledger counter {k}={v!r}")
    if errs:
        return errs
    if useful + wasted != total:
        errs.append(f"ledger invariant broken: useful {useful} + "
                    f"wasted {wasted} != total {total}")
    occ = values.get("route.bucket_occupancy")
    if occ is not None:
        lo, hi = occ.get("min"), occ.get("max")
        if occ.get("count", 0) > 0 and not (
                0 < lo <= hi <= 1.0 + 1e-9):
            errs.append(f"bucket occupancy out of (0, 1]: "
                        f"min={lo} max={hi}")
    comp = values.get("route.compaction_ratio")
    if comp is not None and not 0 < comp <= 1.0 + 1e-9:
        errs.append(f"compaction ratio out of (0, 1]: {comp}")
    wf = values.get("route.relax_wasted_frac")
    if wf is not None and total > 0 and abs(
            wf - wasted / total) > 1e-3:
        errs.append(f"relax_wasted_frac {wf} inconsistent with "
                    f"counters ({wasted}/{total})")
    # device-truth gauges (route.devcost.*, published by obs/devprof):
    # measured bytes must be positive and the measured-vs-modeled ratio
    # inside the declared sanity band
    ba = values.get("route.devcost.bytes_accessed")
    if ba is not None and not (isinstance(ba, (int, float)) and ba > 0):
        errs.append(f"route.devcost.bytes_accessed not positive: {ba!r}")
    bd = values.get("route.devcost.bytes_delta")
    if bd is not None:
        import math
        if not (isinstance(bd, (int, float)) and bd > 0 and
                abs(math.log10(bd)) <= DEVCOST_DELTA_BAND_LOG10):
            errs.append(
                f"route.devcost.bytes_delta {bd!r} outside the "
                f"1e±{DEVCOST_DELTA_BAND_LOG10} measured-vs-modeled "
                f"sanity band")
    # dispatch-shape invariant: one dispatch per populated rung, and a
    # window populates at least one
    dpw = values.get("route.kernel.dispatches_per_window")
    if dpw is not None \
            and not (isinstance(dpw, (int, float)) and dpw >= 1):
        errs.append(
            f"route.kernel.dispatches_per_window not >= 1: {dpw!r}")
    pd = values.get("route.kernel.plane_dtype")
    if pd is not None and pd not in ("f32", "bf16"):
        errs.append(f"bad route.kernel.plane_dtype {pd!r}")
    # per-snapshot monotonicity: counters never decrease along the run
    prev = (0, 0, 0)
    for i, s in enumerate(doc.get("snapshots", [])):
        if not isinstance(s, dict) or "values" not in s:
            errs.append(f"snapshot {i}: not an object with 'values'")
            continue
        cur = _ledger(s["values"])
        if any(c is not None for c in cur):
            cur = tuple(c or 0 for c in cur)
            if any(c < p for c, p in zip(cur, prev)):
                errs.append(f"snapshot {i}: ledger counter decreased "
                            f"{prev} -> {cur}")
            if cur[1] + cur[2] != cur[0]:
                errs.append(f"snapshot {i}: useful {cur[1]} + wasted "
                            f"{cur[2]} != total {cur[0]}")
            prev = cur
    return errs


def summarize(doc) -> str:
    values = doc.get("values", {})
    total, useful, wasted = (v or 0 for v in _ledger(values))
    lines = ["work-efficiency ledger:"]
    frac = wasted / total if total else 0.0
    lines.append(f"  relax sweeps: {total} executed = {useful} useful "
                 f"+ {wasted} wasted ({frac:.1%} wasted)")
    occ = values.get("route.bucket_occupancy")
    if occ and occ.get("count"):
        lines.append(f"  bucket occupancy: mean {occ['mean']:.2f} "
                     f"(min {occ['min']:.2f}, max {occ['max']:.2f}, "
                     f"{occ['count']} dispatches)")
    comp = values.get("route.compaction_ratio")
    if comp is not None:
        lines.append(f"  plan compaction: {comp:.2f} of full width "
                     f"(last window)")
    dpw = values.get("route.kernel.dispatches_per_window")
    if dpw is not None:
        pd = values.get("route.kernel.plane_dtype")
        lines.append(
            f"  dispatch shape (last window): {int(dpw)} dispatch(es), "
            f"one a populated rung, planes {pd or 'f32'}")
    ba = values.get("route.devcost.bytes_accessed")
    if ba is not None:
        bd = values.get("route.devcost.bytes_delta")
        lines.append(
            f"  device-truth cost (dominant variant): "
            f"{values.get('route.devcost.flops', 0):.3g} flops, "
            f"{ba:.3g} B accessed, peak temp "
            f"{values.get('route.devcost.peak_temp_bytes', 0):.3g} B"
            + (f", measured/modeled bytes {bd:g}" if bd is not None
               else "")
            + f" ({values.get('route.devcost.variants', '?')} variants)")
    # trajectory: per-snapshot deltas of the executed/wasted counters
    rows = []
    prev = (0, 0, 0)
    for s in doc.get("snapshots", []):
        v = s.get("values", {})
        cur = _ledger(v)
        if all(c is None for c in cur):
            continue
        cur = tuple(c or 0 for c in cur)
        d_tot = cur[0] - prev[0]
        d_was = cur[2] - prev[2]
        if d_tot:
            rows.append((s.get("labels", {}).get("iteration", "?"),
                         d_tot, d_was))
        prev = cur
    if rows:
        lines.append("  per-window trajectory:")
        lines.append("    iter  sweeps  wasted")
        for it, d_tot, d_was in rows:
            lines.append(f"    {it!s:>4}  {d_tot:>6}  {d_was:>6}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("metrics", help="metrics JSON file "
                                    "(MetricsRegistry.dump output)")
    ap.add_argument("--check", action="store_true",
                    help="validate only; exit nonzero if malformed")
    args = ap.parse_args(argv)

    try:
        doc = load(args.metrics)
    except (OSError, json.JSONDecodeError) as e:
        print(f"MALFORMED: {e}", file=sys.stderr)
        return 2

    errs = validate(doc)
    if args.check:
        if errs:
            print("MALFORMED ledger:", file=sys.stderr)
            for e in errs[:20]:
                print(f"  {e}", file=sys.stderr)
            return 1
        total = doc["values"].get("route.relax_steps", 0)
        print(f"OK: ledger covers {total} relax sweeps")
        return 0

    if errs:
        print(f"warning: {len(errs)} validation problem(s); "
              f"run with --check for details", file=sys.stderr)
    print(summarize(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
