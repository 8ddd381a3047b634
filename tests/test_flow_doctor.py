"""Flow-doctor regression sentinel (tools/flow_doctor.py): bench-row
gates, devprof-ledger gates, and the trace/metrics passthrough.

Runs in-process (importlib, like the other tools tests) so the smoke
stays fast; one subprocess test pins the CLI exit codes.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOW_DOCTOR = os.path.join(REPO, "tools", "flow_doctor.py")

pytestmark = pytest.mark.doctor


def _load():
    spec = importlib.util.spec_from_file_location("flow_doctor",
                                                  FLOW_DOCTOR)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(value=30.0, wirelength=500, wasted=0.3, overlap=0.8, **extra):
    d = {"wirelength": wirelength,
         "ledger": {"relax_wasted_frac": wasted},
         "pipeline": {"overlap_frac": overlap}}
    d.update(extra)
    return {"metric": "nets_routed_per_sec", "value": value,
            "unit": "nets/s", "vs_baseline": 1.0, "detail": d}


# ---- bench-row gates ----

def test_clean_row_passes():
    fd = _load()
    errs, notes = fd.check_row(_row(value=29.5), _row(value=30.0), 0.10)
    assert errs == [] and notes


def test_nets_per_sec_regression_fails():
    fd = _load()
    errs, _ = fd.check_row(_row(value=25.0), _row(value=30.0), 0.10)
    assert any("regressed" in e for e in errs)
    # 10% is the gate: a 9% drop passes, an 11% drop fails
    assert fd.check_row(_row(value=27.3), _row(value=30.0), 0.10)[0] == []
    assert fd.check_row(_row(value=26.7), _row(value=30.0), 0.10)[0]


def test_any_wirelength_increase_fails():
    fd = _load()
    errs, _ = fd.check_row(_row(wirelength=501), _row(wirelength=500),
                           0.10)
    assert any("wirelength" in e for e in errs)
    assert fd.check_row(_row(wirelength=500), _row(wirelength=500),
                        0.10)[0] == []


def test_overlap_floor_and_wasted_slack():
    fd = _load()
    errs, _ = fd.check_row(_row(overlap=0.3), _row(), 0.10)
    assert any("overlap_frac" in e for e in errs)
    errs, _ = fd.check_row(_row(wasted=0.5), _row(wasted=0.3), 0.10)
    assert any("relax_wasted_frac" in e for e in errs)
    assert fd.check_row(_row(wasted=0.4), _row(wasted=0.3), 0.10)[0] == []


def test_missing_keys_tolerated():
    """Older rows predate some riders: gates skip, never crash."""
    fd = _load()
    bare_prev = {"metric": "nets_routed_per_sec", "value": 30.0,
                 "detail": {"wirelength": 500}}
    errs, notes = fd.check_row(_row(value=29.5), bare_prev, 0.10)
    assert errs == []
    errs, notes = fd.check_row({"metric": "m"}, {"metric": "m"}, 0.10)
    assert errs == [] and any("skipped" in n for n in notes)


def test_row_devcost_gates():
    fd = _load()
    good = _row(devcost={"bytes_accessed": 1e6, "bytes_delta": 30.0,
                         "delta_in_band": True})
    assert fd.check_row(good, _row(), 0.10)[0] == []
    bad = _row(devcost={"bytes_accessed": 0})
    assert any("bytes_accessed" in e
               for e in fd.check_row(bad, _row(), 0.10)[0])
    oob = _row(devcost={"bytes_accessed": 1e6, "bytes_delta": 500.0,
                        "delta_in_band": False, "delta_band_log10": 2.0})
    assert any("band" in e for e in fd.check_row(oob, _row(), 0.10)[0])
    unav = _row(devcost={"unavailable": "no backend analysis"})
    errs, notes = fd.check_row(unav, _row(), 0.10)
    assert errs == [] and any("unavailable" in n for n in notes)


# ---- devprof-ledger gates ----

def _devprof(tmp_path, records):
    p = tmp_path / "devprof.json"
    p.write_text(json.dumps({"delta_band_log10": 2.0,
                             "records": records, "summary": {}}))
    return str(p)


def test_devprof_measured_ok(tmp_path):
    fd = _load()
    errs, notes = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["a"], "bytes_accessed": 5e6, "flops": 2e6,
         "bytes_delta": 30.0}]))
    assert errs == [] and any("measured" in n for n in notes)


def test_devprof_zero_bytes_fails(tmp_path):
    fd = _load()
    errs, _ = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["a"], "bytes_accessed": 0.0}]))
    assert any("not positive" in e for e in errs)


def test_devprof_out_of_band_fails(tmp_path):
    fd = _load()
    errs, _ = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["a"], "bytes_accessed": 5e6, "bytes_delta": 500.0}]))
    assert any("band" in e for e in errs)


def test_devprof_small_variant_off_model_is_note(tmp_path):
    """The band gates the dominant (most-nets) variant; an endgame
    window routing 2 nets sits off the per-net traffic model and must
    not fail the gate."""
    fd = _load()
    errs, notes = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["big"], "meta": {"nets": 64}, "bytes_accessed": 5e7,
         "bytes_delta": 21.5},
        {"key": ["crumb"], "meta": {"nets": 2}, "bytes_accessed": 1e6,
         "bytes_delta": 270.0}]))
    assert errs == []
    assert any("off-model" in n for n in notes)
    # but the dominant variant out of band still fails
    errs, _ = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["big"], "meta": {"nets": 64}, "bytes_accessed": 5e7,
         "bytes_delta": 500.0},
        {"key": ["crumb"], "meta": {"nets": 2}, "bytes_accessed": 1e6,
         "bytes_delta": 30.0}]))
    assert any("dominant" in e for e in errs)


def test_devprof_empty_fails(tmp_path):
    fd = _load()
    errs, _ = fd.check_devprof(_devprof(tmp_path, []))
    assert any("no captured dispatch variants" in e for e in errs)


def test_devprof_all_unavailable_passes(tmp_path):
    """A backend without cost analysis is degradation, not regression."""
    fd = _load()
    errs, notes = fd.check_devprof(_devprof(tmp_path, [
        {"key": ["a"], "unavailable": "backend exposes no analysis"}]))
    assert errs == [] and any("unavailable" in n for n in notes)


# ---- CLI ----

def test_cli_exit_codes(tmp_path):
    prev = tmp_path / "BENCH_r01.json"
    fresh = tmp_path / "BENCH_r02.json"
    prev.write_text(json.dumps({"n": 1, "parsed": _row(value=30.0)}))

    def run(row):
        fresh.write_text(json.dumps({"n": 2, "parsed": row}))
        return subprocess.run(
            [sys.executable, FLOW_DOCTOR, "--row", str(fresh),
             "--bench-dir", str(tmp_path)],
            capture_output=True, text=True, timeout=60)

    r = run(_row(value=29.5))
    assert r.returncode == 0 and "HEALTHY" in r.stdout, r.stderr
    r = run(_row(value=25.0))              # ~17% nets/s drop
    assert r.returncode == 1 and "UNHEALTHY" in r.stderr
    r = run(_row(value=29.5, wirelength=501))
    assert r.returncode == 1 and "wirelength" in r.stderr
    # unreadable artifact -> 2
    r = subprocess.run(
        [sys.executable, FLOW_DOCTOR, "--row",
         str(tmp_path / "missing.json")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 2


def test_config_of_record_row_is_healthy():
    """The acceptance gate: the doctor passes the repo's own latest
    bench row against its history (skips when the history is absent,
    e.g. a fresh checkout without BENCH_*.json)."""
    fd = _load()
    hist = fd.latest_bench_rows(REPO)
    if len(hist) < 2:
        pytest.skip("no BENCH_*.json history in this checkout")
    r = subprocess.run(
        [sys.executable, FLOW_DOCTOR, "--row", hist[-1],
         "--bench-dir", REPO],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


# ---- cross-backend refusal + corpus gates ----

def test_row_backend_resolution():
    fd = _load()
    assert fd._row_backend({"backend": "tpu"}) == "tpu"
    # older rows: fall back to detail.platform
    assert fd._row_backend({"detail": {"platform": "cpu"}}) == "cpu"
    assert fd._row_backend({"backend": "tpu",
                            "detail": {"platform": "cpu"}}) == "tpu"
    assert fd._row_backend({}) == ""
    assert fd._row_backend(None) == ""


def test_cross_backend_rows_skip_with_warning(tmp_path):
    """The r04/r05 lesson as a contract: a cpu row is never gated
    against a tpu row — warning note, exit 0, even when the values
    would otherwise scream regression."""
    prev = tmp_path / "BENCH_r01.json"
    fresh = tmp_path / "BENCH_r02.json"
    prev.write_text(json.dumps(
        {"n": 1, "parsed": _row(value=90.0, platform="tpu")}))
    tpu_row = json.loads(prev.read_text())["parsed"]
    tpu_row["backend"] = "tpu"
    prev.write_text(json.dumps({"n": 1, "parsed": tpu_row}))
    cpu_row = _row(value=30.0)             # -66% vs the tpu row
    cpu_row["backend"] = "cpu"
    fresh.write_text(json.dumps({"n": 2, "parsed": cpu_row}))
    r = subprocess.run(
        [sys.executable, FLOW_DOCTOR, "--row", str(fresh),
         "--bench-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WARNING" in r.stdout and "backends differ" in r.stdout


def _corpus(tmp_path, rows, scenario="bench"):
    """Write corpus rows via the runstore itself (schema-checked)."""
    fd = _load()
    rs = fd._load_runstore()
    runs = str(tmp_path / "runs")
    for i, (value, backend, wl, tags) in enumerate(rows):
        rs.append_run(runs, rs.make_record(
            scenario, {"luts": 60}, "nets_routed_per_sec", value,
            "nets/s", backend, backend,
            qor={"wirelength": wl}, tags=tags,
            ts=f"t{i}", rev="abc1234"))
    return runs


def test_corpus_clean_row_passes(tmp_path):
    fd = _load()
    runs = _corpus(tmp_path, [
        (80.0, "cpu", 537, None), (84.0, "cpu", 537, None),
        (83.0, "cpu", 537, None)])
    errs, notes = fd.check_corpus(runs, "bench", 0.10, 5)
    assert errs == [], errs
    assert any("median" in n for n in notes)


def test_corpus_value_regression_fails(tmp_path):
    fd = _load()
    runs = _corpus(tmp_path, [
        (80.0, "cpu", 537, None), (84.0, "cpu", 537, None),
        (60.0, "cpu", 537, None)])          # ~27% under the median
    errs, _ = fd.check_corpus(runs, "bench", 0.10, 5)
    assert any("regressed" in e for e in errs)


def test_corpus_wirelength_regression_fails(tmp_path):
    fd = _load()
    runs = _corpus(tmp_path, [
        (80.0, "cpu", 537, None), (84.0, "cpu", 537, None),
        (84.0, "cpu", 544, None)])          # any wl increase fails
    errs, _ = fd.check_corpus(runs, "bench", 0.10, 5)
    assert any("wirelength" in e for e in errs)


def test_corpus_tenant_rows_gate_per_job(tmp_path):
    """A multi-tenant serve scenario carries one row PER JOB: the gate
    must compare each (tenant, job_id) against ITS OWN trajectory —
    job A's wirelength vs job B's median would be noise (the jobs
    route different circuits)."""
    fd = _load()
    rs = fd._load_runstore()
    runs = str(tmp_path / "runs")
    # interleaved rows of two jobs: wl 89 job keeps finishing after
    # the wl 97 job — ungrouped, 97 > median(89, 97) would fail
    for i, (ten, jid, wl) in enumerate([
            ("t0", "j0", 89), ("t1", "j1", 97),
            ("t0", "j0", 89), ("t1", "j1", 97)]):
        rs.append_run(runs, rs.make_record(
            "serve_x", {"luts": 15}, "nets_per_s", 12.0, "nets/s",
            "cpu", "cpu", qor={"wirelength": wl},
            tenant=ten, job_id=jid, ts=f"t{i}", rev="abc1234"))
    errs, notes = fd.check_corpus(runs, "serve_x", 0.10, 5)
    assert errs == [], errs
    assert any("serve_x:t0/j0" in n for n in notes)
    assert any("serve_x:t1/j1" in n for n in notes)
    # a genuine per-job wirelength regression still fails
    rs.append_run(runs, rs.make_record(
        "serve_x", {"luts": 15}, "nets_per_s", 12.0, "nets/s",
        "cpu", "cpu", qor={"wirelength": 95},
        tenant="t0", job_id="j0", ts="t9", rev="abc1234"))
    errs, _ = fd.check_corpus(runs, "serve_x", 0.10, 5)
    assert any("t0/j0" in e and "wirelength" in e for e in errs)


def test_corpus_cross_backend_and_legacy_never_gate(tmp_path):
    """A fresh cpu row whose only history is tpu rows (or pre_pr2
    imports) has no trajectory: skip-note, no error — cross-backend
    medians were the exact failure this mode exists to prevent."""
    fd = _load()
    runs = _corpus(tmp_path, [
        (30.0, "cpu", 600, {"pre_pr2": True}),  # legacy era
        (90.0, "tpu", 537, None),               # other backend
        (80.0, "cpu", 537, None)])              # the fresh row
    errs, notes = fd.check_corpus(runs, "bench", 0.10, 5)
    assert errs == [], errs
    assert any("skipped" in n for n in notes)


def test_corpus_cli_exit_codes(tmp_path):
    """The acceptance criterion: 0 on a clean re-run, 1 on an injected
    wirelength regression, 2 when the corpus is missing."""
    runs = _corpus(tmp_path, [
        (80.0, "cpu", 537, None), (84.0, "cpu", 537, None)])

    def run(extra=()):
        return subprocess.run(
            [sys.executable, FLOW_DOCTOR, "--corpus", "--runs-dir",
             runs, *extra], capture_output=True, text=True, timeout=60)

    r = run()
    assert r.returncode == 0 and "HEALTHY" in r.stdout, \
        r.stdout + r.stderr
    # inject a wirelength regression as the freshest row
    fd = _load()
    rs = fd._load_runstore()
    rs.append_run(runs, rs.make_record(
        "bench", {"luts": 60}, "nets_routed_per_sec", 84.0, "nets/s",
        "cpu", "cpu", qor={"wirelength": 551}, ts="t9", rev="abc1234"))
    r = run()
    assert r.returncode == 1 and "wirelength" in r.stderr
    r = run(("--scenario", "absent"))
    assert r.returncode == 1               # named scenario must exist
    r = subprocess.run(
        [sys.executable, FLOW_DOCTOR, "--corpus", "--runs-dir",
         str(tmp_path / "nope")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1


def test_trace_and_metrics_passthrough(tmp_path):
    """The doctor reuses the report tools' rule sets wholesale."""
    fd = _load()
    t = tmp_path / "trace.json"
    t.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "route", "cat": "stage", "ts": 0,
         "dur": 100, "pid": 1, "tid": 1},
        {"ph": "C", "name": "route.pres_fac", "cat": "metrics", "ts": 5,
         "pid": 1, "tid": 1, "args": {"value": 0.5}}]}))
    assert fd.check_trace(str(t)) == []
    m = tmp_path / "metrics.json"
    m.write_text(json.dumps({"values": {
        "route.relax_steps": 10, "route.relax_steps_useful": 7,
        "route.relax_steps_wasted": 3,
        "route.devcost.bytes_accessed": 5e6,
        "route.devcost.bytes_delta": 30.0}, "snapshots": []}))
    assert fd.check_metrics(str(m)) == []
    # broken invariants surface through the same paths
    m.write_text(json.dumps({"values": {
        "route.relax_steps": 10, "route.relax_steps_useful": 7,
        "route.relax_steps_wasted": 4}, "snapshots": []}))
    assert fd.check_metrics(str(m))
    m.write_text(json.dumps({"values": {
        "route.relax_steps": 10, "route.relax_steps_useful": 7,
        "route.relax_steps_wasted": 3,
        "route.devcost.bytes_delta": 500.0}, "snapshots": []}))
    assert any("band" in e for e in fd.check_metrics(str(m)))


@pytest.mark.parametrize("doc,bad", [
    ({"dispatch_compiles": 0}, None),
    ({"dispatch_compiles": 3}, "compiled 3"),
    ({}, "no dispatch_compiles"),
], ids=["warm", "compiled", "no_field"])
def test_warm_gate_reads_dispatch_compiles_alone(doc, bad):
    """``--warm`` over a serve or daemon summary: zero window-program
    compiles or unhealthy, whatever else the summary holds (a
    ``rebatch`` section of an old summary is not looked at)."""
    fd = _load()
    errs, notes = fd.check_warm({**doc, "rebatch": {"fused": True}})
    if bad is None:
        assert errs == [] and any("gate ok" in n for n in notes)
    else:
        assert len(errs) == 1 and bad in errs[0]
    assert not hasattr(fd, "check_rebatch")
