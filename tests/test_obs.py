"""Unified observability subsystem (obs/): span tracer + Chrome trace
export, metrics registry, JAX compile capture, --trace CLI surface,
tools/trace_report.py validation."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from parallel_eda_tpu.obs import (DevProfiler, MetricsRegistry, Tracer,
                                  get_metrics, set_devprof, set_metrics,
                                  set_tracer, span, stage)
from parallel_eda_tpu.obs.trace import _NULL_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")


def _load_trace_report():
    spec = importlib.util.spec_from_file_location("trace_report",
                                                  TRACE_REPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test gets (and leaves behind) pristine process-wide obs
    state: no tracer, a fresh disabled registry + devprof."""
    set_tracer(None)
    set_metrics(MetricsRegistry())
    set_devprof(DevProfiler())
    yield
    set_tracer(None)
    set_metrics(MetricsRegistry())
    set_devprof(DevProfiler())


# ---- tracer ----

def test_span_nesting_roundtrip(tmp_path):
    tr = Tracer()
    set_tracer(tr)
    with span("outer", cat="stage", label="x"):
        with span("inner", cat="route", it=3):
            pass
        with span("inner2"):
            pass
    tr.instant("mark", note="here")
    p = tmp_path / "t.json"
    tr.export(str(p))

    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner", "inner2"}
    outer, inner = xs["outer"], xs["inner"]
    # nesting: child contained in parent, µs timestamps, args kept
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"label": "x"}
    assert inner["args"] == {"it": 3}
    assert inner["cat"] == "route"
    # export sorts by ts and every X event has a nonnegative dur
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    assert any(e["ph"] == "i" and e["name"] == "mark" for e in evs)
    # and the validator agrees it is well-formed
    assert _load_trace_report().validate(doc) == []


def test_stage_writes_times_dict():
    tr = Tracer()
    set_tracer(tr)
    times = {}
    with stage("pack", times):
        pass
    assert times["pack"] >= 0.0
    assert tr.total("pack") >= 0.0
    # stage() keeps the legacy dict populated even with tracing off
    set_tracer(None)
    with stage("route", times):
        pass
    assert "route" in times


def test_disabled_path_is_true_noop():
    assert span("anything", it=1) is _NULL_SPAN
    assert span("other") is span("different")     # one shared singleton
    with span("nested"):
        with span("deeper"):
            pass                                  # no tracer, no effect


# ---- metrics ----

def test_metrics_registry_shapes():
    reg = MetricsRegistry(enabled=True)
    reg.counter("route.iterations").inc(3)
    reg.gauge("route.pres_fac").set(1.3)
    reg.histogram("route.window_wall_s").record(0.5)
    reg.histogram("route.window_wall_s").record(1.5)
    assert reg.counter("route.iterations").value == 3
    h = reg.histogram("route.window_wall_s")
    assert h.count == 2 and h.mean == 1.0 and h.min == 0.5 and h.max == 1.5

    v = reg.values()
    assert v["route.iterations"] == 3
    assert v["route.pres_fac"] == 1.3
    assert v["route.window_wall_s"]["count"] == 2
    assert set(reg.values(prefix="route.pres")) == {"route.pres_fac"}

    s = reg.snapshot(phase="route", iteration=1)
    assert s["labels"] == {"phase": "route", "iteration": 1}
    reg.counter("route.iterations").inc()
    reg.snapshot(phase="route", iteration=2)
    reg.snapshot(phase="place", temperature=0)
    assert reg.series("route.iterations", phase="route") == [3, 4]
    assert len(reg.snapshots) == 3


def test_metrics_disabled_snapshot_noop(tmp_path):
    reg = MetricsRegistry()                 # enabled=False default
    reg.counter("c").inc()                  # updates stay cheap + legal
    assert reg.snapshot(phase="x") is None
    assert reg.snapshots == []
    p = tmp_path / "m.json"
    reg.dump(str(p))
    doc = json.loads(p.read_text())
    assert doc["values"]["c"] == 1 and doc["snapshots"] == []


def test_metrics_reset_keeps_enabled():
    reg = MetricsRegistry(enabled=True)
    reg.counter("c").inc()
    reg.snapshot(phase="x")
    reg.reset()
    assert reg.enabled and reg.values() == {} and reg.snapshots == []


def test_series_ordering_and_labels_across_reset():
    """series() preserves snapshot order, honors label matching, and a
    reset() (the benches' warmup/measured boundary) starts the history
    over instead of splicing old samples in."""
    reg = MetricsRegistry(enabled=True)
    reg.gauge("g").set(1)
    reg.snapshot(phase="route", iteration=1)
    reg.gauge("g").set(2)
    reg.snapshot(phase="route", iteration=2)
    reg.gauge("g").set(9)
    reg.snapshot(phase="place", temperature=0)
    assert reg.series("g", phase="route") == [1, 2]
    assert reg.series("g") == [1, 2, 9]
    assert reg.series("g", phase="route", iteration=2) == [2]
    assert reg.series("g", phase="sta") == []
    reg.reset()
    assert reg.series("g", phase="route") == []
    reg.gauge("g").set(7)
    reg.snapshot(phase="route", iteration=1)
    assert reg.series("g", phase="route") == [7]


def test_dispatch_variant_set_survives_registry_reset():
    """The warmup/measured boundary resets the registry but must NOT
    forget which dispatch variants already compiled: the measured run's
    route.dispatch.* split would otherwise count warm cache hits as
    fresh compiles."""
    from parallel_eda_tpu.route import router as rt

    key = ("test-only-variant", 1, 2, 3)
    rt._DISPATCH_VARIANTS.discard(key)
    try:
        reg = get_metrics()
        assert rt._note_dispatch_variant(key) is True
        assert reg.counter("route.dispatch.compiles").value == 1
        reg.reset()                       # warmup/measured boundary
        assert rt._note_dispatch_variant(key) is False
        assert reg.counter("route.dispatch.cache_hits").value == 1
        assert reg.counter("route.dispatch.compiles").value == 0
    finally:
        rt._DISPATCH_VARIANTS.discard(key)


# ---- Perfetto counter tracks ----

def test_snapshot_mirrors_counter_tracks(tmp_path):
    """Every enabled snapshot mirrors the COUNTER_TRACKS instruments as
    "C" events on the tracer's clock; other instruments (and bools) do
    not leak onto tracks."""
    tr = Tracer()
    set_tracer(tr)
    reg = MetricsRegistry(enabled=True)
    set_metrics(reg)
    with tr.span("route", cat="stage"):
        reg.gauge("route.overused_nodes").set(25)
        reg.gauge("route.pres_fac").set(0.5)
        reg.counter("route.relax_steps_wasted").inc(4)
        reg.gauge("route.success").set(True)      # not a track
        reg.snapshot(phase="route", iteration=1)
        reg.gauge("route.overused_nodes").set(9)
        reg.gauge("route.pres_fac").set(0.65)
        reg.counter("route.relax_steps_wasted").inc(3)
        reg.snapshot(phase="route", iteration=2)
    cs = [e for e in tr.events if e["ph"] == "C"]
    assert {e["name"] for e in cs} == {"route.overused_nodes",
                                       "route.pres_fac",
                                       "route.relax_steps_wasted"}
    by = {}
    for e in cs:
        by.setdefault(e["name"], []).append(e["args"]["value"])
    assert by["route.overused_nodes"] == [25.0, 9.0]
    assert by["route.relax_steps_wasted"] == [4.0, 7.0]
    # the export round-trips through --check (incl. counter rules) and
    # the summary prints the counter-track line
    p = tmp_path / "t.json"
    tr.export(str(p))
    mod = _load_trace_report()
    doc = json.loads(p.read_text())
    assert mod.validate(doc) == []
    assert mod.check_counters(doc) == []
    s = mod.summarize(doc)
    assert "counter tracks:" in s and "route.overused_nodes" in s


def test_snapshot_counter_mirror_without_tracer():
    """No tracer installed: snapshots still record, nothing crashes."""
    reg = MetricsRegistry(enabled=True)
    reg.gauge("route.pres_fac").set(0.5)
    assert reg.snapshot(phase="route") is not None


# ---- JAX compile capture ----

def test_compile_spans_captured():
    import jax
    import jax.numpy as jnp

    tr = Tracer()
    set_tracer(tr)                  # also registers the jax listener
    # a fresh lambda is a fresh jit cache entry -> a real compile
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    f(jnp.ones((7,))).block_until_ready()
    assert tr.total("jax.compile") > 0.0
    names = {e["name"] for e in tr.events if e["cat"] == "jax.compile"}
    assert any(n.startswith("jax.compile.") for n in names)


def test_compile_seconds_accumulator():
    import jax
    import jax.numpy as jnp

    from parallel_eda_tpu.obs import compile_seconds, enable_compile_capture

    enable_compile_capture()
    c0 = compile_seconds()
    jax.jit(lambda x: x + 3.0)(jnp.ones((5,))).block_until_ready()
    assert compile_seconds() > c0


# ---- tools/trace_report.py ----

def test_trace_report_check_accepts_tracer_output(tmp_path):
    tr = Tracer()
    with tr.span("a", x=1):
        with tr.span("b"):
            pass
    p = tmp_path / "ok.json"
    tr.export(str(p))
    r = subprocess.run([sys.executable, TRACE_REPORT, str(p), "--check"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    # and the summary mode runs clean on the same file
    r = subprocess.run([sys.executable, TRACE_REPORT, str(p)],
                       capture_output=True, text=True)
    assert r.returncode == 0 and "compile vs execute" in r.stdout


def test_trace_report_check_rejects_malformed(tmp_path):
    tr = _load_trace_report()
    # field-level problems, detected in-process
    assert tr.validate([]) != []                          # not an object
    assert tr.validate({"traceEvents": [
        {"ph": "X", "name": "a"}]}) != []                 # missing ts/dur
    assert tr.validate({"traceEvents": [
        {"ph": "X", "name": "a", "ts": 5, "dur": 1, "pid": 1, "tid": 1},
        {"ph": "X", "name": "b", "ts": 1, "dur": 1, "pid": 1, "tid": 1},
    ]}) != []                                             # unsorted
    assert tr.validate({"traceEvents": [
        {"ph": "E", "name": "a", "ts": 1, "pid": 1, "tid": 1}]}) != []

    # exit codes through the CLI
    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": [{"ph": "X", "name": "a"}]}')
    r = subprocess.run([sys.executable, TRACE_REPORT, str(bad),
                       "--check"], capture_output=True, text=True)
    assert r.returncode == 1 and "MALFORMED" in r.stderr
    notjson = tmp_path / "not.json"
    notjson.write_text("{nope")
    r = subprocess.run([sys.executable, TRACE_REPORT, str(notjson),
                       "--check"], capture_output=True, text=True)
    assert r.returncode == 2


def test_trace_report_counter_rules(tmp_path):
    """check_counters rejects samples off the span clock origin,
    non-numeric values, and non-monotone per-track timestamps."""
    mod = _load_trace_report()
    x = {"ph": "X", "name": "route", "cat": "stage", "ts": 0,
         "dur": 100, "pid": 1, "tid": 1}

    def c(name, ts, value):
        return {"ph": "C", "name": name, "cat": "metrics", "ts": ts,
                "pid": 1, "tid": 1, "args": {"value": value}}

    # a counter stamped from a different clock origin lands far outside
    # the [0, span end + slack] envelope
    doc = {"traceEvents": [x, c("route.pres_fac", 1e9, 1.0)]}
    errs = mod.check_counters(doc)
    assert errs and "clock" in errs[0]
    # non-numeric / boolean values
    doc = {"traceEvents": [x, c("route.pres_fac", 5, "high")]}
    assert any("non-numeric" in e for e in mod.check_counters(doc))
    doc = {"traceEvents": [x, c("route.pres_fac", 5, True)]}
    assert any("non-numeric" in e for e in mod.check_counters(doc))
    # per-track ts must be non-decreasing
    doc = {"traceEvents": [x, c("route.pres_fac", 50, 1.0),
                           c("route.pres_fac", 10, 2.0)]}
    assert any("monotone" in e for e in mod.check_counters(doc))
    # a clean track passes, and the CLI --check gates the bad one
    doc = {"traceEvents": [x, c("route.pres_fac", 10, 1.0),
                           c("route.pres_fac", 50, 2.0)]}
    assert mod.check_counters(doc) == []
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"traceEvents": [x, c("route.pres_fac", 1e9, 1.0)]}))
    r = subprocess.run([sys.executable, TRACE_REPORT, str(bad),
                       "--check"], capture_output=True, text=True)
    assert r.returncode == 1 and "clock" in r.stderr


def test_reset_compile_seconds():
    import jax
    import jax.numpy as jnp

    from parallel_eda_tpu.obs import (compile_seconds,
                                      enable_compile_capture,
                                      reset_compile_seconds)

    enable_compile_capture()
    jax.jit(lambda x: x - 1.5)(jnp.ones((3,))).block_until_ready()
    assert compile_seconds() > 0.0
    reset_compile_seconds()
    assert compile_seconds() == 0.0
    # and the accumulator keeps counting after the reset
    jax.jit(lambda x: x * 0.5 - 2.0)(jnp.ones((4,))).block_until_ready()
    assert compile_seconds() > 0.0


# ---- bench stderr noise filter ----

def test_bench_stderr_filter_scrubs_noise():
    """The fd-level filter drops the XLA host-machine-features warning
    wall (printed by native code, so it must be caught at fd 2, not
    sys.stderr) while passing ordinary lines through."""
    code = "\n".join([
        "import os, sys",
        f"sys.path.insert(0, {REPO!r})",
        "import bench",
        "bench.install_stderr_filter()",
        "os.write(2, b'keep this line\\n')",
        "os.write(2, b'... SIGILL ... host machine features ...\\n')",
        "os.write(2, b'+sse4a,-avx512vnni,+cmov,-amx,+avx,+avx2,"
        "-foo,+bar,+baz\\n')",
        "os.write(2, b'also keep\\n')",
    ])
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=60)
    assert "keep this line" in r.stderr and "also keep" in r.stderr
    assert "SIGILL" not in r.stderr
    assert "sse4a" not in r.stderr
    # the escape hatch leaves stderr untouched
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
        env=dict(os.environ, BENCH_NO_STDERR_FILTER="1"))
    assert "SIGILL" in r.stderr and "sse4a" in r.stderr


def test_bench_without_cpu_flag_refuses_a_cpu_only_host():
    """No fallback: without --cpu, on a host where JAX finds no TPU,
    bench.py exits non-zero and prints NO metric line (a row measured
    elsewhere must never stand in for a device number)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--luts", "10",
         "--no_corpus"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU found" in r.stderr


# ---- CLI surface ----

def test_cli_trace_smoke(tmp_path, capsys):
    """--trace on the pack-only flow (no place/route: pure host work,
    fast): a valid Chrome trace with the stage spans lands on disk."""
    from parallel_eda_tpu.__main__ import main

    p = tmp_path / "t.json"
    rc = main(["--luts", "12", "--arch", "minimal", "--no_place",
               "--no_route", "--trace", str(p),
               "--out_dir", str(tmp_path / "out")])
    assert rc == 0
    doc = json.loads(p.read_text())
    assert _load_trace_report().validate(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"pack", "rr_graph"} <= names
    assert "trace in" in capsys.readouterr().out


@pytest.mark.slow
def test_cli_trace_full_flow(tmp_path, capsys):
    """Acceptance shape: a routed flow's trace has pack/place/route
    stages, per-route-iteration spans, and a nonzero compile split."""
    from parallel_eda_tpu.__main__ import main

    p = tmp_path / "t.json"
    sd = tmp_path / "stats"
    rc = main(["--luts", "30", "--arch", "minimal", "--no_timing",
               "--trace", str(p), "--stats_dir", str(sd),
               "--out_dir", str(tmp_path / "out")])
    assert rc == 0
    doc = json.loads(p.read_text())
    assert _load_trace_report().validate(doc) == []
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert {"pack", "rr_graph", "place", "route"} <= names
    iters = [e for e in evs if e["name"] == "route.iter"]
    assert iters and all("it" in e["args"] for e in iters)
    assert sum(e["dur"] for e in evs
               if e["cat"] == "jax.compile") > 0     # compile split
    # the metrics sink landed next to the mdclog files, with the
    # per-iteration route snapshots and the shared wire-only overuse
    m = json.loads((sd / "metrics.json").read_text())
    route_snaps = [s for s in m["snapshots"]
                   if s["labels"].get("phase") == "route"]
    assert route_snaps
    assert m["values"]["route.success"] is True
    assert m["values"]["route.overused_wire_nodes"] == 0
    place_snaps = [s for s in m["snapshots"]
                   if s["labels"].get("phase") == "place"]
    assert place_snaps and "place.t" in place_snaps[0]["values"]
