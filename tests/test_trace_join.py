"""The router's host spans on both sinks of ``obs.trace.span()``: a
running profiler session (no Tracer installed) and the Tracer's Chrome
trace; and the two counters timed where the work happens.
"""

import glob
import importlib.util
import os

import pytest

from parallel_eda_tpu.flow import run_place, run_route, synth_flow
from parallel_eda_tpu.obs import (MetricsRegistry, Tracer, get_metrics,
                                  set_metrics, set_tracer)
from parallel_eda_tpu.route import RouterOpts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIPELINE = ("route.pipeline.plan", "route.pipeline.dispatch",
            "route.pipeline.stall")


@pytest.fixture(autouse=True)
def _clean_obs():
    set_tracer(None)
    set_metrics(MetricsRegistry())
    yield
    set_tracer(None)
    set_metrics(MetricsRegistry())


@pytest.fixture(scope="module")
def placed():
    return run_place(synth_flow(num_luts=30, chan_width=12, seed=3))


def _route(placed, **opts):
    return run_route(placed, RouterOpts(program="planes", batch_size=16,
                                        **opts),
                     timing_driven=True, verify=False)


def _inside(child, parent):
    return (parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


def test_profiler_trace_holds_the_routers_spans(placed, tmp_path):
    """No Tracer: the spans exist only as TraceAnnotations, on one host
    line of the profiler's trace, nested route > route.window >
    plan / dispatch / stall, each with its window and route id."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        f = _route(placed)
    finally:
        jax.profiler.stop_trace()
    assert f.route.success
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    lines = [[(e.name, e.start_ns, e.duration_ns, dict(e.stats))
              for e in ln.events
              if e.name == "route" or e.name.startswith("route.")]
             for p in ProfileData.from_file(files[0]).planes
             for ln in p.lines]
    lines = [ln for ln in lines if ln]
    assert len(lines) == 1, [sorted({e[0] for e in ln}) for ln in lines]
    evs = lines[0]
    by = {n: [e for e in evs if e[0] == n]
          for n in ("route", "route.window") + PIPELINE}
    assert len(by["route"]) == 1
    windows = by["route.window"]
    assert len(windows) == len(f.route.stats) >= 2
    assert all(_inside(w, by["route"][0]) for w in windows)
    for w in windows:
        inner = {n: [e for e in by[n] if _inside(e, w)
                     and e[3].get("stage") != "summary"]
                 for n in PIPELINE}
        plan, disp, stall = (inner[n] for n in PIPELINE)
        assert plan and disp and len(stall) == 1, (w, inner)
        for e in plan + disp + stall:
            assert e[3]["window"] == w[3]["window"]
            assert e[3]["route"] == w[3]["route"]
        # in time order: a rung is planned, then dispatched; the stall
        # comes after the last dispatch
        assert plan[0][1] + plan[0][2] <= disp[0][1]
        assert disp[-1][1] + disp[-1][2] <= stall[0][1]
        assert w[3]["first_iter"] <= w[3]["last_iter"]
    # every pipeline span lies in some window (a deferred summary in
    # the next one); the host's control step follows each window and
    # lies in none
    for n in PIPELINE:
        assert all(any(_inside(e, w) for w in windows) for e in by[n])
    control = [e for e in evs if e[0] == "route.pipeline.control"]
    assert [e[3]["window"] for e in control] == \
        [w[3]["window"] for w in windows]
    assert not any(_inside(e, w) for e in control for w in windows)
    assert all(_inside(e, by["route"][0]) for e in control)
    # a first call says so
    assert any(e[3].get("first") for e in by["route.pipeline.dispatch"]) \
        or get_metrics().counter("route.dispatch.compiles").value == 0


def test_chrome_trace_still_checks_and_holds_the_new_spans(placed,
                                                           tmp_path):
    tracer = Tracer()
    set_tracer(tracer)
    try:
        f = _route(placed)
    finally:
        set_tracer(None)
    assert f.route.success
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    assert report.main([str(path), "--check"]) == 0
    evs = [e for e in tracer.events if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert set(PIPELINE) | {"route", "route.window",
                            "route.pipeline.exec"} <= names
    windows = [e for e in evs if e["name"] == "route.window"]
    assert len(windows) == len(f.route.stats)
    for w in windows:
        a = w["args"]
        # the live span's own args, and the deferred ledger on the
        # SAME event
        assert a["first_iter"] <= a["last_iter"] and "window" in a
        assert a["relax_steps"] == (a["relax_steps_useful"]
                                    + a["relax_steps_wasted"])
        assert a["K"] == a["last_iter"] - a["first_iter"] + 1
    # no synthetic per-iteration spans inside a K>1 window
    iters = [e for e in evs if e["name"] == "route.iter"]
    assert len(iters) == sum(1 for w in windows if w["args"]["K"] == 1)
    assert all("approx" not in e["args"] for e in iters)
    assert sum(w["args"]["relax_steps"] for w in windows) \
        == f.route.total_relax_steps


def test_dispatch_counters_are_timed_where_the_work_happens(
        placed, monkeypatch):
    import parallel_eda_tpu.route.router as router_mod
    from parallel_eda_tpu.route import planes

    reg = get_metrics()
    first = reg.counter("route.dispatch.first_call_ms_total")
    # zero at a route's start: what the first dispatch finds, before
    # anything of this route was dispatched
    reg.gauge("route.pipeline.dispatch_ms_total").set(123.0)
    real = planes.route_window_planes
    found = []

    def spy(*a, **k):
        found.append(reg.gauge("route.pipeline.dispatch_ms_total").value)
        return real(*a, **k)

    monkeypatch.setattr(planes, "route_window_planes", spy)
    assert _route(placed).route.success
    monkeypatch.undo()
    assert found[0] == 0.0 and all(
        a <= b for a, b in zip(found, found[1:]))

    # the seen-set is process state: other tests of this worker may
    # have dispatched these very variants
    warm = set(router_mod._DISPATCH_VARIANTS)
    router_mod._DISPATCH_VARIANTS.clear()
    try:
        f1 = _route(placed)
        v = reg.values("route.")
        total = v["route.pipeline.dispatch_ms_total"]
        assert 0.0 < v["route.pipeline.dispatch_ms"] <= total
        # dispatch is inside the window's host time
        assert total <= (v["route.pipeline.host_plan_ms_total"]
                         + v["route.pipeline.stall_ms_total"])
        assert v["route.dispatch.compiles"] > 0
        after_first = first.value
        assert 0.0 < after_first <= total + 1e-6

        f2 = _route(placed)
        assert f2.route.wirelength == f1.route.wirelength
        assert first.value == after_first
        assert reg.gauge("route.pipeline.dispatch_ms_total").value > 0.0
    finally:
        router_mod._DISPATCH_VARIANTS.update(warm)
