"""The harness: everything a run does that is not particular to one
configuration, one traffic mix or one per-layer metric.

A cell is found by its name in ``BENCHMARK.json``.  Its configuration
is the file the manifest names; its traffic mix, its driver and the
reader of each per-layer metric are files the harness finds BY NAME
under the manifest's ``paths`` (and, failing that, beside this file):

    configs/<config>.json         the deployment as it is run
    traffic/<traffic>.json        the mix's parameters, and "driver"
    drivers/<driver>.py           run(cell, env) -> Outcome
    layer_metrics/<metric>.py     read(ctx) -> number or None
    problems/<builder>.py         build(config, chan_width) -> problem

so a later PR adds a cell by adding files and manifest entries, never
by editing one.  ``run_cell`` is the whole of a run except the look for
a chip, which only ``run.py``'s ``main`` makes: the CPU tests call
``run_cell`` at tiny sizes, and what they get names ``platform: cpu``
and carries no metric at all.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# what a run writes (inboxes, traces) goes here: inside the checkout,
# at a fixed path, listed in .gitignore
WORK_DIR = os.path.join(REPO, ".bench_work")


# ------------------------------------------------------------ manifest


@dataclass
class Cell:
    """One entry of ``workloads`` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    search: List[str]               # directories searched by name

    def find(self, kind: str, name: str, ext: str) -> str:
        return find_file(self.search, kind, name, ext)


def find_file(search: List[str], kind: str, name: str, ext: str) -> str:
    """``<dir>/<kind>/<name><ext>`` in the first directory that has it."""
    for base in search:
        path = os.path.join(base, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{name}{ext} under any of {search}")


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def search_dirs(manifest: dict, root: str) -> List[str]:
    dirs = [os.path.join(root, p) for p in manifest["paths"]]
    return dirs + [d for d in (HERE,) if d not in dirs]


def load_cell(manifest: dict, root: str, workload: str) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    search = search_dirs(manifest, root)
    with open(os.path.join(root, configs[w["config"]]["file"])) as fh:
        config = json.load(fh)
    with open(find_file(search, "traffic", w["traffic"], ".json")) as fh:
        traffic = json.load(fh)
    return Cell(name=w["name"], chips=int(w["chips"]), config=config,
                traffic=traffic, search=search)


def load_module(path: str):
    """A driver or a reader, imported from its file (their names carry
    dots, so they are not importable as modules by name)."""
    tag = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(f"_bench_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(manifest: dict, group: str, workload: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports.
    An end-to-end metric without a ``workloads`` key belongs to every
    cell; a per-layer metric always lists its cells."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload]
                                 if group == "end_to_end" else [])]


def find_reader(search: List[str], metric: str) -> str:
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``,
    or that of the name less its last dotted part, and so on.  So one
    quantity that the manifest splits by the end-to-end metric it moves
    (``device.idle_share.route``, ``device.idle_share.serve``) is read
    by one file (``device.idle_share.py``)."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        try:
            return find_file(search, "layer_metrics",
                             ".".join(parts[:n]), ".py")
        except FileNotFoundError:
            continue
    raise FileNotFoundError(
        f"no layer_metrics/{metric}.py (or a dotted prefix of it) "
        f"under any of {search}")


# -------------------------------------------------------------- device


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    """The look for a chip: JAX's own devices must be ``chips`` TPUs.
    Nothing relaxes it, and nothing here selects a platform."""
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX reports platform "
                         f"{info['platform']!r}")
    if info["count"] != chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"JAX reports {info['count']}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peak_hbm_bytes_per_s(kind: str) -> float:
    """Published peak HBM bandwidth of a ``device_kind``, from
    ``peaks.json``.  A kind that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)["device_kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r}: "
                       f"add it, with its source, to benchmark/peaks.json")
    return float(table[kind]["hbm_bytes_per_s"])


# ------------------------------------------------------- run-time state


def fresh_dir(base: str, *parts: str) -> str:
    """An empty directory of the run's own under ``base``."""
    path = os.path.join(base, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def fresh_metrics():
    """A metrics registry of this run's own, installed as the
    program's.  Left at its default (no per-iteration history)."""
    from parallel_eda_tpu.obs import MetricsRegistry, set_metrics

    return set_metrics(MetricsRegistry())


class Tracing:
    """The profiler around a slice of the window, and benchmark-side
    spans on its clock.  A whole route is five million device events,
    more than a run has time to read, so a run traces a SLICE: a helper
    thread starts the profiler ``offset_s`` into the window and stops
    it ``seconds`` later, whatever the main thread is doing; the trace
    is read after the window.  With tracing off every method is free."""

    def __init__(self, on: bool, work_dir: str):
        self.on = bool(on)
        self.dir = fresh_dir(work_dir, "trace") if on else ""
        self.reduced: Optional[dict] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._spans: List[tuple] = []   # (name, t0, t1) perf_counter
        self._window_t0 = 0.0           # perf_counter at the window span

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark-side span (``bench.route``, ...), kept on the
        host's clock and laid onto the profiler's through the window
        span: a span that opened before the slice began would be lost
        to the profiler's own annotations."""
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._spans.append((name, t0, time.perf_counter()))

    def _slice(self, offset_s: float, seconds: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, offset_s))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no Python call stacks
            opts.host_tracer_level = 1      # user annotations only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                # the traced window on the profiler's own clock
                with jax.profiler.TraceAnnotation(
                        trace_reduce.WINDOW_SPAN):
                    self._window_t0 = time.perf_counter()
                    time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:      # re-raised by finish()
            self._error = e

    def begin_slice(self, offset_s: float, seconds: float) -> None:
        """Called at the window's start: arm the slice."""
        if not self.on:
            return
        self._thread = threading.Thread(
            target=self._slice, args=(offset_s, seconds),
            name="bench-trace")
        self._thread.start()

    def finish(self) -> None:
        """After the window: wait for the slice, reduce what it wrote."""
        if self._thread is None:
            return
        self._thread.join()
        self._thread = None
        if self._error is not None:
            raise self._error
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"profiler wrote no trace under {self.dir}")
        t0 = time.perf_counter()
        planes = trace_reduce.planes_from_xplane(files[-1])
        planes.append(trace_reduce.host_spans_plane(
            planes, self._spans, self._window_t0))
        self.reduced = trace_reduce.reduce(planes)
        self.reduced["reduce_s"] = time.perf_counter() - t0
        # traces are large: keep the reduction, drop the file
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Check:
    """One number compared, beside its limit."""
    name: str
    value: Any
    limit: Any
    ok: bool

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} against limit "
                f"{self.limit!r} -> {'ok' if self.ok else 'NOT ok'}")


def at_most(name: str, value: float, limit: float) -> Check:
    return Check(name, value, limit, bool(value <= limit))


def exactly(name: str, value, want) -> Check:
    return Check(name, value, want, bool(value == want))


@dataclass
class Env:
    """What the harness hands a driver."""
    seed: int
    seconds: float
    tracing: Tracing
    t_start: float                  # perf_counter at process start
    work_dir: str                   # the run's own, emptied at start
    # test-only: RouterOpts fields forced on the timed path (the
    # lower-precision control).  No command-line option sets it.
    router_overrides: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Outcome:
    """What a driver hands back."""
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]
    checks: List[Check]
    # what the per-layer readers read: the window's routes or jobs,
    # the metrics-registry and SLO snapshots, the reduced trace
    ctx: Dict[str, Any] = field(default_factory=dict)


def say(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


# ----------------------------------------------------------------- run


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: Optional[float] = None,
             work_dir: Optional[str] = None,
             router_overrides: Optional[dict] = None) -> dict:
    """Everything a run does but the look for a chip.  Returns the
    result object; ``run.py`` prints it as the last line."""
    from parallel_eda_tpu.route.router import (
        enable_persistent_compile_cache)

    t_start = time.perf_counter() if t_start is None else t_start
    manifest = load_manifest(root)
    cell = load_cell(manifest, root, workload)
    device = device_info()
    on_chip = device["platform"] == "tpu"
    cache_dir = enable_persistent_compile_cache()
    say(phase="start", workload=workload, seed=seed, seconds=seconds,
        trace=int(trace), device=device, compile_cache_dir=cache_dir)

    driver = load_module(cell.find("drivers", cell.traffic["driver"],
                                   ".py"))
    work_dir = fresh_dir(work_dir or WORK_DIR, workload)
    env = Env(seed=int(seed), seconds=float(seconds),
              tracing=Tracing(trace, work_dir), t_start=t_start,
              work_dir=work_dir,
              router_overrides=dict(router_overrides or {}))
    out: Outcome = driver.run(cell, env)

    for c in out.checks:
        print(c.line(), flush=True)
    correct = all(c.ok for c in out.checks)

    ctx = dict(out.ctx)
    # the drivers read the peak when the window closes, before the
    # reference's own work touches the device
    ctx.setdefault("memory_peak_bytes", memory_peak_bytes())
    ctx.update(trace=env.tracing.reduced, device=device, cell=cell,
               peak_hbm_bytes_per_s=(peak_hbm_bytes_per_s(device["kind"])
                                     if on_chip else None))
    values: Dict[str, float] = {}
    if trace:
        for m in metrics_of(manifest, "per_layer", workload):
            reader = load_module(find_reader(cell.search, m["name"]))
            v = reader.read(ctx)
            if v is not None:
                values[m["name"]] = float(v)
    else:
        values = dict(out.end_to_end, setup_s=out.setup_s)
    group = "per_layer" if trace else "end_to_end"
    listed = metrics_of(manifest, group, workload)
    units = {m["name"]: m["unit"] for m in listed}
    missing = sorted(set(units) - set(values)) if not trace else []
    if missing:
        raise RuntimeError(f"driver reported no {missing}")

    dev = dict(device, memory_peak_bytes=ctx["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": {}, "device": dev}
    if on_chip:
        # a time from a CPU is never written under a metric's name:
        # off the chip the line keeps its keys and carries no metric
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units}
    else:
        counts = {m["name"] for m in listed if m["unit"] == "count"
                  and m["source"] == "program_counter"}
        result["rehearsal"] = {
            "counts": {k: v for k, v in values.items() if k in counts},
            "withheld": sorted(set(values) - counts)}
    red = env.tracing.reduced
    if red is not None:
        say(phase="trace", **{k: red[k] for k in (
            "busy_s", "window_s", "idle_share", "n_device_planes",
            "n_device_events", "reduce_s", "lines_seen")})
    if trace and red is not None and on_chip:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    return result
