"""Span-based tracer exporting Chrome trace-event JSON.

The reference instrumented its routers with LTTng tracepoints
(parallel_route/tp.h: route_start/route_end, net_route, heap ops) and
viewed them in Trace Compass; the TPU flow's equivalent view is the
Chrome trace-event format, openable in Perfetto (ui.perfetto.dev) or
chrome://tracing.  Spans are complete ("X") events with microsecond
timestamps from one process-wide perf_counter origin, so mdclog records
stamped from the same origin (MdcLogger t0) line up exactly.

Two things the tp.h design could not give us come for free here:

- compile vs execute: jax.monitoring publishes per-phase compilation
  durations (/jax/core/compile/*); the listener turns each into a
  "jax.compile.*" span, so XLA compilation — tens of seconds per
  window program — is separable from iteration timings instead of
  polluting the first window of every route.
- disabled = no-op: with no tracer installed and no profiler session
  running, span() hands back one shared null context (one TraceMe
  level check, no allocation, no file, no clock read), like the
  reference's compiled-out log macros.

span() is the one way to open a span and it has two sinks: the
installed Tracer (Chrome trace-event JSON on the host's clock) and, as
a ``jax.profiler.TraceAnnotation`` of the same name and args, whatever
profiler session is running -- so the program's own spans land on the
profiler's clock beside the device ops.  The device side of the same
picture is DEVICE_SCOPES: the fixed ``jax.named_scope`` vocabulary of
the window program, the names a trace reduction keys on.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional


# the window program's stages on the device: every op of
# route_window_planes lies under exactly one
# top-level name (nested ones only under route.dev.relax and
# route.dev.sta).  The names
# are what a reduction of a device trace keys on (benchmark/
# scope_reduce.py, OBSERVABILITY.md), so they outlive any refactor of
# what is inside them; tests/test_device_scopes.py holds the compiled
# programs to the list.
DEVICE_SCOPES = (
    "route.dev.ripup",           # batch rows, dirty predicate, rip-up
    "route.dev.cost_fields",     # congestion cost into cell space, seeds
    "route.dev.relax",           # the min-plus relaxation
    "route.dev.relax.scan",      #   directional scans of one sweep
    "route.dev.relax.turn",      #   turn candidates of one sweep
    "route.dev.relax.crop",      #   crop to / scatter from net tiles
    "route.dev.sink_pick",       # sink candidates, direct, the pick
    "route.dev.traceback",       # pointer chase, path rows, store
    "route.dev.tree_grow",       # grow the tree in cell space
    "route.dev.commit",          # occupancy commit, scatter to state
    "route.dev.history",         # per-iteration acc / pres escalation
    "route.dev.sta",             # the fused STA
    "route.dev.sta.wide_fold",   #   in-edges past the ELL (scatter-max)
    "route.dev.mis_colors",      # conflict colouring of the dirty set
    "route.dev.window_summary",  # packed status / scal, rung stacking
)


def device_scope(name: str):
    """``jax.named_scope`` of one declared DEVICE_SCOPES name: the only
    way the package names device work.  Metadata only -- the compiled
    program and its compile-cache key do not change."""
    if name not in DEVICE_SCOPES:
        raise KeyError(f"{name!r} is not in obs.trace.DEVICE_SCOPES")
    import jax
    return jax.named_scope(name)


class _NullSpan:
    """Shared do-nothing context: the fast path with no sink active."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    event = None

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _NoAnnotation:
    """Stand-in for TraceAnnotation where jax is not importable (tools,
    docs builds): never enabled, so never constructed."""

    @staticmethod
    def is_enabled() -> bool:
        return False


_annotation = None      # jax.profiler.TraceAnnotation, bound on first use


def _annotation_cls():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except ImportError:
            _annotation = _NoAnnotation
    return _annotation


class _Span:
    """One open span, written to both sinks: the profiler session (a
    TraceAnnotation of the same name and args, or None when no session
    runs) and the Tracer (or None when none is installed)."""
    __slots__ = ("tracer", "name", "cat", "args", "event", "_ann",
                 "_t_in")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: dict, ann=None):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.event = None       # the tracer's event, once closed
        self._ann = ann

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t_in = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Args known only inside the span (``first=True`` of a
        dispatch that turned out to compile)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_annotation_args(args))

    def __exit__(self, *exc):
        t = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.tracer is not None:
            self.event = self.tracer.add_complete(
                self.name, self._t_in, t - self._t_in, cat=self.cat,
                **self.args)
        return False


def _annotation_args(args: dict) -> dict:
    """Span args as profiler stats: the profiler keeps numbers and
    strings, so anything else (None, a tile's list) goes as its str."""
    return {k: v if isinstance(v, (bool, int, float, str)) else str(v)
            for k, v in args.items()}


class Tracer:
    """In-memory span recorder; export() writes the trace-event file.

    All timestamps are seconds on time.perf_counter relative to the
    tracer's t0 (converted to µs at export).  Thread-safe appends; tid
    is the OS thread ident so Perfetto draws one track per thread.
    """

    def __init__(self, worker: str = ""):
        self.t0 = time.perf_counter()
        self.worker = str(worker)
        self.events: list = []
        self.declared_counter_tracks: set = set()
        self._lock = threading.Lock()

    def span(self, name: str, cat: str = "flow", **args):
        """A span recorded on THIS tracer (and in a running profiler
        session); the package's own call sites use the module's
        span(), which finds the installed tracer itself."""
        return _open_span(self, name, cat, args)

    def add_complete(self, name: str, t_abs: float, dur: float,
                     cat: str = "flow", **args) -> dict:
        """Record a complete event from absolute perf_counter seconds.
        Returns the event: a caller that learns more about the span
        later (the window's deferred ledger) adds it to its args."""
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": (t_abs - self.t0) * 1e6, "dur": max(0.0, dur) * 1e6,
              "pid": 1, "tid": threading.get_ident() & 0x7FFFFFFF,
              "args": args}
        with self._lock:
            self.events.append(ev)
        return ev

    def mark(self, name: str, t_begin: float, t_end: float,
             cat: str = "flow", **args) -> None:
        """Record a complete event from a measured [t_begin, t_end)
        perf_counter interval — the async-pipeline span shape, where
        the end is a captured completion time rather than "now"
        (add_complete with the duration computed here, so call sites
        cannot flip the operands)."""
        self.add_complete(name, t_begin, t_end - t_begin, cat=cat,
                          **args)

    def instant(self, name: str, cat: str = "flow", **args) -> None:
        ev = {"name": name, "ph": "i", "cat": cat, "s": "t",
              "ts": (time.perf_counter() - self.t0) * 1e6,
              "pid": 1, "tid": threading.get_ident() & 0x7FFFFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def beacon(self, **args) -> None:
        """Clock-sync beacon: one instant carrying a paired absolute
        wall-clock / perf_counter sample taken back to back.  A merge
        tool (tools/trace_merge.py) uses the (wall, ts) pairs to place
        each per-process shard's private perf_counter origin on the
        shared wall timeline; emitting one at start and one per cycle
        both anchors the shard and exposes wall-clock steps as beacon
        origin spread (the residual-skew bound the fleet doctor
        checks)."""
        self.instant("route.trace.beacon", cat="trace",
                     wall=time.time(), perf=time.perf_counter(), **args)

    def declare_counter_tracks(self, names) -> None:
        """Declare counter tracks that SHOULD exist in this shard even
        if no sample was ever recorded (e.g. place.t in a route-only
        run).  Exported as "declaredCounterTracks" so trace_report can
        tell an empty-but-declared track from an unknown name."""
        with self._lock:
            self.declared_counter_tracks.update(str(n) for n in names)

    def counter(self, name: str, value, cat: str = "metrics") -> None:
        """Record one sample of a Perfetto counter track ("C" event)
        on the span clock origin, so trajectories (overuse, pres_fac,
        stall time, SA temperature) render as stepped tracks aligned
        with the spans of the same run."""
        ev = {"name": name, "ph": "C", "cat": cat,
              "ts": (time.perf_counter() - self.t0) * 1e6,
              "pid": 1, "tid": threading.get_ident() & 0x7FFFFFFF,
              "args": {"value": float(value)}}
        with self._lock:
            self.events.append(ev)

    def total(self, name_prefix: str) -> float:
        """Sum of span durations (seconds) whose name starts with
        name_prefix — e.g. total("jax.compile") for the compile split."""
        with self._lock:
            return sum(e.get("dur", 0.0) for e in self.events
                       if e["ph"] == "X"
                       and e["name"].startswith(name_prefix)) / 1e6

    def export(self, path: str, atomic: bool = False) -> None:
        """Write the shard.  atomic=True goes through tmp+os.replace so
        a reader (or the fleet merge after a SIGKILL) never sees a torn
        file — the per-cycle shard export depends on this: the last
        fully written cycle survives the kill."""
        with self._lock:
            evs = sorted(self.events, key=lambda e: e["ts"])
            tracks = sorted(self.declared_counter_tracks)
        pname = "parallel_eda_tpu" + (f" {self.worker}" if self.worker
                                      else "")
        meta = [{"name": "process_name", "ph": "M", "pid": 1, "ts": 0,
                 "args": {"name": pname}}]
        doc = {"traceEvents": meta + evs, "displayTimeUnit": "ms"}
        if self.worker:
            doc["worker"] = self.worker
        if tracks:
            doc["declaredCounterTracks"] = tracks
        if atomic:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        else:
            with open(path, "w") as f:
                json.dump(doc, f)


class FlightRecorder:
    """Always-on bounded ring of recent lifecycle notes and metric
    deltas for ONE worker — the black box that survives into the diag
    bundle when a job dies.

    Deliberately independent of the Tracer: the ring costs one deque
    append per note and exists even when no trace sink is configured
    (the tracer's null fast path stays a true no-op; the recorder is
    only instantiated by the daemon layer, never by plain library
    usage).  No metrics-registry import either — obs/metrics.py imports
    this module, so the dependency must stay one-way."""

    def __init__(self, capacity: int = 256, clock=time.monotonic,
                 wall=time.time):
        self.capacity = max(1, int(capacity))
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self.total = 0

    def note(self, kind: str, **fields) -> None:
        ev = {"kind": kind, "mono": round(self._clock(), 6),
              "wall": round(self._wall(), 6)}
        ev.update(fields)
        with self._lock:
            self._ring.append(ev)
            self.total += 1

    def snapshot(self) -> dict:
        """Point-in-time copy for the diag bundle: the ring's events
        oldest-first plus how much history fell off the end."""
        with self._lock:
            events = list(self._ring)
            total = self.total
        return {"capacity": self.capacity, "recorded": total,
                "dropped": max(0, total - len(events)),
                "events": events}


# ---- process-wide tracer + the disabled fast path ----

_tracer: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    return _tracer


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Install (or clear, with None) the process tracer.  Installing a
    real tracer also hooks the JAX compile-phase listener."""
    global _tracer
    _tracer = tracer
    if tracer is not None:
        enable_compile_capture()


def span(name: str, cat: str = "flow", **args):
    """`with span("route.window", window=3):` -- THE way to open a
    span.  Two sinks: a running profiler session gets a
    ``jax.profiler.TraceAnnotation`` of the same name and args (on the
    profiler's clock, beside the device ops), the installed tracer a
    complete event.  With neither active the cost is one TraceMe level
    check and the shared no-op context comes back."""
    return _open_span(_tracer, name, cat, args)


def _open_span(t: Optional[Tracer], name: str, cat: str, args: dict):
    cls = _annotation_cls()
    if cls.is_enabled():
        return _Span(t, name, cat, args,
                     cls(name, **_annotation_args(args)))
    if t is None:
        return _NULL_SPAN
    return _Span(t, name, cat, args)


class _StageCtx:
    """span() that ALSO writes its duration into a stage->seconds dict
    (FlowResult.times compatibility: the dict becomes a derived view of
    the spans instead of a parallel ad-hoc time.time() ledger)."""
    __slots__ = ("name", "times", "inner", "_t_in")

    def __init__(self, name: str, times: Optional[dict], inner):
        self.name = name
        self.times = times
        self.inner = inner

    def __enter__(self):
        self._t_in = time.perf_counter()
        self.inner.__enter__()
        return self

    def set(self, **args) -> None:
        """Args known only inside the stage (the span's ``set``)."""
        self.inner.set(**args)

    def __exit__(self, *exc):
        r = self.inner.__exit__(*exc)
        if self.times is not None:
            self.times[self.name] = time.perf_counter() - self._t_in
        return r


def stage(name: str, times: Optional[dict] = None,
          key: Optional[str] = None, **args) -> _StageCtx:
    """Flow-stage span ("pack", "place", "route", ...) that keeps the
    legacy times dict populated with the same clock, under ``key``
    where the dict's name for the stage is not the span's."""
    return _StageCtx(key or name, times, span(name, cat="stage", **args))


# ---- JAX compile-phase capture (/jax/core/compile/* monitoring) ----

_compile_s = 0.0
_phase_s: dict = {}     # seconds per compile phase, by short name
_capture_on = False
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if event == _CACHE_READ:
        # inside backend_compile: a split of it, not more compile time
        _phase_s["cache_read"] = _phase_s.get("cache_read", 0.0) + duration
        return
    if not event.startswith("/jax/core/compile/"):
        return
    global _compile_s
    _compile_s += duration
    phase = event.rsplit("/", 1)[1].removesuffix("_duration")
    _phase_s[phase] = _phase_s.get(phase, 0.0) + duration
    t = _tracer
    if t is not None:
        # the listener fires at phase END with only a duration: anchor
        # the span backwards from now (the phase ran synchronously, so
        # it nests inside whatever host span is open)
        t.add_complete("jax.compile." + phase,
                       time.perf_counter() - duration, duration,
                       cat="jax.compile")


def enable_compile_capture() -> None:
    """Register the jax.monitoring duration listener (once).  Safe to
    call without a tracer: the listener then only feeds the process
    compile-seconds accumulator (compile_seconds()), which bench rows
    use for their compile-vs-execute attribution."""
    global _capture_on
    if _capture_on:
        return
    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _capture_on = True
    except Exception:
        # no jax in this interpreter (tools, docs builds): tracing of
        # host spans still works, there is just nothing to compile
        pass


def compile_seconds() -> float:
    """Total JAX compile-phase seconds observed since capture was
    enabled (monotone between resets; diff around a region to
    attribute it)."""
    return _compile_s


def compile_phases() -> dict:
    """Seconds per compile phase since capture was enabled, by the
    event's short name (``jaxpr_trace``, ``jaxpr_to_mlir_module``,
    ``backend_compile``, and ``cache_read``: the part of
    ``backend_compile`` spent reading the persistent cache).  Diff
    around a first dispatch for its split."""
    return dict(_phase_s)


def reset_compile_seconds() -> None:
    """Zero the compile-seconds accumulator.  The benches call this at
    the warmup/measured boundary (alongside MetricsRegistry.reset) so
    a steady-state row's compile split is the measured run's compile
    time alone, never the warmup's folded in."""
    global _compile_s
    _compile_s = 0.0
