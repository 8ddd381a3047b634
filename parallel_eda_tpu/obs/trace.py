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
- disabled = no-op: with no tracer installed, span() hands back one
  shared null context and does nothing else (no allocation, no file,
  no clock read), like the reference's compiled-out log macros.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional


class _NullSpan:
    """Shared do-nothing context: the disabled-tracer fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "_t_in")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._t_in = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t = time.perf_counter()
        self.tracer.add_complete(self.name, self._t_in, t - self._t_in,
                                 cat=self.cat, **self.args)
        return False


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

    def span(self, name: str, cat: str = "flow", **args) -> _Span:
        return _Span(self, name, cat, args)

    def add_complete(self, name: str, t_abs: float, dur: float,
                     cat: str = "flow", **args) -> None:
        """Record a complete event from absolute perf_counter seconds."""
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": (t_abs - self.t0) * 1e6, "dur": max(0.0, dur) * 1e6,
              "pid": 1, "tid": threading.get_ident() & 0x7FFFFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

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
    """`with span("route.iter", it=3):` — records a complete event on
    the installed tracer; a shared no-op context when tracing is off."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return t.span(name, cat=cat, **args)


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

    def __exit__(self, *exc):
        r = self.inner.__exit__(*exc)
        if self.times is not None:
            self.times[self.name] = time.perf_counter() - self._t_in
        return r


def stage(name: str, times: Optional[dict] = None, **args) -> _StageCtx:
    """Flow-stage span ("pack", "place", "route", ...) that keeps the
    legacy times dict populated with the same clock."""
    return _StageCtx(name, times, span(name, cat="stage", **args))


# ---- JAX compile-phase capture (/jax/core/compile/* monitoring) ----

_compile_s = 0.0
_capture_on = False


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if not event.startswith("/jax/core/compile/"):
        return
    global _compile_s
    _compile_s += duration
    t = _tracer
    if t is not None:
        # the listener fires at phase END with only a duration: anchor
        # the span backwards from now (the phase ran synchronously, so
        # it nests inside whatever host span is open)
        name = event.rsplit("/", 1)[1]
        if name.endswith("_duration"):
            name = name[: -len("_duration")]
        t.add_complete("jax.compile." + name,
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


def reset_compile_seconds() -> None:
    """Zero the compile-seconds accumulator.  The benches call this at
    the warmup/measured boundary (alongside MetricsRegistry.reset) so
    a steady-state row's compile split is the measured run's compile
    time alone, never the warmup's folded in."""
    global _compile_s
    _compile_s = 0.0
