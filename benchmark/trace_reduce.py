"""From a profiler trace to device busy time, idle share, an op table
and idle gaps named by what the host was doing.

The reduction works on a neutral form so that it can be checked on a
small trace written by hand (``fixtures/two_ops_one_gap.json``):

    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [[name, start_ns, duration_ns],
                                     ...]}]}, ...]

``planes_from_xplane`` brings a ``.xplane.pb`` of ``jax.profiler`` into
that form with nothing but JAX.  Host events are kept only where the
benchmark wrote them (names starting with ``bench.``): a trace of one
route holds a million device events and the host's own are not read.

Busy is the union of the intervals in which an operation ran on the
device, averaged over the device planes; idle share is 1 - busy /
window.  The window is the ``bench.traced_window`` span the harness
opens around what it traces, and without it the extent of the events.
An op's time in the table is its SELF time: a ``while`` that spans its
body's ops on the same line is charged only what they leave uncovered.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")   # first one present is read
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced_window"
UNNAMED = "unattributed"

Interval = Tuple[float, float]


def planes_from_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in OP_LINES:
                    # kept by name only, so that a run can print which
                    # lines the device plane had
                    lines.append({"name": line.name, "events": []})
                    continue
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
            else:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
                if not events:
                    continue
            lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def host_spans_plane(planes: List[dict], spans: Sequence[Sequence],
                     window_t0: float) -> dict:
    """Spans the harness timed on the host's clock -- (name, t0, t1) in
    seconds -- as a plane on the trace's clock.  The window span is on
    both clocks (``window_t0`` is the host's reading at its start), and
    that pins one to the other."""
    window_ns = next((e[1] for p in planes for ln in p["lines"]
                      for e in ln["events"] if e[0] == WINDOW_SPAN), None)
    events = [] if window_ns is None else [
        [name, window_ns + (t0 - window_t0) * 1e9, (t1 - t0) * 1e9]
        for name, t0, t1 in spans]
    return {"name": "/host:bench", "lines": [{"name": "spans",
                                              "events": events}]}


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def self_times(events: Sequence[Sequence]) -> Dict[str, float]:
    """{name: ns} with each event charged its duration less what the
    events nested inside it (same line) cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    total: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(0.0, own)

    for name, start, dur in order:
        close(start)
        if stack:
            # a child takes its (clipped) duration out of its parent
            stack[-1][2] -= min(dur, max(0.0, stack[-1][1] - start))
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def _op_line(plane: dict) -> Optional[dict]:
    by_name = {ln["name"]: ln for ln in plane["lines"]}
    for name in OP_LINES:
        if by_name.get(name, {}).get("events"):
            return by_name[name]
    return None


def reduce(planes: List[dict], top: int = 10) -> dict:
    """The reduction.  An idle gap may be attributed to any ``bench.``
    span of a host plane but the window span itself."""
    host_events = [e for p in planes
                   if not p["name"].startswith(DEVICE_PREFIX)
                   for ln in p["lines"] for e in ln["events"]
                   if e[0].startswith(HOST_PREFIX)]
    dev_planes = [p for p in planes
                  if p["name"].startswith(DEVICE_PREFIX)]
    op_lines = [(p["name"], _op_line(p)) for p in dev_planes]
    op_lines = [(n, ln) for n, ln in op_lines if ln is not None]

    window = None
    for name, start, dur in host_events:
        if name == WINDOW_SPAN:
            window = (start, start + dur)
    if window is None:
        every = [e for _, ln in op_lines for e in ln["events"]] \
            + host_events
        window = (min(e[1] for e in every),
                  max(e[1] + e[2] for e in every)) if every else (0.0, 0.0)
    lo, hi = window
    window_ns = max(hi - lo, 0.0)

    busy_each: List[float] = []
    merged_first: List[Interval] = []
    ops: Dict[str, float] = {}
    n_events = 0
    for i, (_, ln) in enumerate(op_lines):
        evs = ln["events"]
        n_events += len(evs)
        merged = clip(merge([(s, s + d) for _, s, d in evs]), lo, hi)
        busy_each.append(sum(b - a for a, b in merged))
        if i == 0:
            merged_first = merged
        for name, ns in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + ns
    n_dev = max(1, len(op_lines))
    busy_ns = sum(busy_each) / n_dev

    # idle gaps of the first device, longest first, each named by the
    # benchmark-side span that covers most of it (the shortest such
    # span on a tie: the innermost)
    gaps: List[Interval] = []
    cur = lo
    for a, b in merged_first:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur and op_lines:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, s + d) for n, s, d in host_events
             if n != WINDOW_SPAN]
    idle_gaps = []
    for a, b in gaps[:top]:
        best, best_key = UNNAMED, (0.0, 0.0)
        for n, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0 and (ov, -(e - s)) > best_key:
                best, best_key = n, (ov, -(e - s))
        idle_gaps.append([best, (b - a) / 1e9])

    device_ops = sorted(([n, ns / 1e9] for n, ns in ops.items()),
                        key=lambda r: -r[1])
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": (1.0 - busy_ns / window_ns) if window_ns else None,
        "device_ops": device_ops[:top],
        "idle_gaps": idle_gaps,
        "n_device_planes": len(dev_planes),
        "n_device_events": n_events,
        "lines_seen": {p["name"]: [ln["name"] for ln in p["lines"]]
                       for p in dev_planes},
    }
