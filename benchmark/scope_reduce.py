"""From a profiler trace to device time per declared scope of the
window program, and idle gaps named by the program's own host spans.

The program names its device stages with ``jax.named_scope`` from one
fixed vocabulary (``route.dev.*``, ``parallel_eda_tpu/obs/trace.py``
``DEVICE_SCOPES``) and writes its host spans (``route.*``, ``serve.*``)
into the profiler's trace.  This module reads both; it knows the
vocabulary's PREFIX and nothing else of the program.

The neutral form is ``trace_reduce``'s with one more field on a device
event, its scope path:

    [name, start_ns, duration_ns, "route.dev.relax/route.dev.relax.scan"]

-- the ``route.dev.*`` parts of the op's ``op_name``, outermost first,
"" where it has none.  An op belongs to its INNERMOST scope; a scope's
top level is its first three dotted parts.  Time is SELF time, as in
``trace_reduce``: a ``while`` that spans its body's ops is charged what
they leave uncovered, so the rows of the table partition busy time.

``planes_from_xplane`` reads the ``.xplane.pb`` itself (a few protobuf
fields, below): on the TPU the op_name is the stat ``tf_op`` of an
event's METADATA, which ``jax.profiler.ProfileData`` does not show; on
XLA:CPU an op event names its ``hlo_op`` and ``program_id`` and the
op_name is looked up in the HLO module the profiler stores in the
trace's ``/host:metadata`` plane.  The second way also serves a TPU op
without ``tf_op`` (the compiler's own ``while``).
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Tuple

from .trace_reduce import WINDOW_SPAN, clip, merge, self_times

SCOPE_PREFIX = "route.dev."
HOST_PREFIXES = ("bench.", "route.", "serve.")
HOST_STAGE = "route"            # the flow's stage span above route.window
DEVICE_PREFIXES = ("/device:TPU:", "/device:CPU:")
OP_LINE = "XLA Ops"
CPU_PLANE = "/device:CPU:0"     # XLA:CPU's op events, gathered
UNSCOPED = "unscoped"
UNNAMED = "unattributed"


def scope_of(op_name: str) -> str:
    return "/".join(p for p in op_name.split("/")
                    if p.startswith(SCOPE_PREFIX))


def top_level(scope: str) -> str:
    """``route.dev.relax.scan`` -> ``route.dev.relax``."""
    return ".".join(scope.split(".")[:3])


# ------------------------------------------------- protobuf, by hand
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata map,
# 5: stat_metadata map}; XLine{2: name, 3: timestamp_ns, 4: events};
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps, 4: stats};
# XEventMetadata{1: id, 2: name, 4: display_name, 5: stats};
# XStat{1: metadata_id, 2: double, 3: uint64, 4: int64, 5: str,
# 6: bytes, 7: ref (a stat_metadata id whose name is the value)};
# HloProto{1: HloModuleProto{3: computations{2: instructions{1: name,
# 7: OpMetadata{2: op_name}}}}}.


def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        c = buf[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    slice of ``buf`` for anything with a length or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            v = buf[i:i + width]
            i += width
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf8", "replace")


def _stats(raw: List, names: Dict[int, str]) -> dict:
    out = {}
    for buf in raw:
        key = val = None
        for f, v in _fields(buf):
            if f == 1:
                key = names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f in (3, 4):
                val = v
            elif f == 5:
                val = _text(v)
            elif f == 6:
                val = bytes(v)
            elif f == 7:
                val = names.get(v, str(v))
        out[key] = val
    return out


def _map_entry(buf) -> Tuple[int, object]:
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _raw_plane(buf) -> dict:
    name, lines, emeta_raw, names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta_raw.append(v)
        elif f == 5:
            key, val = _map_entry(v)
            names[key] = next((_text(x) for g, x in _fields(val)
                               if g == 2), "")
    emeta = {}
    for entry in emeta_raw:
        key, val = _map_entry(entry)
        md = {"name": "", "display": "", "stats": []}
        for f, v in _fields(val):
            if f == 2:
                md["name"] = _text(v)
            elif f == 4:
                md["display"] = _text(v)
            elif f == 5:
                md["stats"].append(v)
        md["stats"] = _stats(md["stats"], names)
        emeta[key] = md
    return {"name": name, "lines": lines, "emeta": emeta, "names": names}


def _raw_events(line_buf, plane: dict, want_stats: bool):
    """(line name, [(metadata, start_ns, duration_ns, stats)])."""
    name, t0, events = "", 0, []
    for f, v in _fields(line_buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    out = []
    for buf in events:
        mid = off = dur = 0
        stats = []
        for f, v in _fields(buf):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
            elif f == 4 and want_stats:
                stats.append(v)
        out.append((plane["emeta"].get(mid, {"name": "", "display": "",
                                             "stats": {}}),
                    t0 + off / 1e3, dur / 1e3,
                    _stats(stats, plane["names"]) if stats else {}))
    return name, out


def _hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: op_name} of one stored HLO module."""
    out = {}
    for f, module in _fields(memoryview(hlo_proto)):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, instr in _fields(comp):
                if h != 2:
                    continue
                name = op_name = ""
                for k, v in _fields(instr):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for m, x in _fields(v)
                                        if m == 2), "")
                out[name] = op_name
    return out


def planes_from_xplane(path: str) -> List[dict]:
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    raw = [_raw_plane(v) for f, v in _fields(space) if f == 1]

    programs: Dict[str, Dict[str, str]] = {}    # program id -> names

    def op_name_of(program_id, instruction: str) -> str:
        pid = str(program_id)
        if pid not in programs:
            programs[pid] = {}
            for p in raw:
                if p["name"] != "/host:metadata":
                    continue
                md = p["emeta"].get(int(pid)) if pid.isdigit() else None
                proto = (md or {"stats": {}})["stats"].get("Hlo Proto")
                if proto:
                    programs[pid] = _hlo_op_names(proto)
        return programs[pid].get(instruction, "")

    planes, cpu_lines = [], []
    for p in raw:
        device = p["name"].startswith(DEVICE_PREFIXES[0])
        lines = []
        for buf in p["lines"]:
            name, events = _raw_events(buf, p, want_stats=not device)
            if device:
                if name != OP_LINE:
                    lines.append({"name": name, "events": []})
                    continue
                out = []
                for md, start, dur, _ in events:
                    st = md["stats"]
                    op_name = st.get("tf_op") or op_name_of(
                        st.get("program_id", ""), md["display"])
                    scope = scope_of(op_name)
                    # an op without a scope keeps its whole instruction
                    # text (shapes, operands): all there is to know it by
                    out.append([(scope and md["display"]) or md["name"],
                                start, dur, scope])
                lines.append({"name": name, "events": out})
                continue
            ops = [[st["hlo_op"], start, dur, scope_of(op_name_of(
                        st.get("program_id", ""), st["hlo_op"]))]
                   for md, start, dur, st in events if "hlo_op" in st]
            if ops:
                cpu_lines.append({"name": OP_LINE, "events": ops})
            host = [[md["name"], start, dur]
                    for md, start, dur, _ in events
                    if md["name"].startswith(HOST_PREFIXES)
                    or md["name"] == HOST_STAGE]
            if host:
                lines.append({"name": name, "events": host})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    if cpu_lines:
        planes.append({"name": CPU_PLANE, "lines": cpu_lines})
    return planes


# --------------------------------------------------------- reduction


def reduce(planes: List[dict], top: int = 10) -> dict:
    """Self time per scope over every device, the share left unscoped,
    and the idle gaps of the first device, each by the host span that
    is the innermost one for most of it.  Shares are of the summed self
    time: of busy time, where a device runs one op at a time."""
    host = [e for p in planes if not p["name"].startswith(DEVICE_PREFIXES)
            for ln in p["lines"] for e in ln["events"]
            if e[0].startswith(HOST_PREFIXES) or e[0] == HOST_STAGE]
    devices = [[ln for ln in p["lines"]
                if ln["name"] == OP_LINE and ln["events"]]
               for p in planes if p["name"].startswith(DEVICE_PREFIXES)]
    devices = [lines for lines in devices if lines]
    op_lines = [ln for lines in devices for ln in lines]
    host_spans: Dict[str, int] = {}
    for e in host:
        host_spans[e[0]] = host_spans.get(e[0], 0) + 1
    window = next(((s, s + d) for n, s, d in host if n == WINDOW_SPAN),
                  None)
    if window is None:
        every = [e for ln in op_lines for e in ln["events"]] + host
        window = (min(e[1] for e in every),
                  max(e[1] + e[2] for e in every)) if every else (0., 0.)
    lo, hi = window

    by_scope: Dict[str, float] = {}
    loose: Dict[str, float] = {}        # unscoped ops, by name
    n_events = 0
    for ln in op_lines:
        evs = ln["events"]
        n_events += len(evs)
        # keyed by scope, and by name too where there is no scope
        for (scope, name), ns in self_times(
                [[(e[3], "" if e[3] else e[0]), e[1], e[2]]
                 for e in evs]).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + ns
            if not scope:
                loose[name] = loose.get(name, 0.0) + ns
    total = sum(by_scope.values())

    def share(ns: float) -> float:
        return 100.0 * ns / total if total else 0.0

    tops: Dict[str, float] = {}
    nested: Dict[str, Dict[str, float]] = {}
    for scope, ns in by_scope.items():
        inner = scope.rsplit("/", 1)[-1]
        t = top_level(inner) if inner else UNSCOPED
        tops[t] = tops.get(t, 0.0) + ns
        if inner and inner != t:
            nested.setdefault(t, {})[inner] = \
                nested.setdefault(t, {}).get(inner, 0.0) + ns

    def rows(d: Dict[str, float]) -> List[list]:
        return sorted(([k, ns / 1e9, share(ns)] for k, ns in d.items()),
                      key=lambda r: -r[1])

    # busy and gaps are the first device's (XLA:CPU runs ops on several
    # threads: a gap there is a time in which none ran one)
    merged = clip(merge([(e[1], e[1] + e[2])
                         for ln in (devices[0] if devices else [])
                         for e in ln["events"]]), lo, hi)
    busy_ns = sum(b - a for a, b in merged)
    gaps, cur = [], lo
    for a, b in merged:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur and op_lines:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    # each instant of a gap belongs to the innermost (shortest) span
    # around it; the gap is named by the span that so owns most of it
    spans = sorted(((e - s, n, s, e) for n, s, d in host
                    for e in (s + d,) if n != WINDOW_SPAN))
    idle_gaps = []
    for a, b in gaps[:top]:
        left, owned = [(a, b)], {}
        for _, n, s, e in spans:
            took = clip(left, s, e)
            if took:
                owned[n] = owned.get(n, 0.0) + sum(y - x for x, y in took)
                left = [(x, y) for lo_, hi_ in left
                        for x, y in ((lo_, min(hi_, s)), (max(lo_, e), hi_))
                        if y > x]
        if left:
            owned[UNNAMED] = sum(y - x for x, y in left)
        best = max(owned, key=owned.get)
        idle_gaps.append([best, (b - a) / 1e9,
                          {n: ns / 1e9 for n, ns in owned.items()}])

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": (1.0 - busy_ns / (hi - lo)) if hi > lo else None,
        "self_s": total / 1e9,
        "scopes": rows(tops),
        "nested": {t: rows(d) for t, d in nested.items()},
        "unscoped_share": share(tops.get(UNSCOPED, 0.0)),
        "unscoped_ops": rows(loose)[:top],
        "idle_gaps": idle_gaps,
        "host_spans": host_spans,
        "n_device_events": n_events,
    }


def table(red: dict) -> str:
    """The reduction as lines of text."""
    out = [f"device self time {red['self_s']:.6f} s over "
           f"{red['n_device_events']} events; busy {red['busy_s']:.6f} "
           f"of {red['window_s']:.6f} s",
           f"{'scope':<34}{'self_s':>12}{'% of busy':>11}"]
    for name, s, pct in red["scopes"]:
        out.append(f"{name:<34}{s:>12.6f}{pct:>11.3f}")
        inner = red["nested"].get(name, [])
        for n2, s2, pct2 in inner:
            out.append(f"  {n2:<32}{s2:>12.6f}{pct2:>11.3f}")
        if inner:
            own = s - sum(r[1] for r in inner)
            out.append(f"  {'(' + name + ' itself)':<32}{own:>12.6f}"
                       f"{pct - sum(r[2] for r in inner):>11.3f}")
    out.append(f"sum of top-level shares "
               f"{sum(r[2] for r in red['scopes']):.3f}%")
    for name, s, pct in red["unscoped_ops"]:
        out.append(f"unscoped op {s:>10.6f} s{pct:>8.3f}%  {name[:200]}")
    for name, s, owned in red["idle_gaps"]:
        parts = ", ".join(f"{n} {v * 1e3:.4f}" for n, v in sorted(
            owned.items(), key=lambda kv: -kv[1]))
        out.append(f"idle gap {s * 1e3:10.4f} ms in {name} ({parts})")
    out.append("host spans in the trace: " + ", ".join(
        f"{n} x{c}" for n, c in sorted(red["host_spans"].items())))
    return "\n".join(out)
