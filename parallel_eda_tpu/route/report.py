"""Post-route wirelength / channel-occupancy reporting.

Equivalent of the reference's stats subsystem (vpr/SRC/base/stats.c
routing_stats: wirelength, channel occupancy factors;
route/segment_stats.c get_segment_usage_stats: per-segment-type wire
counts and utilization).  Pure host reporting over the routed result —
printed after routing and/or written next to the stats files.
"""

from __future__ import annotations

import numpy as np

from ..rr.graph import CHANX, CHANY, RRGraph


def overused_wire_nodes(rr: RRGraph, occ: np.ndarray) -> int:
    """Count of WIRE nodes (CHANX/CHANY) over capacity.  stats.c counts
    overuse on routing wires only — SOURCE/SINK/pin nodes are not
    fabric resources — so both the human-readable report and the
    metrics registry (obs.metrics 'route.overused_wire_nodes') go
    through this one helper and cannot drift."""
    occ = np.asarray(occ)
    nt = np.asarray(rr.node_type)
    wire = (nt == CHANX) | (nt == CHANY)
    over = occ - np.asarray(rr.capacity, dtype=np.int64)
    return int(((over > 0) & wire).sum())


def route_report(rr: RRGraph, occ: np.ndarray,
                 num_nets: int) -> str:
    """Human-readable routing statistics block."""
    occ = np.asarray(occ)
    is_x = np.asarray(rr.node_type) == CHANX
    is_y = np.asarray(rr.node_type) == CHANY
    wire = is_x | is_y
    used = occ > 0
    span = (np.asarray(rr.xhigh) - np.asarray(rr.xlow)
            + np.asarray(rr.yhigh) - np.asarray(rr.ylow) + 1)

    lines = ["Routing statistics (stats.c routing_stats equivalent):"]
    total_wl = int(span[wire & used].sum())
    lines.append(f"  nets routed: {num_nets}")
    lines.append(f"  total wirelength: {total_wl} tile-lengths "
                 f"({int((wire & used).sum())} wire nodes)")
    lines.append(f"  avg wirelength per net: "
                 f"{total_wl / max(1, num_nets):.2f}")

    # channel occupancy factors (utilization of each channel's tracks)
    for name, m in (("CHANX", is_x), ("CHANY", is_y)):
        cap = int(m.sum())
        u = int((m & used).sum())
        lines.append(f"  {name} utilization: {u}/{cap} "
                     f"({100.0 * u / max(1, cap):.1f}%)")

    # per-segment-type usage (segment_stats.c get_segment_usage_stats);
    # cost_index encodes the segment type for wires
    ci = np.asarray(rr.cost_index)
    for c in sorted(set(ci[wire].tolist())):
        m = wire & (ci == c)
        u = int((m & used).sum())
        L = int(span[m].max()) if m.any() else 0
        lines.append(f"  segment cost_index {int(c)} (len<={L}): "
                     f"{u}/{int(m.sum())} wires used")

    # occupancy histogram: how contested the fabric is (wire nodes
    # only, stats.c semantics — see overused_wire_nodes)
    lines.append(f"  overused nodes: {overused_wire_nodes(rr, occ)}")
    return "\n".join(lines)


# RouteResult.wall's intervals, in the order they happen
WALL_KEYS = ("prologue_s", "windows_s", "control_s", "epilogue_s")


def format_window_table(result) -> str:
    """The route by window, one line a ``RouteResult.stats`` row: what
    each window IS (``kind``), what it did and what it cost, and
    whether its result is in the route returned (``kept``); on a route
    with fanout classes two more columns, the sweeps and waves of the
    batches of a class above the first; on a route of the window
    program one more, the distance elements its sink picks read as a
    share of what dense picks read in the same waves, and another, the
    canvas cells its sweeps covered (``cell_sweeps``, in millions: the
    sweeps x the rung's batch width x its canvas's cells); on a route
    that switched the scans' guard on, which windows ran guarded.  Under it
    the route's wall by named interval where the result carries one
    (``RouteResult.wall``: the four add up to the ``route`` stage)."""
    head = ("window", "iter", "kind", "overused", "nets", "seconds",
            "stall_s", "control_s", "sweeps", "waves", "waves_crop",
            "batches", "routes", "routes/batch", "kept")

    def fill(routes, batches):
        # net routes a batch the window ran: of B slots, how many worked
        return f"{routes / batches:.1f}" if batches else "-"

    rows = [(s.window, s.iteration, s.kind or "-", s.overused_nodes,
             s.rerouted_nets, f"{s.route_time_s:.3f}", f"{s.stall_s:.3f}",
             f"{s.control_s:.4f}", s.relax_steps, s.waves,
             s.waves_cropped, s.batches,
             s.net_routes, fill(s.net_routes, s.batches),
             "yes" if s.kept else "NO") for s in result.stats]
    batches = sum(s.batches for s in result.stats)
    routes = sum(s.net_routes for s in result.stats)
    rows.append(("sum", result.iterations, "", "", "",
                 f"{sum(s.route_time_s for s in result.stats):.3f}",
                 f"{sum(s.stall_s for s in result.stats):.3f}",
                 f"{sum(s.control_s for s in result.stats):.4f}",
                 sum(s.relax_steps for s in result.stats),
                 sum(s.waves for s in result.stats),
                 sum(s.waves_cropped for s in result.stats),
                 batches, routes, fill(routes, batches),
                 f"{sum(1 for s in result.stats if s.kept)}"
                 f"/{len(result.stats)}"))
    if any(getattr(s, "fanout_class", 0) for s in result.stats):
        # a route with fanout classes: of sweeps and waves, what the
        # batches of a class above the first spent
        wide = [(s.relax_steps_wide, s.waves_wide) for s in result.stats]
        wide.append(tuple(sum(col) for col in zip(*wide)))
        head += ("sweeps_wide", "waves_wide")
        rows = [r + w for r, w in zip(rows, wide)]
    reads = [(s.sink_reads, s.sink_reads_dense) for s in result.stats]
    if any(dense for _, dense in reads):
        # a route of the window program: of the distance elements dense
        # sink picks read in the window's waves, what its picks read
        reads.append(tuple(sum(col) for col in zip(*reads)))
        head += ("pick_read%",)
        rows = [r + (f"{100.0 * got / dense:.1f}" if dense else "-",)
                for r, (got, dense) in zip(rows, reads)]
    swept = [s.cell_sweeps for s in result.stats]
    if any(swept):
        swept.append(sum(swept))
        head += ("Mcell_sweeps",)
        rows = [r + (f"{n / 1e6:.1f}",) for r, n in zip(rows, swept)]
    guarded = [getattr(s, "scan_guard", False) for s in result.stats]
    if any(guarded):
        # a route that met the predecessor 2-cycle's state: the windows
        # whose relaxations ran with the scans guarded
        head += ("guard",)
        rows = [r + (g,) for r, g in zip(
            rows, ["yes" if g else "-" for g in guarded]
            + [f"{sum(guarded)}/{len(guarded)}"])]
    cells = [head] + [tuple(str(c) for c in r) for r in rows]
    width = [max(len(r[i]) for r in cells) for i in range(len(head))]
    lines = ["  ".join(c.ljust(w) if i == 2 else c.rjust(w)
                       for i, (c, w) in enumerate(zip(r, width))).rstrip()
             for r in cells]
    wall = getattr(result, "wall", None)
    if wall:
        lines.append("wall: " + " + ".join(
            f"{k} {wall[k]:.4f}" for k in WALL_KEYS if k in wall)
            + f" = {sum(wall.values()):.4f} s")
    return "\n".join(lines)


def write_route_report(path: str, rr: RRGraph, occ: np.ndarray,
                       num_nets: int) -> None:
    with open(path, "w") as f:
        f.write(route_report(rr, occ, num_nets) + "\n")
