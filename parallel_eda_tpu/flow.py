"""Flow orchestration: the vpr_api / place_and_route equivalent.

Mirrors the reference's flow driver (vpr/SRC/base/vpr_api.c vpr_init /
vpr_pack / vpr_place_and_route and base/place_and_route.c:51
place_and_route_new): front end -> pack -> place -> route -> verify, with
each stage's artifacts exposed so callers (CLI, tests, bench, the driver
entry points) share one pipeline instead of re-deriving it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .arch.builtin import k6_n10_arch, minimal_arch
from .arch.model import Arch
from .netlist.generate import generate_circuit
from .netlist.netlist import LogicalNetlist
from .netlist.packed import PackedNetlist
from .obs import stage
from .pack.packer import pack_netlist
from .place.initial import initial_placement
from .place.sa import Placer, PlacerOpts, PlaceStats
from .route.check import check_route
from .route.router import RouteResult, Router, RouterOpts
from .rr.graph import RRGraph, build_rr_graph, check_rr_graph
from .rr.grid import DeviceGrid, size_grid
from .rr.terminals import NetTerminals, net_terminals
from .timing.graph import TimingGraph, build_timing_graph
from .timing.sta import TimingAnalyzer


@dataclass
class FlowResult:
    """Everything the flow produced (the analogue of VPR's globals)."""
    arch: Arch
    nl: LogicalNetlist
    pnl: PackedNetlist
    grid: DeviceGrid
    pos: np.ndarray
    rr: RRGraph
    term: NetTerminals
    tg: Optional[TimingGraph] = None
    analyzer: Optional[TimingAnalyzer] = None
    route: Optional[RouteResult] = None
    place_stats: Optional[PlaceStats] = None
    bb_factor: int = 3
    # stage -> seconds: a derived view of the obs stage spans (every
    # writer goes through obs.stage, so with a tracer installed the
    # same intervals appear as spans in the trace file)
    times: dict = field(default_factory=dict)
    sdc: Optional[object] = None    # timing.sdc.SdcConstraints (or None)

    @property
    def crit_path_delay(self) -> float:
        return self.analyzer.crit_path_delay if self.analyzer else float(
            "nan")


def prepare(nl: LogicalNetlist, arch: Arch, chan_width: int,
            seed: int = 0, nx: int = 0, ny: int = 0,
            bb_factor: int = 3,
            pnl: Optional[PackedNetlist] = None) -> FlowResult:
    """Front end through initial placement + rr-graph (no SA, no route).
    Pass ``pnl`` to resume from a packed netlist (.net file) instead of
    running the packer."""
    times: dict = {}
    with stage("pack", times):
        if pnl is None:
            pnl = pack_netlist(nl, arch)
    n_io = n_clb = 0
    hard_counts: dict = {}
    for i in range(pnl.num_blocks):
        bt = pnl.block_type(i)
        if bt.is_io:
            n_io += 1
        elif bt.name == "clb":
            n_clb += 1
        else:
            hard_counts[bt.name] = hard_counts.get(bt.name, 0) + 1
    grid = size_grid(n_clb, n_io, arch, nx=nx, ny=ny,
                     hard_counts=hard_counts)
    pos = initial_placement(pnl, grid, seed=seed)
    with stage("rr_graph", times):
        rr = build_rr_graph(arch, grid, chan_width=chan_width)
    term = net_terminals(pnl, rr, pos, bb_factor=bb_factor)
    res = FlowResult(arch=arch, nl=nl, pnl=pnl, grid=grid, pos=pos, rr=rr,
                     term=term, bb_factor=bb_factor)
    res.times.update(times)
    return res


def synth_flow(num_luts: int = 100, num_inputs: int = 8,
               num_outputs: int = 8, chan_width: int = 16, seed: int = 1,
               ff_ratio: float = 0.3, arch: Optional[Arch] = None,
               use_k6: bool = False, bb_factor: int = 3) -> FlowResult:
    """Synthetic-circuit front end (the shared fixture for tests, bench,
    and the driver entry points)."""
    arch = arch or (k6_n10_arch() if use_k6 else
                    minimal_arch(chan_width=chan_width))
    nl = generate_circuit(num_luts=num_luts, num_inputs=num_inputs,
                          num_outputs=num_outputs, K=arch.K, seed=seed,
                          ff_ratio=ff_ratio)
    return prepare(nl, arch, chan_width, bb_factor=bb_factor)


def run_place_native(flow: FlowResult, seed: int = 7,
                     inner_num: float = 1.0) -> FlowResult:
    """Anneal with the native C++ serial placer (place/serial_sa.py) and
    refresh net terminals — the host-side fast path for benches and
    tools that need a good placement without compiling the device
    placer's programs.  Same invariant as run_place: any position
    change must re-derive the terminals."""
    from .place.serial_sa import serial_sa_place

    with stage("place", flow.times, native=True):
        res = serial_sa_place(flow.pnl, flow.grid, flow.pos, seed=seed,
                              inner_num=inner_num)
        flow.pos = res.pos
    flow.term = net_terminals(flow.pnl, flow.rr, flow.pos,
                              bb_factor=flow.bb_factor)
    return flow


def run_place(flow: FlowResult,
              opts: Optional[PlacerOpts] = None,
              timing_driven: bool = True) -> FlowResult:
    """SA placement; refreshes net terminals for the new positions.

    Timing-driven mode computes the delay-lookup matrices by routing
    sample nets (timing_place_lookup.c:981) and feeds lookup-delay STA
    criticalities into the annealer's cost (PATH_TIMING_DRIVEN_PLACE)."""
    timing = None
    opts = opts or PlacerOpts()
    if timing_driven and opts.timing_tradeoff > 0:
        from .place.delay_lookup import compute_delay_lookup
        from .place.sa import PlacerTiming

        with stage("delay_lookup", flow.times):
            lookup = compute_delay_lookup(flow.rr)
        if flow.tg is None:
            flow.tg = build_timing_graph(flow.nl, flow.pnl, flow.term)
        timing = PlacerTiming(flow.pnl, lookup, flow.term, flow.tg,
                              td_place_exp=opts.td_place_exp)
    with stage("place", flow.times):
        from .place.macros import form_macros
        macros = form_macros(flow.nl, flow.pnl) \
            if flow.nl is not None else []
        placer = Placer(flow.pnl, flow.grid, opts, timing=timing,
                        macros=macros)
        flow.pos, flow.place_stats = placer.place(flow.pos)
    flow.term = net_terminals(flow.pnl, flow.rr, flow.pos,
                              bb_factor=flow.bb_factor)
    return flow


def routes_from_result(term: NetTerminals, route: RouteResult,
                       num_nodes: int) -> dict:
    """Per-net route trees {packed net index: [(node, parent), ...]} in
    tree order (SOURCE first, parent -1), from the router's per-sink path
    segments (each stored sink -> join-node; the join node is already in
    the tree).  This is the .route file payload (print_route semantics,
    vpr/SRC/route/route_common.c)."""
    out = {}
    for r, ni in enumerate(term.net_ids):
        src = int(term.source[r])
        rows = [(src, -1)]
        in_tree = {src}
        ns = int(term.num_sinks[r])
        segs = []
        for s in range(ns):
            seg = route.paths[r, s]
            seg = seg[seg < num_nodes]
            if seg.size:
                segs.append(seg)
        # segments were grown in criticality-wave order, not sink-slot
        # order: insert each once its join node (seg[-1]) is in the tree
        while segs:
            progressed = False
            rest = []
            for seg in segs:
                if int(seg[-1]) in in_tree:
                    # seg = [sink ... join]; parent of seg[j] is seg[j+1]
                    for j in range(len(seg) - 2, -1, -1):
                        node = int(seg[j])
                        if node in in_tree:
                            continue
                        rows.append((node, int(seg[j + 1])))
                        in_tree.add(node)
                    progressed = True
                else:
                    rest.append(seg)
            if not progressed:
                raise ValueError(
                    f"net {ni}: disconnected route-tree segments")
            segs = rest
        out[int(ni)] = rows
    return out


def save_artifacts(flow: FlowResult, out_dir: str,
                   prefix: Optional[str] = None) -> dict:
    """Write .net / .place / .route (the flow's checkpoint/resume surface,
    SURVEY §5.4; vpr_api.c output files).  Returns {kind: path}."""
    import os

    from .netlist.files import (write_net_file, write_place_file,
                                write_route_file)

    os.makedirs(out_dir, exist_ok=True)
    # nl.name may be a file path (BLIF with no .model line): keep only a
    # safe basename so artifacts always land inside out_dir
    base = os.path.basename(prefix or flow.nl.name) or "circuit"
    paths = {}
    p = os.path.join(out_dir, base + ".net")
    write_net_file(flow.pnl, p)
    paths["net"] = p
    p = os.path.join(out_dir, base + ".place")
    write_place_file(flow.pnl, flow.pos, flow.grid.nx, flow.grid.ny, p,
                     net_file=paths["net"])
    paths["place"] = p
    if flow.route is not None:
        routes = routes_from_result(flow.term, flow.route,
                                    flow.rr.num_nodes)
        p = os.path.join(out_dir, base + ".route")
        write_route_file(flow.pnl, flow.rr, routes, p,
                         flow.grid.nx, flow.grid.ny)
        paths["route"] = p
    return paths


def binary_search_route(flow: FlowResult,
                        opts: Optional[RouterOpts] = None,
                        timing_driven: bool = True,
                        max_width: int = 0, mesh=None) -> int:
    """Find the minimum routable channel width W_min (the reference's
    binary_search_place_and_route, base/place_and_route.c:432): starting
    from the flow's current width, halve while routable / double while
    not, then bisect the (failed, routed] bracket.  Leaves the flow
    routed at W_min and returns it."""
    last_w = [flow.rr.chan_width if flow.route is not None else -1]

    def attempt(w: int) -> bool:
        if w != flow.rr.chan_width:
            flow.rr = build_rr_graph(flow.arch, flow.grid, chan_width=w)
        flow.term = net_terminals(flow.pnl, flow.rr, flow.pos,
                                  bb_factor=flow.bb_factor)
        flow.tg = None          # routed-delay indices depend on term
        flow.analyzer = None
        run_route(flow, opts, timing_driven=timing_driven, verify=False,
                  mesh=mesh)
        last_w[0] = w
        return flow.route.success

    w = flow.rr.chan_width
    if attempt(w):
        hi = w
        lo = 0                  # nothing known to fail yet
        while hi > 1:
            half = hi // 2
            if attempt(half):
                hi = half
            else:
                lo = half
                break
    else:
        lo = w
        while True:
            w = min(w * 2, max_width) if max_width else w * 2
            if attempt(w):
                hi = w
                break
            lo = w
            if max_width and w >= max_width:
                raise RuntimeError(f"unroutable even at W={w}")
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if attempt(mid):
            hi = mid
        else:
            lo = mid
    if last_w[0] != hi:
        attempt(hi)             # leave the flow routed at W_min
    check_route(flow.rr, flow.term, flow.route.paths, occ=flow.route.occ)
    return hi


def run_route(flow: FlowResult, opts: Optional[RouterOpts] = None,
              timing_driven: bool = True, verify: bool = True,
              mesh=None) -> FlowResult:
    """Route + STA loop + legality oracle (try_route_new semantics,
    route/route_common.c:298; check_route place_and_route.c:169).

    ``mesh``: optional (net, node) jax.sharding.Mesh — runs the same
    negotiation loop sharded over the devices (parallel.shard)."""
    # the call's wall by named stage: what comes before the route (the
    # timing graph and analyzer where missing, the router's tables),
    # the route, and after it the STA and the oracle; the last two
    # carry the id of the route's own spans (RouteResult.route_id)
    with stage("flow.route.setup", flow.times, key="route.setup"):
        if timing_driven:
            if flow.tg is None:
                flow.tg = build_timing_graph(flow.nl, flow.pnl, flow.term)
            if flow.analyzer is None:
                flow.analyzer = TimingAnalyzer(flow.tg, sdc=flow.sdc)
        router = Router(flow.rr, opts, mesh=mesh)
    # timing-driven: the planes program fuses the per-iteration STA on
    # device (analyzer mode, K>1 windows); ELL falls back to the host cb
    with stage("route", flow.times, timing_driven=timing_driven):
        flow.route = router.route(
            flow.term, analyzer=flow.analyzer if timing_driven else None)
    rid = flow.route.route_id
    if timing_driven:
        with stage("flow.route.sta", flow.times, key="route.sta",
                   route=rid) as st:
            flow.analyzer.analyze(flow.route.sink_delay)
            st.set(crit_path_hard_arcs=flow.analyzer.crit_path_hard_arcs())
    if verify and flow.route.success:
        with stage("flow.route.verify", flow.times, key="route.verify",
                   route=rid):
            check_route(flow.rr, flow.term, flow.route.paths,
                        occ=flow.route.occ)
    return flow
