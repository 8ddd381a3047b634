"""Routing-resource graph builder → flat CSR device arrays.

TPU-native equivalent of the reference rr-graph layer
(vpr/SRC/route/rr_graph.c:385 build_rr_graph, rr_graph2.c track maps,
rr_graph_sbox.c switch boxes, rr_graph_indexed_data.c base costs) and of the
parallel layer's trimmed mirror (parallel_route/new_rr_graph.h:10-64,
init.cxx:22 init_graph).  Unlike the reference — which builds pointer-rich
``rr_node[]`` structs and then mirrors them into a cache-friendly
``cache_graph_t`` — we build the final form directly: structure-of-arrays
numpy, CSR in both directions (out-edges for push, in-edges for the pull-based
batched relaxation the TPU router uses).

Graph semantics (island-style, subset switch boxes):
  SOURCE -> OPIN -> CHANX/CHANY -> ... -> CHANX/CHANY -> IPIN -> SINK
Wires of segment length L span L tiles as a single rr-node (xlow..xhigh),
staggered by track so breaks are distributed; wires connect at their
endpoints to crossing/continuing wires (Fs=3-style subset pattern) and along
their span to block IPINs (Fc_in) / from block OPINs (Fc_out).

Two directionality modes (reference rr_graph.c:432-548, the
UNI_DIRECTIONAL vs BI_DIRECTIONAL segment split):
  * bidir (VPR4-style): every wire is drivable at both endpoints;
    wire<->wire edges come in symmetric pairs (tri-state switches).
  * unidir (every modern VTR/Titan arch): tracks pair by parity —
    even = INC (left->right / bottom->top), odd = DEC — and every wire
    has a SINGLE DRIVER at its start: OPINs and switchbox muxes connect
    only where a wire STARTS (mux switch of the TARGET segment), and
    only IPIN taps stay span-wide.  W is rounded up to a multiple of
    twice the longest segment (even, and whole turn groups).  The box
    is build_rr_graph's "Unidir switch box".
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..arch.model import Arch, PIN_CLASS_DRIVER, PIN_CLASS_RECEIVER
from ..obs import get_metrics, span
from .grid import DeviceGrid

# rr-node types (order matches files.py writers and the reference's t_rr_type)
SOURCE, SINK, OPIN, IPIN, CHANX, CHANY = range(6)
RR_TYPE_NAMES = ["SOURCE", "SINK", "OPIN", "IPIN", "CHANX", "CHANY"]

# cost indices (rr_graph_indexed_data.c equivalent)
COST_SOURCE, COST_SINK, COST_OPIN, COST_IPIN = range(4)
# wires: 4 + seg (CHANX), 4 + num_seg + seg (CHANY)


@dataclass
class RRGraph:
    """Flat SoA rr-graph.  All arrays are host numpy; the router uploads the
    ones it needs as jnp device arrays (see route/device_graph.py)."""
    # --- nodes ---
    node_type: np.ndarray       # int8   [N]
    xlow: np.ndarray            # int16  [N]
    ylow: np.ndarray            # int16  [N]
    xhigh: np.ndarray           # int16  [N]
    yhigh: np.ndarray           # int16  [N]
    ptc: np.ndarray             # int32  [N]  pin/class/track index
    capacity: np.ndarray        # int16  [N]
    R: np.ndarray               # f32    [N]
    C: np.ndarray               # f32    [N]
    cost_index: np.ndarray      # int8   [N]
    base_cost: np.ndarray       # f32    [N]
    # --- out-edge CSR ---
    out_row_ptr: np.ndarray     # int32  [N+1]
    out_dst: np.ndarray         # int32  [E]
    out_switch: np.ndarray      # int8   [E]
    # --- in-edge CSR (derived; in_src sorted by destination) ---
    in_row_ptr: np.ndarray      # int32  [N+1]
    in_src: np.ndarray          # int32  [E]
    in_switch: np.ndarray       # int8   [E]
    # per-in-edge traversal delay: switch Tdel + C_dst*(R_switch + R_dst/2)
    in_delay: np.ndarray        # f32    [E]
    # --- lookups (host only) ---
    src_of: Dict[Tuple[int, int, int, int], int]   # (x,y,z,class) -> node
    sink_of: Dict[Tuple[int, int, int, int], int]
    opin_of: Dict[Tuple[int, int, int, int], int]  # (x,y,z,pin)  -> node
    ipin_of: Dict[Tuple[int, int, int, int], int]
    grid: DeviceGrid
    chan_width: int
    switch_Tdel: np.ndarray     # f32 [num_switches+1] (last = delayless)
    switch_R: np.ndarray        # f32 [num_switches+1]
    # per-track segment / wire-to-wire switch (planes kernel co-design:
    # route/planes.py derives its static delay planes from these)
    seg_of_track: Optional[np.ndarray] = None       # int32 [W]
    wire_switch_of_track: Optional[np.ndarray] = None  # int32 [W]
    # unidir graphs: per-track direction (0 = INC, 1 = DEC); None = bidir
    dir_of_track: Optional[np.ndarray] = None       # int32 [W]
    # unidir graphs, the switch box's data ("Unidir switch box",
    # build_rr_graph): per-track segment length and sb marks, and the
    # number of consecutive tracks that form a turn group
    seg_len_of_track: Optional[np.ndarray] = None   # int32 [W]
    sb_of_track: Optional[np.ndarray] = None        # bool [W, Lmax + 1]
    group_tracks: int = 0

    @property
    def unidir(self) -> bool:
        return self.dir_of_track is not None

    @property
    def num_nodes(self) -> int:
        return len(self.node_type)

    @property
    def num_edges(self) -> int:
        return len(self.out_dst)

    def describe(self, node: int) -> str:
        """Pretty printer (parallel_route/utility.c:13 sprintf_rr_node)."""
        t = RR_TYPE_NAMES[self.node_type[node]]
        return (f"{node} {t} ({self.xlow[node]},{self.ylow[node]})"
                f"->({self.xhigh[node]},{self.yhigh[node]}) ptc "
                f"{self.ptc[node]}")


def _fc_tracks(pin_ptc: int, side: int, W: int, fc: float) -> List[int]:
    """Which of the W tracks a pin connects to in one adjacent channel.
    Staggered spread (rr_graph2.c alloc_and_load_pin_to_track_map semantics —
    independently chosen pattern with the same spreading goal)."""
    fc_abs = max(1, int(round(fc * W)))
    fc_abs = min(fc_abs, W)
    start = (pin_ptc * 7 + side * 3) % W
    return [ (start + (j * W) // fc_abs) % W for j in range(fc_abs) ]


def _adjacent_channels(grid: DeviceGrid, x: int, y: int):
    """The channels tile (x, y) faces, as (kind, channel index, position
    along it): a CHANX adjacency is ('x', y_chan, x), a CHANY one
    ('y', x_chan, y).  A cluster faces four, a pad tile the one beside
    the perimeter."""
    nx, ny = grid.nx, grid.ny
    if grid.is_clb(x, y):
        return [("x", y, x), ("x", y - 1, x), ("y", x, y), ("y", x - 1, y)]
    if x == 0:                            # left IO
        return [("y", 0, y)]
    if x == nx + 1:                       # right IO
        return [("y", nx, y)]
    if y == 0:                            # bottom IO
        return [("x", 0, x)]
    if y == ny + 1:                       # top IO
        return [("x", ny, x)]
    return []


def _spread_picks(n: int, f: int, phase: int) -> List[int]:
    """f of n candidates for the pin-and-side of ordinal ``phase``
    (unidir graphs): evenly spaced, offset in units of 1/f of a
    candidate by a golden-ratio stride coprime to n * f, so that n * f
    successive pins differ in offset or in rounding and no stride of
    pins collapses onto one set (``_fc_tracks``' stride 7 gives every
    pin of a side the same tracks when 7 divides W, and pins 8 apart
    the same starts when 16 start)."""
    m = n * f
    k = int(0.6180339887 * m) | 1
    while math.gcd(k, m) != 1:
        k += 2
    o = (phase * k) % m
    return [((j * n + o) // f) % n for j in range(f)]


def unidir_exit_point(dec, seg_len, stag, c, lo, hi):
    """Which switch point (1..L) of a single-driver wire lies on the
    corner of coordinate c, 0 if the wire has no exit there ("Unidir
    switch box", build_rr_graph).  Everything broadcasts: ``dec`` the
    track's direction, ``seg_len`` its segment's length L, ``stag`` its
    stagger, ``lo`` and ``hi`` the wire's span along its channel.  A
    corner has the coordinate of the position on its low side; an INC
    wire exits on the high side of each of its positions, a DEC wire on
    the low side.  Points count from the wire's start, by the track's
    stagger (a wire the device's edge cut short keeps the count of the
    whole wire); the point where the wire physically ends is point L."""
    L = np.asarray(seg_len)
    k_inc = np.where(c == hi, L, (c - stag - 1) % L + 1)
    k_dec = np.where(c == lo - 1, L, L - (c - stag) % L)
    on = np.where(dec, (c >= lo - 1) & (c <= hi - 1),
                  (c >= lo) & (c <= hi))
    return np.where(on, np.where(dec, k_dec, k_inc), 0)


def unidir_turn_group(grp, into_y: bool, par, n_grp: int):
    """The group of tracks on which a turn out of group ``grp`` lands
    at a corner of parity ``par`` = (x + y) % 2: its own at an even
    corner; at an odd one the next group (cyclic) for a turn from CHANX
    into CHANY and the previous for a turn from CHANY into CHANX, so an
    X -> Y -> X detour nets -1, 0 or +1 by the corners it picks
    ("Unidir switch box", build_rr_graph)."""
    return (grp + (par if into_y else -par)) % n_grp


def build_rr_graph(arch: Arch, grid: DeviceGrid,
                   chan_width: Optional[int] = None) -> RRGraph:
    """Build the full rr-graph (semantics of rr_graph.c:385 build_rr_graph).

    Runs under the span ``rr.build``; a unidir graph sets the gauges
    ``rr.exit_turns_min`` and ``rr.opin_starts_min`` (``unidir_box_stats``).

    **Unidir switch box** (single-driver segments; the one statement of
    the rule: ``route/planes.py``'s directional stencils and
    ``tests/test_unidir.py``'s plain-Python rule set follow it).

    * Lanes and groups.  Tracks 2q (INC) and 2q + 1 (DEC) are lane q;
      lane q's span breaks stagger by q % L (L its segment's length).
      ``group_tracks`` = 2 * Lmax consecutive tracks are a GROUP, g =
      t // group_tracks: with one segment type, each whole group has
      exactly one INC and one DEC wire STARTING at every corner of
      every channel (all its wires of a direction start where the
      device's edge cuts them).
    * Exits.  A wire is driven only at its start (OPINs of the blocks
      there, and the muxes of this box).  It EXITS at every switch
      point k = 1..L its segment's ``sb`` pattern marks, counted from
      its start (``unidir_exit_point``); its physical end is point L
      and always marked; point 0, its own mux, is no exit.  ``sb`` all
      ones is the published pattern; ``1 0 0 0 1`` turns at the end
      only.
    * Targets (Fs = 3).  An exit at corner (x, y), parity p = (x + y)
      % 2, from a CHANX wire of group g drives every CHANY wire that
      STARTS at the corner in group (g + p) % G -- one going up, one
      going down -- and, only where the wire ends, the wire of its own
      track that starts there (straight on).  From a CHANY wire: the
      CHANX starters of group (g - p) % G, and straight on at the end
      (``unidir_turn_group``).  Which track a target has inside its
      group does not matter: it is the group's lane that starts there.
      On the planes that is a min over a group's lanes, a roll over
      groups at odd corners, and a broadcast onto the start cells.
    * Pins.  An OPIN drives Fc_out x W of the wires that START at its
      position in each channel it faces, an IPIN hears Fc_in x W of the
      wires passing it, both spread by ``_spread_picks``.

    Wilton's permutation is not built: this box is the repo's own Fs=3.
    """
    with span("rr.build", cat="rr") as sp:
        rr = _build_rr_graph(arch, grid, chan_width)
        is_wire = (rr.node_type == CHANX) | (rr.node_type == CHANY)
        sp.set(unidir=rr.unidir, W=int(rr.chan_width),
               max_span=int(np.maximum(
                   rr.xhigh - rr.xlow, rr.yhigh - rr.ylow)[is_wire].max(
                       initial=0)) + 1,
               nodes=rr.num_nodes, edges=rr.num_edges,
               block_types=len({grid.interior_type_name(x)
                                for x in range(1, grid.nx + 1)}) + 1,
               hard_columns=len(grid.col_types),
               tall_rows=max(grid.type_heights.values(), default=1))
        if rr.unidir:
            exit_min, opin_min = unidir_box_stats(rr)
            get_metrics().set_gauges({"rr.exit_turns_min": exit_min,
                                      "rr.opin_starts_min": opin_min})
    return rr


def _build_rr_graph(arch: Arch, grid: DeviceGrid,
                    chan_width: Optional[int] = None) -> RRGraph:
    W = chan_width or arch.default_chan_width
    nx, ny = grid.nx, grid.ny
    num_seg = len(arch.segments)

    if getattr(arch, "sb_type", "subset_rotated") not in (
            "subset", "subset_rotated"):
        import warnings

        warnings.warn(
            f"arch requests switch_block type={arch.sb_type!r} "
            f"fs={arch.sb_fs}; this builder implements its own Fs=3 "
            "boxes, co-designed with the planes kernel (bidir: subset + "
            "parity-rotated turns; unidir: build_rr_graph's \"Unidir "
            "switch box\").  Same O(W) switch count and the "
            "index-permutation property, but track-level topology "
            f"differs from VPR's {arch.sb_type} box.")

    dirs = {s.directionality for s in arch.segments}
    if len(dirs) > 1:
        raise ValueError(f"segments mix directionalities {dirs}; the rr "
                         f"builder requires one mode (rr_graph.c:432)")
    unidir = dirs == {"unidir"}
    if unidir:
        # unidir tracks pair INC/DEC (VPR forces even W) and turn by
        # whole groups of Lmax lanes ("Unidir switch box"): W rounds up
        # to a multiple of 2 * Lmax, so no group lacks a lane that
        # starts at some corner
        group_tracks = 2 * max(max(1, s.length) for s in arch.segments)
        W = -(-W // group_tracks) * group_tracks

    # segment type per track: frequency-proportional contiguous blocks
    # (unidir: assigned per INC/DEC track PAIR so both directions of a
    # lane share a segment type, rr_graph.c unidir pairing)
    Wa = W // 2 if unidir else W
    seg_assign = np.zeros(Wa, dtype=np.int32)
    freqs = np.array([s.frequency for s in arch.segments], dtype=np.float64)
    freqs = freqs / freqs.sum()
    bounds = np.floor(np.cumsum(freqs) * Wa + 0.5).astype(np.int64)
    lo = 0
    for s, hi in enumerate(bounds):
        seg_assign[lo:hi] = s
        lo = hi
    seg_assign[lo:] = num_seg - 1
    seg_of_track = np.repeat(seg_assign, 2) if unidir else seg_assign
    seg_len_of_track = np.array([max(1, arch.segments[s].length)
                                 for s in seg_of_track], dtype=np.int64)
    # stagger of a track's span breaks.  Unidir: by LANE (the INC/DEC
    # track pair), so the wire starts of each direction spread over all
    # positions (t % L would give every INC track the same phase,
    # leaving whole columns with no drive point)
    stag_of_track = ((np.arange(W) // 2) if unidir
                     else np.arange(W)) % seg_len_of_track
    if unidir:
        sb_of_track = np.zeros((W, group_tracks // 2 + 1), dtype=bool)
        for t in range(W):
            m = arch.segments[seg_of_track[t]].sb_marks()
            sb_of_track[t, :len(m)] = m

    def block_at(x: int, y: int):
        """(block type, anchor row) of the block site covering tile
        (x, y), or (None, y) on a corner or an empty tile.  Interior
        columns may hold heterogeneous types (grid.col_types,
        SetupGrid.c column assignment); a type of ``height`` h is
        anchored every h rows of its column and covers the h rows from
        its anchor (grid.block_at)."""
        if 1 <= x <= nx and 1 <= y <= ny:
            site = grid.block_at(x, y)
            if site is None:
                return None, y
            return arch.block_type(site[0]), site[1]
        if grid.is_io(x, y):
            return arch.io_type, y
        return None, y

    # every tile that holds pins, in node order.  A block's SOURCE and
    # SINK nodes exist ONCE, at its anchor tile, and span its rows; its
    # pin p lies on row p % height and reaches the channels beside that
    # row (the channels run through a hard column).  A block of height 1
    # is the same rule: all its pins on its one row
    tiles = []
    for x in range(nx + 2):
        for y in range(ny + 2):
            bt, y0 = block_at(x, y)
            if bt is not None:
                tiles.append((x, y, bt, y0, [
                    p for p in range(bt.num_pins)
                    if p % bt.height == y - y0]))

    ntype: List[int] = []
    xlo: List[int] = []; ylo: List[int] = []
    xhi: List[int] = []; yhi: List[int] = []
    ptc: List[int] = []; cap: List[int] = []
    Rn: List[float] = []; Cn: List[float] = []
    cidx: List[int] = []

    def add_node(t, x1, y1, x2, y2, p, c, r_, c_, ci) -> int:
        ntype.append(t); xlo.append(x1); ylo.append(y1)
        xhi.append(x2); yhi.append(y2); ptc.append(p); cap.append(c)
        Rn.append(r_); Cn.append(c_); cidx.append(ci)
        return len(ntype) - 1

    src_of: Dict = {}; sink_of: Dict = {}
    opin_of: Dict = {}; ipin_of: Dict = {}

    # ---- block-pin nodes (SOURCE/SINK/OPIN/IPIN), per tile/subtile;
    # the lookups are keyed by the block's ANCHOR (x, y0) ----
    for x, y, bt, y0, pins in tiles:
        ncls = len(bt.pin_classes)
        for z in range(bt.capacity):
            for k, cls in enumerate(bt.pin_classes if y == y0 else ()):
                pc = z * ncls + k
                if cls.direction == PIN_CLASS_DRIVER:
                    src_of[(x, y, z, k)] = add_node(
                        SOURCE, x, y, x, y + bt.height - 1, pc,
                        len(cls.pins), 0.0, 0.0, COST_SOURCE)
                else:
                    sink_of[(x, y, z, k)] = add_node(
                        SINK, x, y, x, y + bt.height - 1, pc,
                        len(cls.pins), 0.0, 0.0, COST_SINK)
            for p in pins:
                pc = z * bt.num_pins + p
                k = bt.pin_class_of[p]
                if bt.pin_classes[k].direction == PIN_CLASS_DRIVER:
                    opin_of[(x, y0, z, p)] = add_node(
                        OPIN, x, y, x, y, pc, 1, 0.0, 0.0, COST_OPIN)
                else:
                    ipin_of[(x, y0, z, p)] = add_node(
                        IPIN, x, y, x, y, pc, 1, 0.0, 0.0, COST_IPIN)

    # ---- wire nodes ----
    # chanx_wire[y][t, x] / chany_wire[x][t, y]: node covering that position
    chanx_wire = [np.full((W, nx + 1), -1, dtype=np.int64)
                  for _ in range(ny + 1)]
    chany_wire = [np.full((W, ny + 1), -1, dtype=np.int64)
                  for _ in range(nx + 1)]

    def wire_spans(lo_pos: int, hi_pos: int, L: int, stagger: int):
        """Partition [lo_pos, hi_pos] into length-L spans with break after
        every position p where (p - stagger) % L == 0."""
        spans = []
        a = lo_pos
        for p in range(lo_pos, hi_pos + 1):
            if (p - stagger) % L == 0 or p == hi_pos:
                spans.append((a, p))
                a = p + 1
        return spans

    for y in range(ny + 1):
        for t in range(W):
            seg = arch.segments[seg_of_track[t]]
            L = max(1, seg.length)
            for (a, b) in wire_spans(1, nx, L, int(stag_of_track[t])):
                span = b - a + 1
                node = add_node(CHANX, a, y, b, y, t, 1,
                                seg.Rmetal * span, seg.Cmetal * span,
                                4 + seg_of_track[t])
                chanx_wire[y][t, a:b + 1] = node
    for x in range(nx + 1):
        for t in range(W):
            seg = arch.segments[seg_of_track[t]]
            L = max(1, seg.length)
            for (a, b) in wire_spans(1, ny, L, int(stag_of_track[t])):
                span = b - a + 1
                node = add_node(CHANY, x, a, x, b, t, 1,
                                seg.Rmetal * span, seg.Cmetal * span,
                                4 + num_seg + seg_of_track[t])
                chany_wire[x][t, a:b + 1] = node

    N = len(ntype)
    node_type = np.array(ntype, dtype=np.int8)
    xlow = np.array(xlo, dtype=np.int16); ylow = np.array(ylo, dtype=np.int16)
    xhigh = np.array(xhi, dtype=np.int16); yhigh = np.array(yhi, dtype=np.int16)

    # ---- switch table (+ appended delayless switch) ----
    nsw = len(arch.switches)
    delayless = nsw
    switch_Tdel = np.array([s.Tdel for s in arch.switches] + [0.0],
                           dtype=np.float32)
    switch_R = np.array([s.R for s in arch.switches] + [0.0],
                        dtype=np.float32)

    e_src: List[int] = []; e_dst: List[int] = []; e_sw: List[int] = []

    def add_edge(s, d, sw):
        e_src.append(s); e_dst.append(d); e_sw.append(sw)

    # ---- SOURCE->OPIN, IPIN->SINK (delayless) ----
    for x, y, bt, y0, _ in tiles:
        if y != y0:
            continue
        for z in range(bt.capacity):
            for k, cls in enumerate(bt.pin_classes):
                if cls.direction == PIN_CLASS_DRIVER:
                    s = src_of[(x, y, z, k)]
                    for p in cls.pins:
                        add_edge(s, opin_of[(x, y, z, p)], delayless)
                else:
                    snk = sink_of[(x, y, z, k)]
                    for p in cls.pins:
                        add_edge(ipin_of[(x, y, z, p)], snk, delayless)

    # ---- pin <-> channel edges ----
    def starting_tracks(kind: str, ci: int, pos: int) -> List[int]:
        """Unidir: tracks whose wire STARTS at this channel position (the
        only legal drive points; INC starts at its low end, DEC at its
        high end — rr_graph.c unidir opin/mux placement)."""
        out = []
        for t in range(W):
            w = int(chanx_wire[ci][t, pos] if kind == "x"
                    else chany_wire[ci][t, pos])
            if w < 0:
                continue
            if kind == "x":
                start = (xlo[w] == pos) if t % 2 == 0 else (xhi[w] == pos)
            else:
                start = (ylo[w] == pos) if t % 2 == 0 else (yhi[w] == pos)
            if start:
                out.append(t)
        return out

    for x, y, bt, y0, pins in tiles:
        adj = _adjacent_channels(grid, x, y)
        for z in range(bt.capacity):
            for p in pins:
                k = bt.pin_class_of[p]
                cls = bt.pin_classes[k]
                is_out = cls.direction == PIN_CLASS_DRIVER
                node = (opin_of if is_out else ipin_of)[(x, y0, z, p)]
                fc = arch.fc_frac(W, is_out, type_name=bt.name, pin=p)
                pin_ptc = z * bt.num_pins + p
                for side, (kind, ci, pos) in enumerate(adj):
                    if unidir:
                        # single-driver wires: an OPIN drives only
                        # wires that START at its position, an IPIN
                        # hears any wire passing it; Fc of W, spread
                        # over the candidates
                        cands = (starting_tracks(kind, ci, pos)
                                 if is_out else list(range(W)))
                        if not cands:
                            continue
                        fc_abs = min(len(cands),
                                     max(1, int(round(fc * W))))
                        for i in _spread_picks(len(cands), fc_abs,
                                               4 * pin_ptc + side):
                            t = cands[i]
                            wire = int(chanx_wire[ci][t, pos]
                                       if kind == "x"
                                       else chany_wire[ci][t, pos])
                            if is_out:
                                add_edge(node, wire, arch.segments[
                                    seg_of_track[t]].opin_switch)
                            else:
                                add_edge(wire, node, arch.ipin_switch)
                        continue
                    for t in _fc_tracks(pin_ptc, side, W, fc):
                        wire = (chanx_wire[ci][t, pos] if kind == "x"
                                else chany_wire[ci][t, pos])
                        if wire < 0:
                            continue
                        if is_out:
                            sw = arch.segments[seg_of_track[t]].opin_switch
                            add_edge(node, int(wire), sw)
                        else:
                            add_edge(int(wire), node, arch.ipin_switch)

    # ---- dedicated direct connections (<directlist>,
    # physical_types.h t_direct_inf): OPIN -> IPIN of the offset
    # neighbour through a private wire, bypassing the fabric ----
    # (between block ANCHORS: the offset is in tiles)
    for d in arch.directs:
        sw = d.switch if d.switch >= 0 else delayless
        for x in range(nx + 2):
            for y in range(ny + 2):
                bt, y0 = block_at(x, y)
                if bt is None or bt.name != d.from_type or y != y0:
                    continue
                tx, ty = x + d.dx, y + d.dy
                tt, ty0 = block_at(tx, ty)
                if tt is None or tt.name != d.to_type or ty != ty0:
                    continue
                for z in range(bt.capacity):
                    src_n = opin_of.get((x, y, z, d.from_pin))
                    dst_n = ipin_of.get((tx, ty, z, d.to_pin))
                    if src_n is not None and dst_n is not None:
                        add_edge(src_n, dst_n, sw)

    # ---- switch-box edges (endpoint rule; subset + rotated mixing) ----
    # Straight continuations and same-index turns follow the subset rule
    # (rr_graph_sbox.c get_subset_sbox: track t only meets track t), which
    # converges fast under PathFinder because the per-track subnetworks are
    # interchangeable.  A pure subset box, however, never mixes track
    # indices, so a pin whose Fc track-set misses the target pin's set is
    # simply unreachable (real case: two bottom-edge IO pads with disjoint
    # 2-3 track sets).  We therefore ADD endpoint-gated turns at a rotated
    # index, CHANX t <-> CHANY (t + 1 + (x+y) mod 2) mod W: the shift
    # varies with corner parity so an X->Y->X loop nets an index change of
    # +-1 (the Wilton property that matters — turns permute indices so the
    # reachable track set grows, rr_graph_sbox.c get_wilton_sbox
    # motivation) while every edge still obeys the endpoint rule, keeping
    # the switch count O(W) per corner like the reference's Fs=3 boxes.
    # (A previous variant put rotated turns at EVERY corner a wire passes
    # and dropped same-index turns entirely; it stayed connected but made
    # congestion negotiation ~2-3x slower to converge — per-track
    # interchangeability is what lets PathFinder shift a net sideways.)
    # corner (x, y): x in 0..nx, y in 0..ny
    def ends_at(w: int, x: int, y: int) -> bool:
        if node_type[w] == CHANX:
            return xhigh[w] == x or xlow[w] == x + 1
        return yhigh[w] == y or ylow[w] == y + 1

    if unidir:
        # ---- directed switch box: build_rr_graph's docstring,
        # "Unidir switch box", is the rule; unidir_exit_point (shared
        # with route/planes.py build_planes) and unidir_turn_group are
        # its two halves ----
        tr = np.arange(W)
        dec = tr % 2 == 1
        grp = tr // group_tracks
        n_grp = int(grp[-1]) + 1
        wsw = [arch.segments[s].wire_switch for s in seg_of_track]

        def at_corner(wire_tc, c, n, lo_a, hi_a):
            """Of one channel (wire_tc [W, n + 1]) at the corner of
            coordinate c: per track the wire that EXITS there (-1:
            none) with whether it ends there, and the wire that STARTS
            there (-1: none).  A wire reaches the corner from its low
            side (INC, position c) or its high side (DEC, c + 1) and
            starts away from it on the other."""
            p_src = np.where(dec, c + 1, c)
            p_tgt = np.where(dec, c, c + 1)
            w_src = np.where((p_src >= 1) & (p_src <= n),
                             wire_tc[tr, np.clip(p_src, 1, n)], -1)
            w_tgt = np.where((p_tgt >= 1) & (p_tgt <= n),
                             wire_tc[tr, np.clip(p_tgt, 1, n)], -1)
            lo, hi = lo_a[w_src], hi_a[w_src]
            k = unidir_exit_point(dec, seg_len_of_track, stag_of_track,
                                  c, lo, hi)
            w_src = np.where(sb_of_track[tr, k] & (k > 0), w_src, -1)
            ends = np.where(dec, lo == c + 1, hi == c)
            starts = np.where(dec, hi_a[w_tgt] == c,
                              lo_a[w_tgt] == c + 1)
            return w_src, ends, np.where(starts, w_tgt, -1)

        for x in range(nx + 1):
            for y in range(ny + 1):
                sx, ex, tx = at_corner(chanx_wire[y], x, nx, xlow, xhigh)
                sy, ey, ty = at_corner(chany_wire[x], y, ny, ylow, yhigh)
                for src, ends, own, other, into_y in (
                        (sx, ex, tx, ty, True), (sy, ey, ty, tx, False)):
                    for t in np.flatnonzero(src >= 0):
                        w = int(src[t])
                        for t2 in np.flatnonzero(
                                (other >= 0)
                                & (grp == unidir_turn_group(
                                    grp[t], into_y, (x + y) % 2,
                                    n_grp))):
                            add_edge(w, int(other[t2]), wsw[t2])
                        if ends[t] and own[t] >= 0:
                            add_edge(w, int(own[t]), wsw[t])

    for x in (range(nx + 1) if not unidir else ()):
        # bidir switch box (the unidir box was emitted above)
        for y in range(ny + 1):
            for t in range(W):
                sw = arch.segments[seg_of_track[t]].wire_switch

                def chanx_at(tt):
                    out: List[int] = []
                    for px in (x, x + 1):
                        if 1 <= px <= nx:
                            w = int(chanx_wire[y][tt, px])
                            if w >= 0 and w not in out:
                                out.append(w)
                    return out

                def chany_at(tt):
                    out: List[int] = []
                    for py in (y, y + 1):
                        if 1 <= py <= ny:
                            w = int(chany_wire[x][tt, py])
                            if w >= 0 and w not in out:
                                out.append(w)
                    return out

                hx = chanx_at(t)
                vy = chany_at(t)
                vy_turn = chany_at((t + 1 + (x + y) % 2) % W)

                # straight continuations (same index, endpoint-gated)
                for i in range(len(hx)):
                    for j in range(i + 1, len(hx)):
                        a, b = hx[i], hx[j]
                        if ends_at(a, x, y) or ends_at(b, x, y):
                            add_edge(a, b, sw)
                            add_edge(b, a, sw)
                for i in range(len(vy)):
                    for j in range(i + 1, len(vy)):
                        a, b = vy[i], vy[j]
                        if ends_at(a, x, y) or ends_at(b, x, y):
                            add_edge(a, b, sw)
                            add_edge(b, a, sw)
                # same-index turns (subset rule, endpoint-gated)
                for a in hx:
                    for b in vy:
                        if ends_at(a, x, y) or ends_at(b, x, y):
                            add_edge(a, b, sw)
                            add_edge(b, a, sw)
                # rotated turns (index mixing, endpoint-gated); at W <= 2
                # the rotated track can coincide with t — skip to avoid
                # duplicating the same-index turns above
                if (t + 1 + (x + y) % 2) % W != t:
                    for a in hx:
                        for b in vy_turn:
                            if ends_at(a, x, y) or ends_at(b, x, y):
                                add_edge(a, b, sw)
                                add_edge(b, a, sw)

    # ---- pack CSR ----
    E = len(e_src)
    esrc = np.array(e_src, dtype=np.int64)
    edst = np.array(e_dst, dtype=np.int64)
    esw = np.array(e_sw, dtype=np.int8)

    order = np.argsort(esrc, kind="stable")
    out_dst = edst[order].astype(np.int32)
    out_switch = esw[order]
    out_row_ptr = np.zeros(N + 1, dtype=np.int32)
    np.add.at(out_row_ptr, esrc + 1, 1)
    out_row_ptr = np.cumsum(out_row_ptr, dtype=np.int64).astype(np.int32)

    iorder = np.argsort(edst, kind="stable")
    in_src = esrc[iorder].astype(np.int32)
    in_switch = esw[iorder]
    in_row_ptr = np.zeros(N + 1, dtype=np.int32)
    np.add.at(in_row_ptr, edst + 1, 1)
    in_row_ptr = np.cumsum(in_row_ptr, dtype=np.int64).astype(np.int32)

    Rarr = np.array(Rn, dtype=np.float32)
    Carr = np.array(Cn, dtype=np.float32)
    in_dst_sorted = edst[iorder]
    in_delay = (switch_Tdel[in_switch.astype(np.int64)]
                + Carr[in_dst_sorted]
                * (switch_R[in_switch.astype(np.int64)]
                   + 0.5 * Rarr[in_dst_sorted])).astype(np.float32)

    # ---- base costs (rr_graph_indexed_data.c semantics, simplified) ----
    cost_index = np.array(cidx, dtype=np.int8)
    base_cost = np.ones(N, dtype=np.float32)
    base_cost[node_type == IPIN] = 0.95
    base_cost[node_type == SINK] = 0.0

    return RRGraph(
        node_type=node_type, xlow=xlow, ylow=ylow, xhigh=xhigh, yhigh=yhigh,
        ptc=np.array(ptc, dtype=np.int32),
        capacity=np.array(cap, dtype=np.int16),
        R=Rarr, C=Carr, cost_index=cost_index, base_cost=base_cost,
        out_row_ptr=out_row_ptr, out_dst=out_dst, out_switch=out_switch,
        in_row_ptr=in_row_ptr, in_src=in_src, in_switch=in_switch,
        in_delay=in_delay,
        src_of=src_of, sink_of=sink_of, opin_of=opin_of, ipin_of=ipin_of,
        grid=grid, chan_width=W,
        switch_Tdel=switch_Tdel, switch_R=switch_R,
        seg_of_track=seg_of_track.astype(np.int32),
        wire_switch_of_track=np.array(
            [arch.segments[s].wire_switch for s in seg_of_track],
            dtype=np.int32),
        dir_of_track=(np.arange(W, dtype=np.int32) % 2) if unidir
        else None,
        seg_len_of_track=(seg_len_of_track.astype(np.int32) if unidir
                          else None),
        sb_of_track=sb_of_track if unidir else None,
        group_tracks=group_tracks if unidir else 0,
    )


_LEGAL_EDGES = {
    SOURCE: {OPIN},
    OPIN: {CHANX, CHANY, IPIN},      # OPIN->IPIN = direct connection
    IPIN: {SINK},
    CHANX: {CHANX, CHANY, IPIN},
    CHANY: {CHANX, CHANY, IPIN},
    SINK: set(),
}


def _wire_axis(rr: RRGraph, w):
    """Of wire nodes ``w``: (is CHANX, DEC, span lo, span hi, the
    coordinate across the channel)."""
    is_x = rr.node_type[w] == CHANX
    lo = np.where(is_x, rr.xlow[w], rr.ylow[w]).astype(np.int64)
    hi = np.where(is_x, rr.xhigh[w], rr.yhigh[w]).astype(np.int64)
    fixed = np.where(is_x, rr.ylow[w], rr.xlow[w]).astype(np.int64)
    return is_x, rr.ptc[w] % 2 == 1, lo, hi, fixed


def unidir_box_stats(rr: RRGraph):
    """The two numbers a unidir box can get wrong in silence, read back
    from the edges alone.  ``exit_turns_min``: fewest turn edges (onto
    the other channel type) at any switch point a wire's ``sb`` pattern
    marks, the device's edge rows and columns apart -- 0 when a marked
    exit turns nowhere.  ``opin_starts_min``: fewest distinct wires an
    OPIN drives in any one channel it drives at all."""
    wire = (rr.node_type == CHANX) | (rr.node_type == CHANY)
    src = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    dst = rr.out_dst.astype(np.int64)
    nx, ny = rr.grid.nx, rr.grid.ny

    # a turn edge lies on the corner its TARGET starts at; along the
    # source's own axis that corner has the target's cross coordinate
    turn = (wire[src] & wire[dst]
            & (rr.node_type[src] != rr.node_type[dst]))
    ts, td = src[turn], dst[turn]
    _, _, _, _, d_fixed = _wire_axis(rr, td)
    keys = ts * (max(nx, ny) + 2) + d_fixed
    have = dict(zip(*np.unique(keys, return_counts=True)))

    w = np.flatnonzero(wire)
    is_x, dec, lo, hi, fixed = _wire_axis(rr, w)
    t = rr.ptc[w]
    L = rr.seg_len_of_track[t].astype(np.int64)
    n_ax = np.where(is_x, nx, ny)
    n_cross = np.where(is_x, ny, nx)
    exit_min = None
    for j in range(int(L.max()) + 1):
        c = lo - 1 + j
        k = unidir_exit_point(dec, L, (t // 2) % L, c, lo, hi)
        need = (rr.sb_of_track[t, k] & (k > 0) & (c > 0) & (c < n_ax)
                & (fixed > 0) & (fixed < n_cross))
        for wi, ci in zip(w[need], c[need]):
            n = have.get(int(wi) * (max(nx, ny) + 2) + int(ci), 0)
            exit_min = n if exit_min is None else min(exit_min, n)

    drive = (rr.node_type[src] == OPIN) & wire[dst]
    os_, od = src[drive], dst[drive]
    d_is_x, _, _, _, d_fixed = _wire_axis(rr, od)
    chan = (os_ * 2 + d_is_x) * (max(nx, ny) + 2) + d_fixed
    pairs = np.unique(np.stack([chan, od], axis=1), axis=0)
    _, per_chan = np.unique(pairs[:, 0], return_counts=True)
    return (int(exit_min) if exit_min is not None else 0,
            int(per_chan.min()) if len(per_chan) else 0)


def _check_unidir_box(rr: RRGraph, arch: Optional[Arch]) -> None:
    """The unidir rules of check_rr_graph: wire -> wire and OPIN -> wire
    edges land only on wire STARTS; every marked switch point off the
    device's edge turns somewhere; with the architecture given, every
    OPIN drives its Fc share of distinct starts in every channel it
    faces."""
    wire = (rr.node_type == CHANX) | (rr.node_type == CHANY)
    src = np.repeat(np.arange(rr.num_nodes), np.diff(rr.out_row_ptr))
    dst = rr.out_dst.astype(np.int64)
    into = wire[dst]
    s, d = src[into], dst[into]
    d_is_x, d_dec, d_lo, d_hi, _ = _wire_axis(rr, d)
    start = np.where(d_dec, d_hi, d_lo)
    # an OPIN sits AT the start position; a wire's exit corner touches it
    s_is_wire = wire[s]
    pos = np.where(d_is_x, rr.xlow[s], rr.ylow[s]).astype(np.int64)
    assert np.all(pos[~s_is_wire] == start[~s_is_wire]), \
        "an OPIN drives a wire away from its start"
    sw, dw = s[s_is_wire], d[s_is_wire]
    same = rr.node_type[sw] == rr.node_type[dw]
    _, s_dec, s_lo, s_hi, s_fixed = _wire_axis(rr, sw)
    _, w_dec, w_lo, w_hi, w_fixed = _wire_axis(rr, dw)
    w_start_c = np.where(w_dec, w_hi, w_lo - 1)     # its start corner
    s_end_c = np.where(s_dec, s_lo - 1, s_hi)
    assert np.all(w_start_c[same] == s_end_c[same]), \
        "a wire continues straight away from its end"
    # a turn: the source covers the corner the target starts at
    tc = w_fixed[~same]
    assert np.all(np.where(s_dec[~same], (tc >= s_lo[~same] - 1)
                           & (tc <= s_hi[~same] - 1),
                           (tc >= s_lo[~same]) & (tc <= s_hi[~same]))
                  & (w_start_c[~same] == s_fixed[~same])), \
        "a turn lands on a wire that does not start at the corner"
    exit_min, opin_min = unidir_box_stats(rr)
    assert exit_min >= 1, "a marked switch point has no turn edge"
    assert opin_min >= 1
    if arch is None:
        return
    starts: Dict[Tuple[bool, int, int], int] = {}
    w = np.flatnonzero(wire)
    is_x, dec, lo, hi, fixed = _wire_axis(rr, w)
    for kx, f, p in zip(is_x, fixed, np.where(dec, hi, lo)):
        key = (bool(kx), int(f), int(p))
        starts[key] = starts.get(key, 0) + 1
    drive = (rr.node_type[src] == OPIN) & wire[dst]
    got: Dict[Tuple[int, bool, int], set] = {}
    for o, t in zip(src[drive], dst[drive]):
        kx = bool(rr.node_type[t] == CHANX)
        f = int(rr.ylow[t] if kx else rr.xlow[t])
        got.setdefault((int(o), kx, f), set()).add(int(t))
    for (x, y, z, p), o in rr.opin_of.items():
        bt = (arch.io_type if rr.grid.is_io(x, y)
              else arch.block_type(rr.grid.interior_type_name(x)))
        fc = arch.fc_frac(rr.chan_width, True, type_name=bt.name, pin=p)
        want = max(1, int(round(fc * rr.chan_width)))
        # the key is the block's anchor; the pin's own row is the node's
        for kind, f, pos_ in _adjacent_channels(rr.grid, x,
                                                int(rr.ylow[o])):
            kx = kind == "x"
            n = starts.get((kx, f, pos_), 0)
            if n == 0:
                continue
            have = len(got.get((o, kx, f), ()))
            assert have >= min(want, n), (
                f"OPIN {rr.describe(o)} drives {have} starts of "
                f"{'CHANX' if kx else 'CHANY'} {f}, its Fc share is "
                f"{min(want, n)}")


def check_rr_graph(rr: RRGraph, reachability: bool = True,
                   arch: Optional[Arch] = None) -> None:
    """Graph sanity checker (vpr/SRC/route/check_rr_graph.c equivalent).
    Raises AssertionError on any violation.  A unidir graph is also held
    to its switch box's rules (``_check_unidir_box``; the Fc share of
    every OPIN needs ``arch``)."""
    N, E = rr.num_nodes, rr.num_edges
    assert rr.out_row_ptr[0] == 0 and rr.out_row_ptr[-1] == E
    assert rr.in_row_ptr[0] == 0 and rr.in_row_ptr[-1] == E
    assert np.all(rr.out_dst >= 0) and np.all(rr.out_dst < N)
    assert np.all(rr.in_src >= 0) and np.all(rr.in_src < N)

    # type-legal edges, no self loops (vectorized over ALL edges)
    src_ids = np.repeat(np.arange(N), np.diff(rr.out_row_ptr))
    assert not np.any(src_ids == rr.out_dst), "self edge"
    pair_codes = np.unique(rr.node_type[src_ids].astype(np.int64) * 6
                           + rr.node_type[rr.out_dst])
    for code in pair_codes:
        s_t, d_t = int(code) // 6, int(code) % 6
        assert d_t in _LEGAL_EDGES[s_t], \
            f"illegal edge {RR_TYPE_NAMES[s_t]}->{RR_TYPE_NAMES[d_t]}"

    # out/in CSR hold the same multiset of edges
    a = np.stack([src_ids, rr.out_dst.astype(np.int64)], axis=1)
    dst_ids = np.repeat(np.arange(N), np.diff(rr.in_row_ptr))
    b = np.stack([rr.in_src.astype(np.int64), dst_ids], axis=1)
    a = a[np.lexsort((a[:, 1], a[:, 0]))]
    b = b[np.lexsort((b[:, 1], b[:, 0]))]
    assert np.array_equal(a, b), "in/out CSR mismatch"

    # every OPIN drives a wire; every IPIN is driven by a wire
    out_deg = np.diff(rr.out_row_ptr)
    in_deg = np.diff(rr.in_row_ptr)
    opins = rr.node_type == OPIN
    assert np.all(out_deg[opins] >= 1), "dead OPIN"
    ipins = rr.node_type == IPIN
    assert np.all(in_deg[ipins] >= 1), "dead IPIN (no driving wire)"
    assert np.all(out_deg[rr.node_type == SINK] == 0)
    assert np.all(in_deg[rr.node_type == SOURCE] == 0)
    if rr.unidir:
        _check_unidir_box(rr, arch)

    if reachability and N <= 200000:
        # all SINKs reachable from the union of SOURCEs (frontier sweep)
        reach = rr.node_type == SOURCE
        frontier = reach.copy()
        while frontier.any():
            nxt = np.zeros(N, dtype=bool)
            fsrc = np.where(frontier)[0]
            for s in fsrc:
                d = rr.out_dst[rr.out_row_ptr[s]:rr.out_row_ptr[s + 1]]
                nxt[d] = True
            frontier = nxt & ~reach
            reach |= frontier
        sinks = rr.node_type == SINK
        assert np.all(reach[sinks]), \
            f"{int((~reach[sinks]).sum())} unreachable SINKs"
