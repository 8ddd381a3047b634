"""Planes relaxation kernel: structured shortest-path search without gathers.

The replacement for the ELL pull-relaxation of search.py (_relax): instead of
[B, N, D] gathers over an arbitrary edge table, the router state is laid out
as dense per-direction wire grids ("planes") co-designed with the rr
builder's regular channel structure (rr/graph.py):

    dx [B, W, NX, NY+1]   the CHANX wire covering (track t, x, y)
    dy [B, W, NX+1, NY]   the CHANY wire covering (track t, x, y)

Every wire relaxation is a structured tensor op:

  * straight continuation along a channel row — one min-plus ASSOCIATIVE
    SCAN per direction: s[x] = min(d0[x], s[x-1] + c[x]), where c[x] pays
    the switch delay + PathFinder congestion cost only at span breaks (the
    builder's staggered length-L wire spans are static break masks).  One
    scan propagates a whole row, so the relaxation converges in O(#turns)
    sweeps instead of O(path length).
  * switchbox turns — shifted masked mins between the dx/dy canvases; the
    builder's rotated-subset pattern (CHANX t <-> CHANY (t+1+parity) mod W)
    is literally a jnp.roll along the track axis with a checkerboard parity
    mask.  Single-driver (unidir) graphs turn by GROUPS of tracks (rr/graph.py
    "Unidir switch box"): a min over a group's lanes at each corner, a roll
    over groups at odd corners, a broadcast onto the cells that start there.
  * terminal hops (SOURCE->OPIN->wire, wire->IPIN->SINK) — small per-net
    tables, outside the sweep loop entirely: pins are only ever endpoints
    (OPIN is reachable only from SOURCE, IPIN leads only to SINK), so the
    sweeps never need pin planes.

Alongside the distance, every relaxation step tracks the IMMEDIATE
PREDECESSOR CELL and the true (un-weighted) delay of the entering edge, as
elementwise payloads of the same scans/shifts.  Traceback is then a pure
pointer chase over `pred` with take_along_axis — the one dynamic-access
pattern this design allows itself.  (A chain of dependent
[B, G]-from-[B, Ncells] take_alongs is cheap, while anything touching the
[N, D] ELL rows in a loop — row gathers, flattened takes, even one-hot
matmuls — was measured far slower on an earlier backend; not re-measured
on the current chip.  The entire batch step below therefore uses ONLY
elementwise ops, scans, rolls, scatters, and take_along gathers.)

The pred chase cannot cycle: every strict improvement re-sets (dist, pred,
w) atomically and dist is monotone non-increasing, so d(pred(x)) < d(x)
along any snapshot chain (ties never update), and walks terminate at a
pred==self cell — a tree seed or a SOURCE-side entry.

This is the round-3 answer to the reference's heap-search work-efficiency
(vpr/SRC/parallel_route/dijkstra.h:15, route_timing.c:603
timing_driven_expand_neighbours).  Cost model, seeding semantics, jitter,
and the congestion view are shared with search.py
(congestion_cost_arrays), so the negotiation is identical.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax

from ..obs import get_metrics
from ..obs.trace import device_scope
from ..rr.graph import CHANX, CHANY, RRGraph, unidir_exit_point
from ..rr.terminals import FANOUT_BASE, FANOUT_STEP
from .device_graph import DeviceRRGraph
from .search import JITTER_EPS, congestion_cost, usage_from_paths

INF = jnp.inf
# PlanesTerminals.uid_pcrank where a pin does not hear a cell: past
# every rank, so the device needs no K
RANK_PAD = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# Static plane metadata (host build, once per Router)
# ---------------------------------------------------------------------------


@struct.dataclass
class PlanesGraph:
    """Static per-graph plane layout + masks (device arrays, pytree).

    Cell space: every (track, x, y) channel position is a cell; a length-L
    wire owns L cells.  chanx cells [W, NX, NY+1] flattened first, then
    chany cells [W, NX+1, NY]; `ncells` total.
    """
    node_of_cell: jnp.ndarray       # int32 [Ncells] rr-node id of each cell
    cell_of_node: jnp.ndarray       # int32 [N] representative cell
    #                                 (non-wire nodes -> Ncells = INF pad)
    # span-break masks (x axis for chanx, y axis for chany)
    brk_before_x: jnp.ndarray       # bool [W, NX, NY+1]
    brk_after_x: jnp.ndarray
    brk_before_y: jnp.ndarray       # bool [W, NX+1, NY]
    brk_after_y: jnp.ndarray
    # span endpoint masks (for the endpoint-gated switchbox rule).
    # Directional graphs: the mask of a track's DRIVING side -- last_*
    # on an INC track, first_* on a DEC one -- marks every cell whose
    # corner on that side is an EXIT of its wire (the wire's sb pattern,
    # rr/graph.py "Unidir switch box": the true end and whatever the
    # pattern marks before it); the other mask stays the wire's START
    first_x: jnp.ndarray            # bool: cell is its node's span start
    last_x: jnp.ndarray
    first_y: jnp.ndarray
    last_y: jnp.ndarray
    # enter-delay planes: delay of an edge INTO this cell's node
    #   delay_x / delay_y: switch = wire_switch of the cell's own track
    #   (straight continuation, same-index turns, rotated turns into CHANX)
    delay_x: jnp.ndarray            # f32 [W, NX, NY+1]
    delay_y: jnp.ndarray            # f32 [W, NX+1, NY]
    #   rotated turns into CHANY use the SOURCE track's switch
    #   (rr/graph.py adds both rotated directions with the chanx track's
    #   switch): delay with wire_switch of track (t - 1 - parity) mod W
    delay_y_rot0: jnp.ndarray       # f32 [W, NX+1, NY] (parity 0)
    delay_y_rot1: jnp.ndarray       # f32 [W, NX+1, NY] (parity 1)
    # unidirectional graphs (rr.dir_of_track, rr_graph.c:432-548): every
    # track has a direction (INC/DEC), wires are driven only at their
    # start, all edges use the TARGET's switch.  `directional` is static
    # (it selects a different relaxation program); inc_track is the
    # per-track INC mask
    directional: bool = struct.field(pytree_node=False, default=False)
    inc_track: Optional[jnp.ndarray] = None     # bool [W]
    # directional graphs: consecutive tracks that form one turn group
    # (static; W is a multiple of it): a turn lands on the lane of the
    # group that STARTS at the corner, whatever its track
    group_tracks: int = struct.field(pytree_node=False, default=0)
    # longest wire span in grid units (static): the bb-crop margin —
    # a wire INTERSECTING a net's bb can overhang it by max_span-1
    max_span: int = struct.field(pytree_node=False, default=1)
    # the scans' guard against a predecessor 2-cycle inside a wire span
    # (_scan_update; static: it selects a different relaxation program).
    # Off in every program a route starts with; the window driver
    # switches it on for the windows of a route that ended one with
    # nothing over capacity and a sink unreached
    scan_guard: bool = struct.field(pytree_node=False, default=False)

    @property
    def shape_x(self):
        return self.brk_before_x.shape      # (W, NX, NY+1)

    @property
    def shape_y(self):
        return self.brk_before_y.shape      # (W, NX+1, NY)

    @property
    def ncells(self) -> int:
        sx, sy = self.shape_x, self.shape_y
        return int(np.prod(sx) + np.prod(sy))


def _cover_cells(ids, t, lo, hi, fixed, horizontal, W, NX, NY):
    """Flat cell indices covered by wire spans (vectorized arange trick)."""
    reps = (hi - lo + 1).astype(np.int64)
    total = int(reps.sum())
    node_rep = np.repeat(ids, reps)
    t_rep = np.repeat(t, reps).astype(np.int64)
    f_rep = np.repeat(fixed, reps).astype(np.int64)
    starts = np.repeat(np.cumsum(reps) - reps, reps)
    pos = np.repeat(lo, reps).astype(np.int64) + (np.arange(total) - starts)
    if horizontal:      # chanx: (t, x=pos in 1..NX, y=fixed in 0..NY)
        cell = (t_rep * NX + (pos - 1)) * (NY + 1) + f_rep
    else:               # chany: (t, x=fixed in 0..NX, y=pos in 1..NY)
        cell = (t_rep * (NX + 1) + f_rep) * NY + (pos - 1)
    return node_rep, cell


def build_planes(rr: RRGraph) -> PlanesGraph:
    """Derive the plane layout from a built RRGraph.  Requires the builder's
    per-track switch map (rr.wire_switch_of_track)."""
    if rr.wire_switch_of_track is None:
        raise ValueError("planes need rr.wire_switch_of_track "
                         "(graph not built by rr.graph.build_rr_graph)")
    W = rr.chan_width
    NX, NY = rr.grid.nx, rr.grid.ny
    N = rr.num_nodes
    ncx = W * NX * (NY + 1)
    ncy = W * (NX + 1) * NY
    ncells = ncx + ncy

    node_of_cell = np.full(ncells, N, dtype=np.int64)
    is_x = rr.node_type == CHANX
    is_y = rr.node_type == CHANY
    idx = np.where(is_x)[0]
    nrep, cell = _cover_cells(idx, rr.ptc[idx], rr.xlow[idx], rr.xhigh[idx],
                              rr.ylow[idx], True, W, NX, NY)
    node_of_cell[cell] = nrep
    idy = np.where(is_y)[0]
    nrep, cell = _cover_cells(idy, rr.ptc[idy], rr.ylow[idy], rr.yhigh[idy],
                              rr.xlow[idy], False, W, NX, NY)
    node_of_cell[ncx + cell] = nrep
    assert (node_of_cell < N).all(), "uncovered channel cell"

    cell_of_node = np.full(N + 1, ncells, dtype=np.int64)
    # first covered cell of each node (reverse write keeps the lowest)
    order = np.arange(ncells - 1, -1, -1)
    cell_of_node[node_of_cell[order]] = order
    cell_of_node = cell_of_node[:N]

    nx_pl = node_of_cell[:ncx].reshape(W, NX, NY + 1)
    ny_pl = node_of_cell[ncx:].reshape(W, NX + 1, NY)

    def breaks(pl, axis):
        d = np.diff(pl, axis=axis) != 0
        pad = np.ones(tuple(1 if a == axis else s
                            for a, s in enumerate(pl.shape)), dtype=bool)
        before = np.concatenate([pad, d], axis=axis)
        after = np.concatenate([d, pad], axis=axis)
        return before, after

    brk_before_x, brk_after_x = breaks(nx_pl, 1)
    brk_before_y, brk_after_y = breaks(ny_pl, 2)

    xcoord = np.arange(1, NX + 1)[None, :, None]
    ycoord = np.arange(1, NY + 1)[None, None, :]
    first_x = rr.xlow[nx_pl] == xcoord
    last_x = rr.xhigh[nx_pl] == xcoord
    first_y = rr.ylow[ny_pl] == ycoord
    last_y = rr.yhigh[ny_pl] == ycoord
    if rr.unidir:
        # the driving side's mask takes the wire's exits: an INC cell
        # at position p exits on corner p, a DEC cell on corner p - 1
        tr = np.arange(W)[:, None, None]
        dec = tr % 2 == 1
        L = rr.seg_len_of_track.astype(np.int64)[:, None, None]

        def exits(pl, coord, lo, hi):
            k = unidir_exit_point(dec, L, (tr // 2) % L,
                                  coord - dec, lo[pl].astype(np.int64),
                                  hi[pl].astype(np.int64))
            return rr.sb_of_track[tr, k] & (k > 0)

        ex = exits(nx_pl, xcoord, rr.xlow, rr.xhigh)
        ey = exits(ny_pl, ycoord, rr.ylow, rr.yhigh)
        last_x, first_x = np.where(dec, last_x, ex), np.where(dec, ex,
                                                              first_x)
        last_y, first_y = np.where(dec, last_y, ey), np.where(dec, ey,
                                                              first_y)

    # enter-delay planes: Tdel[sw] + C[node]*(R[sw] + R[node]/2) — the
    # exact in_delay formula of the builder (rr/graph.py in_delay)
    def enter_delay(pl, sw_of_t):
        Csw = rr.C[pl]
        Rsw = rr.R[pl]
        tdel = rr.switch_Tdel[sw_of_t][:, None, None]
        rs = rr.switch_R[sw_of_t][:, None, None]
        return (tdel + Csw * (rs + 0.5 * Rsw)).astype(np.float32)

    swt = rr.wire_switch_of_track.astype(np.int64)
    delay_x = enter_delay(nx_pl, swt)
    delay_y = enter_delay(ny_pl, swt)
    rot0 = swt[(np.arange(W) - 1) % W]       # parity 0: src = (t-1) mod W
    rot1 = swt[(np.arange(W) - 2) % W]       # parity 1: src = (t-2) mod W
    delay_y_rot0 = enter_delay(ny_pl, rot0)
    delay_y_rot1 = enter_delay(ny_pl, rot1)

    j = jnp.asarray
    return PlanesGraph(
        node_of_cell=j(node_of_cell, dtype=jnp.int32),
        cell_of_node=j(cell_of_node, dtype=jnp.int32),
        brk_before_x=j(brk_before_x), brk_after_x=j(brk_after_x),
        brk_before_y=j(brk_before_y), brk_after_y=j(brk_after_y),
        first_x=j(first_x), last_x=j(last_x),
        first_y=j(first_y), last_y=j(last_y),
        delay_x=j(delay_x), delay_y=j(delay_y),
        delay_y_rot0=j(delay_y_rot0), delay_y_rot1=j(delay_y_rot1),
        directional=rr.unidir,
        inc_track=(j(rr.dir_of_track == 0) if rr.unidir else None),
        group_tracks=int(rr.group_tracks) if rr.unidir else 0,
        max_span=int(max(
            (rr.xhigh[is_x] - rr.xlow[is_x] + 1).max(initial=1),
            (rr.yhigh[is_y] - rr.ylow[is_y] + 1).max(initial=1))),
    )


# ---------------------------------------------------------------------------
# Per-route-call terminal tables (host build; exact edge enumeration from
# the graph — the net_t source/sink expansion of route.h:70)
# ---------------------------------------------------------------------------


@dataclass
class PlanesTerminals:
    """Per-net terminal entry tables.

    SOURCE side: the net's source-class OPINs and every OPIN->wire edge as
    (wire cell, opin index, exact edge delay).  SINK side: every
    (wire -> IPIN -> SINK) two-edge hop, stored once per distinct SINK
    rr-node (U ~ #blocks; every (net, sink) slot holds an int32 row
    index) and FACTORED into the sink's distinct wire cells x its input
    pins: a cluster's equivalent pins hear the same few tracks, so the K
    hops of a sink name only C ~ K / 5 cells and P pins, and the wave
    reads a distance once a CELL and a pin's cost once a PIN (on the
    chip an element read out of a per-net canvas costs about 10 ns,
    PERF.md PR 29).  Pin p hears cell slot c iff ``uid_pcrank[u, p, c]``
    is not RANK_PAD; the rank is the hop's position k in the pin-major
    enumeration (the sink's IPINs in in-edge order, then each IPIN's
    in-edges), which is the order equal costs are broken in.

    Sizes from the shapes, a unique sink: (C + P) * 4 + P * C * 8 bytes
    against the K * 12 of a flat (cell, pin, delay) list.  At the
    benchmark's k6_N10 shape (K 1,320, C 256, P 33) that is 69 KB
    against 16 KB, 15 MB for its 227 sinks; at U = 10^4 unique sinks of
    that shape 690 MB against 160 MB, about 4x.  On a graph whose pins
    share no track (C = K) the dense table is P times the flat list and
    the form saves no read.  All host numpy; the Router uploads the
    tables once per route() call and keeps them device-resident."""
    opin_node: np.ndarray       # int32 [R, O] source-class OPINs (pad N)
    entry_cell: np.ndarray      # int32 [R, Ko] wire cell (pad Ncells)
    entry_oidx: np.ndarray      # int32 [R, Ko] index into opin_node (pad 0)
    entry_delay: np.ndarray     # f32  [R, Ko] edge delay OPIN -> wire
    sink_uid: np.ndarray        # int32 [R, S] unique-sink row (pad U)
    uid_ucell: np.ndarray       # int32 [U+1, C] distinct wire cells,
    #                             ascending (pad Ncells)
    uid_upin: np.ndarray        # int32 [U+1, P] IPIN nodes, in-edge
    #                             order (pad N)
    uid_pcdel: np.ndarray       # f32  [U+1, P, C] delay wire->IPIN->SINK
    #                             (0 where the pin does not hear the cell)
    uid_pcrank: np.ndarray      # int32 [U+1, P, C] rank k of the hop
    #                             (RANK_PAD where there is none)
    sink_cands: int             # K: most hops any sink has
    # dedicated direct connections (OPIN->IPIN edges, t_direct_inf):
    # per (net, sink) the best source-class OPIN that directly drives
    # one of the sink's IPINs (-1 = none) — the planes wave compares
    # this fabric-bypassing candidate against the relaxation candidates
    direct_oidx: np.ndarray     # int32 [R, S] index into opin_node / -1
    direct_ipin: np.ndarray     # int32 [R, S] IPIN node (pad N)
    direct_delay: np.ndarray    # f32  [R, S] OPIN->IPIN->SINK delay


def _ragged_flat(row_ptr: np.ndarray, nodes: np.ndarray):
    """Flatten the CSR slices row_ptr[n]:row_ptr[n+1] for every n in
    ``nodes``: returns (edge_idx [T], owner [T]) where owner[t] is the
    position in ``nodes`` the edge belongs to.  owner is nondecreasing,
    so per-owner running indices come from one cumsum."""
    deg = row_ptr[nodes + 1] - row_ptr[nodes]
    tot = int(deg.sum())
    owner = np.repeat(np.arange(len(nodes)), deg)
    off = np.arange(tot) - np.repeat(np.cumsum(deg) - deg, deg)
    return np.repeat(row_ptr[nodes], deg) + off, owner


def _within(owner: np.ndarray, n_owners: int):
    """Running index of each element within its (nondecreasing) owner."""
    cnt = np.bincount(owner, minlength=n_owners)
    return (np.arange(len(owner))
            - np.repeat(np.cumsum(cnt) - cnt, cnt)), cnt


def build_planes_terminals(rr: RRGraph, source: np.ndarray,
                           sinks: np.ndarray, cell_of_node: np.ndarray,
                           ncells: int) -> PlanesTerminals:
    """source [R], sinks [R, S] (-1 pad) -> terminal tables.  `ncells` is
    the table pad value (one past the last real cell: the batch step pads
    its dist arrays with one INF slot there — out-of-range pads would hit
    take_along_axis's NaN fill and poison every argmin).

    Fully vectorized (two-level ragged CSR flattening): the candidate
    order is identical to the per-net/per-sink loop it replaced (edge
    order within each row), so routing stays bit-deterministic; host
    build time is O(total edges touched) numpy work, which is what lets
    a 10^4-LUT circuit prepare in seconds."""
    R = len(source)
    S = sinks.shape[1]
    N = rr.num_nodes

    orp, odst, osw = rr.out_row_ptr, rr.out_dst, rr.out_switch
    irp, isrc, idel = rr.in_row_ptr, rr.in_src, rr.in_delay
    src = np.asarray(source, dtype=np.int64)

    # --- SOURCE side: net -> OPINs -> wire entries ---
    e1, net_of_op = _ragged_flat(orp, src)          # source out-edges
    op_nodes = odst[e1].astype(np.int64)            # [To] OPIN nodes
    oi_of_op, deg_o = _within(net_of_op, R)
    O = max(1, int(deg_o.max()) if R else 1)
    opin_node = np.full((R, O), N, dtype=np.int32)
    opin_node[net_of_op, oi_of_op] = op_nodes

    e2, op_of_e = _ragged_flat(orp, op_nodes)       # OPIN -> wire edges
    wires = odst[e2].astype(np.int64)
    esw = osw[e2].astype(np.int64)
    edel = (rr.switch_Tdel[esw] + rr.C[wires]
            * (rr.switch_R[esw] + 0.5 * rr.R[wires])).astype(np.float32)
    net_of_e = net_of_op[op_of_e]
    ki, ent_cnt = _within(net_of_e, R)
    Ko = max(1, int(ent_cnt.max()) if R else 1)
    entry_cell = np.full((R, Ko), ncells, dtype=np.int32)
    entry_oidx = np.zeros((R, Ko), dtype=np.int32)
    entry_delay = np.zeros((R, Ko), dtype=np.float32)
    entry_cell[net_of_e, ki] = cell_of_node[wires]
    entry_oidx[net_of_e, ki] = oi_of_op[op_of_e]
    entry_delay[net_of_e, ki] = edel

    # --- SINK side: unique sink nodes -> IPINs -> wire candidates
    # (shared sink classes repeat across nets; computed once per node) ---
    sk_flat = sinks.reshape(-1).astype(np.int64)
    valid = sk_flat >= 0
    uniq, inv = np.unique(sk_flat[valid], return_inverse=True)
    U = len(uniq)
    f1, u_of_1 = _ragged_flat(irp, uniq)            # sink in-edges
    ipins = isrc[f1].astype(np.int64)
    w1 = idel[f1].astype(np.float64)
    f2, p_of_2 = _ragged_flat(irp, ipins)           # ipin in-edges
    wires2 = isrc[f2].astype(np.int64)
    wtot = (w1[p_of_2] + idel[f2]).astype(np.float32)
    u_of_2 = u_of_1[p_of_2]
    k2, cand_cnt = _within(u_of_2, U)
    K = max(1, int(cand_cnt.max()) if U else 1)
    pin_of_1, pin_cnt = _within(u_of_1, U)
    P = max(1, int(pin_cnt.max()) if U else 1)
    cells2 = cell_of_node[wires2].astype(np.int64)
    ucells, cell_of_2 = np.unique(u_of_2 * (ncells + 1) + cells2,
                                  return_inverse=True)
    u_of_c = ucells // (ncells + 1)
    slot_of_c, cell_cnt = _within(u_of_c, U)
    C = max(1, int(cell_cnt.max()) if U else 1)
    # one pad row at U, and pad slots in every shorter row: cell=ncells
    # / ipin=N / delay=0 / no rank -- extraction on a pad slot sees
    # only INF-distance candidates
    u_ucell = np.full((U + 1, C), ncells, dtype=np.int32)
    u_upin = np.full((U + 1, P), N, dtype=np.int32)
    u_pcdel = np.zeros((U + 1, P, C), dtype=np.float32)
    u_pcrank = np.full((U + 1, P, C), RANK_PAD, dtype=np.int32)
    u_ucell[u_of_c, slot_of_c] = ucells % (ncells + 1)
    u_upin[u_of_1, pin_of_1] = ipins
    pslot, cslot = pin_of_1[p_of_2], slot_of_c[cell_of_2]
    # a (pin, cell) pair holds ONE hop.  Several OPIN -> IPIN edges of
    # one pin all sit on the pad cell (an OPIN has no cell) and can
    # never win on cost: the lowest rank stands for them, as it does
    # among equal costs.  Two edges from one WIRE into one pin would
    # need two delays in one slot
    _, first = np.unique((u_of_2 * P + pslot) * C + cslot,
                         return_index=True)
    if (np.delete(cells2, first) < ncells).any():
        raise ValueError("parallel wire -> IPIN edges: the sink tables "
                         "hold one hop per (pin, cell)")
    held = (u_of_2[first], pslot[first], cslot[first])
    u_pcdel[held] = wtot[first]
    u_pcrank[held] = k2[first]
    get_metrics().set_gauges({"route.sink_pick.cands_per_sink": K,
                              "route.sink_pick.cells_per_sink": C,
                              "route.sink_pick.pins_per_sink": P,
                              # real (cell, pin) hops over the dense
                              # [U, P, C] entries: 1 / P where a hard
                              # block's one-pin sinks sit beside a
                              # cluster's P equivalent inputs
                              "route.sink_pick.table_fill":
                              len(first) / max(1, U * P * C)})

    sink_uid = np.full(R * S, U, dtype=np.int32)
    sink_uid[valid] = inv.astype(np.int32)

    # --- direct connections: OPIN -> IPIN -> SINK candidates ---
    # (small: one pass over the graph's direct edges only)
    direct_oidx = np.full((R, S), -1, dtype=np.int32)
    direct_ipin = np.full((R, S), N, dtype=np.int32)
    direct_delay = np.zeros((R, S), dtype=np.float32)
    ntype = rr.node_type
    # OPIN -> IPIN edges present?
    from ..rr.graph import IPIN as _IPIN, OPIN as _OPIN
    e_is_direct = ((ntype[odst] == _IPIN)
                   & (ntype.repeat(np.diff(orp))[...] == _OPIN)
                   if len(odst) else np.zeros(0, bool))
    if e_is_direct.any():
        # ragged lookups over the REAL entries only (argwhere, not the
        # dense R*O / R*S nested loops — those are millions of python
        # iterations at synth10k scale)
        opin_owner: dict = {}
        for r, oi in np.argwhere(opin_node < N):
            opin_owner.setdefault(int(opin_node[r, oi]),
                                  []).append((int(r), int(oi)))
        sink_slots: dict = {}
        for r, s in np.argwhere(sinks >= 0):
            sink_slots.setdefault(int(sinks[r, s]),
                                  []).append((int(r), int(s)))
        e_src_all = np.repeat(np.arange(N), np.diff(orp))
        for e in np.where(e_is_direct)[0]:
            o, ip = int(e_src_all[e]), int(odst[e])
            if o not in opin_owner:
                continue
            esw = int(rr.out_switch[e])
            d1 = (rr.switch_Tdel[esw] + rr.C[ip]
                  * (rr.switch_R[esw] + 0.5 * rr.R[ip]))
            for e2 in range(orp[ip], orp[ip + 1]):
                snk = int(odst[e2])
                if snk not in sink_slots:
                    continue
                sw2 = int(rr.out_switch[e2])
                d2 = (rr.switch_Tdel[sw2] + rr.C[snk]
                      * (rr.switch_R[sw2] + 0.5 * rr.R[snk]))
                for (r, s) in sink_slots[snk]:
                    for (ro, oi) in opin_owner[o]:
                        if ro != r:
                            continue
                        dd = np.float32(d1 + d2)
                        if (direct_oidx[r, s] < 0
                                or dd < direct_delay[r, s]):
                            direct_oidx[r, s] = oi
                            direct_ipin[r, s] = ip
                            direct_delay[r, s] = dd
    return PlanesTerminals(opin_node, entry_cell, entry_oidx, entry_delay,
                           sink_uid.reshape(R, S), u_ucell, u_upin,
                           u_pcdel, u_pcrank, K,
                           direct_oidx, direct_ipin, direct_delay)




# ---------------------------------------------------------------------------
# The relaxation: min-plus scans + turn shifts, with (pred, wenter) payload
# ---------------------------------------------------------------------------


def _minplus_scan(d0, c, axis, reverse=False):
    """s[x] = min(d0[x], s[x-1] + c[x]) along axis (reverse: x+1 side).

    First-order (min, +) recurrence on pairs, combine((c1, m1),
    (c2, m2)) = (c1 + c2, min(m1 + c2, m2)), grouped as the odd-even
    tree of a parallel prefix scan (tests/scan_refs.py keeps the
    library's whole-array form of it as the oracle: the same combines
    on the same operands, so the same bits) and run on the axis's
    n = shape[axis] per-position SLABS: static slices in, ONE
    concatenate out, a reverse scan the list reversed.  The whole-array
    form puts the tree's halves back together by interior pads and an
    add of zeros a level and flips the canvases around a reverse scan;
    on the v5e none of that fuses (a fifth to a third of the
    relaxation's traffic).  n is 11 to 27 in the benchmark's cells:
    some 2n combines of one slab."""
    n = d0.shape[axis]
    els = [(lax.slice_in_dim(c, i, i + 1, axis=axis),
            lax.slice_in_dim(d0, i, i + 1, axis=axis)) for i in range(n)]
    if reverse:
        els.reverse()

    def tree(els):
        """The m half of the scan of ``els``; the c half of a scanned
        pair feeds nothing."""
        if len(els) < 2:
            return [els[0][1]]
        odd = tree([(ca + cb, jnp.minimum(ma + cb, mb))
                    for (ca, ma), (cb, mb) in zip(els[0::2], els[1::2])])
        even = [els[0][1]] + [jnp.minimum(m + cb, mb)
                              for m, (cb, mb) in zip(odd, els[2::2])]
        return [m for pair in zip(even, odd) for m in pair] + even[len(odd):]

    s = tree(els)
    if reverse:
        s.reverse()
    return lax.concatenate(s, axis)


def _scan_update(d, pred, w, cstep, wstep, self_idx, stride, axis,
                 reverse, guard=False):
    """Run one directional scan and fold (dist, pred, wenter): improved
    cells point at the immediate neighbor in the scan direction.

    ``guard`` (static): a cell is NOT improved over a free step (inside
    a wire span) from the neighbour whose own predecessor it is.  The
    scan's odd-even tree sums a path's costs in an order that differs
    cell to cell, so a cell reached THROUGH its span neighbour can come out
    an ulp below it; the opposite scan would then improve the neighbour
    from it, ``pred[A] = B`` and ``pred[B] = A``, and the traceback
    circles between the two for its budget and leaves a sink unreached.
    Inside a span every cell is the same node, so refusing the step
    loses no path; a span that truly turns round (the neighbour's
    predecessor is some other cell) is improved as before."""
    s = _minplus_scan(d, cstep, axis, reverse)
    imp = s < d
    if guard:
        from_me = jnp.roll(pred, -1 if reverse else 1, axis) == self_idx
        imp &= ~((cstep == 0.0) & from_me)
    nb = self_idx + (stride if reverse else -stride)
    return (jnp.where(imp, s, d),
            jnp.where(imp, nb, pred),
            jnp.where(imp, wstep, w))


@struct.dataclass
class PlanesGeom:
    """Sweep-body geometry with an explicit leading broadcast axis G:
    G == 1 (shared, the whole-grid program — arrays are the PlanesGraph
    fields expanded with [None]) or G == B (per-net bb-CROPPED views of
    the same arrays: each net's masks/delays/ids sliced at its crop
    origin).  The sweep body is written once against this layout; the
    crop is the planes analogue of the reference's per-net bounding
    boxes (route.h:70-165) — work per net scales with its bb, not the
    device.

    idxx/idxy carry GLOBAL flat cell ids (pred payloads and scan
    neighbor strides stay in global index space, so traceback and the
    scatter-back are crop-agnostic); base_par carries the GLOBAL corner
    parity (x + y) % 2 so rotated-turn parity survives cropping."""
    brk_before_x: jnp.ndarray       # [G, W, X, Y+1] (crop-local X/Y)
    brk_after_x: jnp.ndarray
    brk_before_y: jnp.ndarray       # [G, W, X+1, Y]
    brk_after_y: jnp.ndarray
    first_x: jnp.ndarray
    last_x: jnp.ndarray
    first_y: jnp.ndarray
    last_y: jnp.ndarray
    delay_x: jnp.ndarray
    delay_y: jnp.ndarray
    delay_y_rot0: jnp.ndarray
    delay_y_rot1: jnp.ndarray
    idxx: jnp.ndarray               # int32 [G, W, X, Y+1] global ids
    idxy: jnp.ndarray               # int32 [G, W, X+1, Y]
    base_par: jnp.ndarray           # int32 [G, X+1, Y+1] global (x+y)%2
    stride_x: int = struct.field(pytree_node=False, default=0)  # global NY+1
    directional: bool = struct.field(pytree_node=False, default=False)
    inc_track: Optional[jnp.ndarray] = None     # bool [W] (shared)
    group_tracks: int = struct.field(pytree_node=False, default=0)
    scan_guard: bool = struct.field(pytree_node=False, default=False)

    @property
    def shape_x(self):
        return self.brk_before_x.shape[1:]      # (W, X, Y+1) crop-local

    @property
    def shape_y(self):
        return self.brk_before_y.shape[1:]


def geom_full(pg: PlanesGraph) -> PlanesGeom:
    """The G=1 shared geometry of the whole grid (views, no copies)."""
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1
    idxx = jnp.arange(ncx, dtype=jnp.int32).reshape(1, W, NX, NYp1)
    idxy = (ncx + jnp.arange(W * NXp1 * NY, dtype=jnp.int32)
            ).reshape(1, W, NXp1, NY)
    base_par = ((jnp.arange(NX + 1)[:, None]
                 + jnp.arange(NY + 1)[None, :]) % 2)[None]
    return PlanesGeom(
        brk_before_x=pg.brk_before_x[None], brk_after_x=pg.brk_after_x[None],
        brk_before_y=pg.brk_before_y[None], brk_after_y=pg.brk_after_y[None],
        first_x=pg.first_x[None], last_x=pg.last_x[None],
        first_y=pg.first_y[None], last_y=pg.last_y[None],
        delay_x=pg.delay_x[None], delay_y=pg.delay_y[None],
        delay_y_rot0=pg.delay_y_rot0[None],
        delay_y_rot1=pg.delay_y_rot1[None],
        idxx=idxx, idxy=idxy, base_par=base_par,
        stride_x=NYp1, directional=pg.directional,
        inc_track=pg.inc_track, group_tracks=pg.group_tracks,
        scan_guard=pg.scan_guard)


def _shift_bits(n: int):
    """The powers of two whose sums are the shifts 0..n (static)."""
    return [1 << j for j in range(max(n, 0).bit_length())]


def _pad_axis(a, before: int, after: int, axis: int):
    """Zeros before and after ``a`` along ``axis``: ``lax.pad`` with a
    host scalar (``jnp.pad`` converts its constant by an eager op a
    call, and these forms pad a hundred times a program's trace)."""
    config = [(0, 0, 0)] * a.ndim
    config[axis] = (before, after, 0)
    return lax.pad(a, np.zeros((), a.dtype), config)


def _cut_axis(a, o, size: int, axis: int):
    """``size`` entries of ``a`` along ``axis`` from the per-net start
    ``o`` (int32, broadcastable against ``a``, 0 <= o <= a.shape[axis]
    - size): one select a BIT of the largest start, highest bit first,
    between two static slices -- the array shrinks to what the lower
    bits can still reach as the shift resolves.  No gather: XLA:TPU
    expands a per-net dynamic slice into a loop over the batch."""
    for k in reversed(_shift_bits(a.shape[axis] - size)):
        L = a.shape[axis]
        keep = min(size + k - 1, L)
        lo = lax.slice_in_dim(a, 0, keep, axis=axis)
        hi = lax.slice_in_dim(a, k, min(k + keep, L), axis=axis)
        if hi.shape[axis] < keep:
            # past the end only where a set bit k leaves the lower bits
            # less than k - 1 to add: never part of a tile
            hi = _pad_axis(hi, 0, keep - hi.shape[axis], axis)
        a = jnp.where((o & k) != 0, hi, lo)
    return a


def _place_axis(t, o, length: int, axis: int):
    """The mirror of _cut_axis: ``t`` moved right by the per-net ``o``
    along ``axis`` into ``length`` entries (0 <= o <= length -
    t.shape[axis]); what lies outside the moved tile is unspecified."""
    for k in _shift_bits(length - t.shape[axis]):   # lowest first: grows
        L = t.shape[axis]
        new = min(L + k, length)
        hi = lax.slice_in_dim(_pad_axis(t, k, 0, axis), 0, new, axis=axis)
        t = jnp.where((o & k) != 0, hi, _pad_axis(t, 0, new - L, axis))
    return t


def cut_tiles(a, ox, oy, xs: int, ys: int):
    """Per-net tiles of the canvases ``a`` [G, ..., X, Y] (G == 1: one
    canvas shared by the batch, or G == B): net b's (xs, ys) tile
    starts at (ox[b], oy[b]) -> [B, ..., xs, ys]."""
    o = (slice(None),) + (None,) * (a.ndim - 1)
    t = _cut_axis(_cut_axis(a, ox[o], xs, a.ndim - 2), oy[o], ys,
                  a.ndim - 1)
    return jnp.broadcast_to(t, ox.shape + t.shape[1:])


def put_tiles(full, tiles, ox, oy):
    """``full`` [G, ..., X, Y] with net b's tile written at (ox[b],
    oy[b]) -> [B, ..., X, Y]: the tile moved to its place by
    _place_axis, then ONE select under the tile's footprint (two
    iotas against the origins).  No scatter, no loop over the batch."""
    nd = full.ndim
    X, Y = full.shape[-2:]
    xs, ys = tiles.shape[-2:]
    o = (slice(None),) + (None,) * (nd - 1)
    ox, oy = ox[o], oy[o]
    moved = _place_axis(_place_axis(tiles, oy, Y, nd - 1), ox, X, nd - 2)
    ix = lax.broadcasted_iota(jnp.int32, (1,) * (nd - 2) + (X, 1), nd - 2)
    iy = lax.broadcasted_iota(jnp.int32, (1,) * (nd - 1) + (Y,), nd - 1)
    inside = ((ix >= ox) & (ix < ox + xs) & (iy >= oy) & (iy < oy + ys))
    return jnp.where(inside, moved, full)


def geom_cropped(pg: PlanesGraph, ox, oy, cnx: int,
                 cny: int) -> PlanesGeom:
    """Per-net cropped geometry: net b's slice starts at grid cell
    (ox[b], oy[b]) and spans a STATIC (cnx, cny) tile (compile-time;
    the caller buckets tile sizes).  Exact iff every wire a net may
    legally use (bb-intersecting, see the window cc mask) lies inside
    its tile — callers expand the bb by (max wire length - 1) and clamp
    to the grid."""
    full = geom_full(pg)
    NYp1 = pg.shape_x[2]

    def crop(a, xs, ys):
        # a: [1, (W,) X, Y]; per-net tile -> [B, (W,) xs, ys]
        return cut_tiles(a, ox, oy, xs, ys)

    return PlanesGeom(
        brk_before_x=crop(full.brk_before_x, cnx, cny + 1),
        brk_after_x=crop(full.brk_after_x, cnx, cny + 1),
        brk_before_y=crop(full.brk_before_y, cnx + 1, cny),
        brk_after_y=crop(full.brk_after_y, cnx + 1, cny),
        first_x=crop(full.first_x, cnx, cny + 1),
        last_x=crop(full.last_x, cnx, cny + 1),
        first_y=crop(full.first_y, cnx + 1, cny),
        last_y=crop(full.last_y, cnx + 1, cny),
        delay_x=crop(full.delay_x, cnx, cny + 1),
        delay_y=crop(full.delay_y, cnx + 1, cny),
        delay_y_rot0=crop(full.delay_y_rot0, cnx + 1, cny),
        delay_y_rot1=crop(full.delay_y_rot1, cnx + 1, cny),
        idxx=crop(full.idxx, cnx, cny + 1),
        idxy=crop(full.idxy, cnx + 1, cny),
        base_par=crop(full.base_par, cnx + 1, cny + 1),
        stride_x=NYp1, directional=pg.directional,
        inc_track=pg.inc_track, group_tracks=pg.group_tracks,
        scan_guard=pg.scan_guard)


def _group_corner_min(gm: PlanesGeom, src, idx, axis: int, shift: int):
    """Unidir turns, the source half: ``src`` [B, W, NX+2, NY+2] is a
    padded canvas of one plane, INF wherever a cell has no exit, with
    positions as indices along ``axis``; ``idx`` its global cell ids.
    Returns, per corner and turn GROUP, the cheapest wire exiting there
    -- an INC track's cell at the corner's own position, a DEC track's
    one further -- as (value [B, G, NX+1, NY+1], source cell), read
    from group g - ``shift`` at odd corners (rr/graph.py
    ``unidir_turn_group``): one fold over a group's lanes and one roll
    over groups, no gather over tracks."""
    W = src.shape[1]
    gt = gm.group_tracks
    n = src.shape[axis] - 1
    inc = gm.inc_track[:, None, None]

    def at_corner(a):
        lo = lax.slice_in_dim(a, 0, n, axis=axis)
        hi = lax.slice_in_dim(a, 1, n + 1, axis=axis)
        other = 5 - axis
        m = a.shape[other] - 1
        a = jnp.where(inc, lax.slice_in_dim(lo, 0, m, axis=other),
                      lax.slice_in_dim(hi, 0, m, axis=other))
        return a.reshape(a.shape[0], W // gt, gt, *a.shape[2:])

    v, i = at_corner(src), at_corner(idx)
    best, bi = v[:, :, 0], jnp.broadcast_to(i[:, :, 0], v[:, :, 0].shape)
    for lane in range(1, gt):
        better = v[:, :, lane] < best
        best = jnp.where(better, v[:, :, lane], best)
        bi = jnp.where(better, i[:, :, lane], bi)
    odd = (gm.base_par == 1)[:, None]
    return (jnp.where(odd, jnp.roll(best, shift, axis=1), best),
            jnp.where(odd, jnp.roll(bi, shift, axis=1), bi))


def _group_to_tracks(gm: PlanesGeom, for_inc, for_dec):
    """Unidir turns, the target half: per-group corner values [B, G,
    X, Y], one view aligned to the cells whose INC wire starts at the
    corner and one to the DEC ones, spread onto every track of the
    group ([B, W, X, Y]; track = (group, lane, direction))."""
    B, G, X, Y = for_inc.shape
    lanes = gm.group_tracks // 2
    a = jnp.stack([for_inc, for_dec], axis=2)[:, :, None]
    return jnp.broadcast_to(a, (B, G, lanes, 2, X, Y)).reshape(
        B, G * lanes * 2, X, Y)


def _turn_triples_into_y(gm: PlanesGeom, dx, crit_c, cc_y):
    """Best switchbox-turn candidate INTO each chany cell from dx.

    Returns (val, src, w): [B, W, NX+1, NY] candidate cost, global source
    cell index, true enter delay.  For target chany cell (t', x, v),
    contributions come from chanx cells (x+a, v-b), a,b in {0,1}, at
    corner (x, v-b); the edge exists iff the source cell ends at the
    corner (a=0: last_x, a=1: first_x) OR the target does (b=0: last_y,
    b=1: first_y).  Rotated turns take t = (t'-1-parity) mod W with
    parity = (x + v - b) mod 2 — a roll along the track axis applied
    identically to the value and index canvases."""
    B = dx.shape[0]
    W, NX, NYp1 = gm.shape_x
    NY = NYp1 - 1
    G = gm.idxx.shape[0]

    def canvas_x(a, fill):
        c = jnp.full((a.shape[0], W, NX + 2, NY + 2), fill, a.dtype)
        return c.at[:, :, 1:NX + 1, 0:NY + 1].set(a)

    ix = canvas_x(gm.idxx, jnp.int32(0))            # [G, W, NX+2, NY+2]

    if gm.directional:
        # unidir: every chanx wire that EXITS at corner (x, y) -- an INC
        # cell at position x, a DEC cell at x + 1 -- drives the chany
        # wires that START there in its group, or the next group at an
        # odd corner: INC starts at first_y (the corner below, b=1),
        # DEC at last_y (b=0).  Target's switch throughout (delay_y).
        exit_x = jnp.where(gm.inc_track[:, None, None], gm.last_x,
                           gm.first_x)
        cv, ci = _group_corner_min(
            gm, canvas_x(jnp.where(exit_x, dx, INF), INF), ix,
            axis=2, shift=1)
        tv, ts = (_group_to_tracks(gm, a[..., 0:NY], a[..., 1:NY + 1])
                  for a in (cv, ci))
        start_y = jnp.where(gm.inc_track[:, None, None], gm.first_y,
                            gm.last_y)
        cand = jnp.where(start_y, tv, INF) + crit_c * gm.delay_y + cc_y
        return cand, ts, jnp.broadcast_to(gm.delay_y, cand.shape)

    cx_all = canvas_x(dx, INF)
    cx_last = canvas_x(jnp.where(gm.last_x, dx, INF), INF)
    cx_first = canvas_x(jnp.where(gm.first_x, dx, INF), INF)

    best = jnp.full((B, W, NX + 1, NY), INF, dx.dtype)
    bsrc = jnp.zeros((B, W, NX + 1, NY), jnp.int32)
    bw = jnp.zeros((B, W, NX + 1, NY), jnp.float32)

    def fold(best, bsrc, bw, cand, src, w):
        better = cand < best
        return (jnp.where(better, cand, best),
                jnp.where(better, src, bsrc),
                jnp.where(better, w, bw))

    for b_off in (0, 1):
        tgt_gate = gm.last_y if b_off == 0 else gm.first_y
        par = gm.base_par[:, :, 1 - b_off:1 - b_off + NY]
        for a_off in (0, 1):
            src_gated = cx_last if a_off == 0 else cx_first
            sl = (slice(None), slice(None),
                  slice(a_off, a_off + NX + 1),
                  slice(1 - b_off, 1 - b_off + NY))
            v_any = cx_all[sl]
            v_src = src_gated[sl]
            src_i = ix[sl]
            cand = jnp.minimum(v_src, jnp.where(tgt_gate, v_any, INF))
            cand = cand + crit_c * gm.delay_y + cc_y
            best, bsrc, bw = fold(best, bsrc, bw, cand, src_i, gm.delay_y)
            for p in (0, 1):
                if (1 + p) % W == 0:
                    continue
                r_all = jnp.roll(cx_all, 1 + p, axis=1)[sl]
                r_src = jnp.roll(src_gated, 1 + p, axis=1)[sl]
                r_i = jnp.roll(ix, 1 + p, axis=1)[sl]
                dly = gm.delay_y_rot0 if p == 0 else gm.delay_y_rot1
                cand = jnp.minimum(r_src, jnp.where(tgt_gate, r_all, INF))
                cand = cand + crit_c * dly + cc_y
                cand = jnp.where(par[:, None] == p, cand, INF)
                best, bsrc, bw = fold(best, bsrc, bw, cand, r_i, dly)
    return best, bsrc, bw


def _turn_triples_into_x(gm: PlanesGeom, dy, crit_c, cc_x):
    """Mirror of _turn_triples_into_y: candidates INTO the chanx plane.
    Target chanx cell (t, u, y) receives from chany cells (u-a, y+b) at
    corner (u-a, y); gates: src b=0: last_y, b=1: first_y; tgt a=0:
    last_x, a=1: first_x.  Rotated source track is (t+1+parity) mod W with
    parity = (u-a+y) mod 2; both rotated directions use the CHANX track's
    switch (delay_x, see rr/graph.py edge emission)."""
    B = dy.shape[0]
    W, NXp1, NY = gm.shape_y
    NX = NXp1 - 1

    def canvas_y(a, fill):
        c = jnp.full((a.shape[0], W, NX + 2, NY + 2), fill, a.dtype)
        return c.at[:, :, 0:NX + 1, 1:NY + 1].set(a)

    iy = canvas_y(gm.idxy, jnp.int32(0))            # [G, W, NX+2, NY+2]

    if gm.directional:
        # unidir mirror: chany wires that exit at corner (x, y) -- INC
        # at position y, DEC at y + 1 -- drive the chanx wires starting
        # there in their group, or the previous group at an odd corner:
        # INC starts at first_x (the corner to its left), DEC at last_x
        exit_y = jnp.where(gm.inc_track[:, None, None], gm.last_y,
                           gm.first_y)
        cv, ci = _group_corner_min(
            gm, canvas_y(jnp.where(exit_y, dy, INF), INF), iy,
            axis=3, shift=-1)
        tv, ts = (_group_to_tracks(gm, a[:, :, 0:NX], a[:, :, 1:NX + 1])
                  for a in (cv, ci))
        start_x = jnp.where(gm.inc_track[:, None, None], gm.first_x,
                            gm.last_x)
        cand = jnp.where(start_x, tv, INF) + crit_c * gm.delay_x + cc_x
        return cand, ts, jnp.broadcast_to(gm.delay_x, cand.shape)

    cy_all = canvas_y(dy, INF)
    cy_last = canvas_y(jnp.where(gm.last_y, dy, INF), INF)
    cy_first = canvas_y(jnp.where(gm.first_y, dy, INF), INF)

    best = jnp.full((B, W, NX, NY + 1), INF, dy.dtype)
    bsrc = jnp.zeros((B, W, NX, NY + 1), jnp.int32)
    bw = jnp.zeros((B, W, NX, NY + 1), jnp.float32)

    def fold(best, bsrc, bw, cand, src, w):
        better = cand < best
        return (jnp.where(better, cand, best),
                jnp.where(better, src, bsrc),
                jnp.where(better, w, bw))

    for a_off in (0, 1):
        tgt_gate = gm.last_x if a_off == 0 else gm.first_x
        par = gm.base_par[:, 1 - a_off:1 - a_off + NX, :]
        for b_off in (0, 1):
            src_gated = cy_last if b_off == 0 else cy_first
            sl = (slice(None), slice(None),
                  slice(1 - a_off, 1 - a_off + NX),
                  slice(b_off, b_off + NY + 1))
            v_any = cy_all[sl]
            v_src = src_gated[sl]
            src_i = iy[sl]
            cand = jnp.minimum(v_src, jnp.where(tgt_gate, v_any, INF))
            cand = cand + crit_c * gm.delay_x + cc_x
            best, bsrc, bw = fold(best, bsrc, bw, cand, src_i, gm.delay_x)
            for p in (0, 1):
                if (1 + p) % W == 0:
                    continue
                r_all = jnp.roll(cy_all, -(1 + p), axis=1)[sl]
                r_src = jnp.roll(src_gated, -(1 + p), axis=1)[sl]
                r_i = jnp.roll(iy, -(1 + p), axis=1)[sl]
                cand = jnp.minimum(r_src, jnp.where(tgt_gate, r_all, INF))
                cand = cand + crit_c * gm.delay_x + cc_x
                cand = jnp.where(par[:, None] == p, cand, INF)
                best, bsrc, bw = fold(best, bsrc, bw, cand, r_i,
                                      gm.delay_x)
    return best, bsrc, bw


def _sweep_costs(gm: PlanesGeom, crit_c, cc_x, cc_y):
    """Scan step costs: pay switch delay + congestion only at span
    breaks.  Unidir: a forward (increasing-coordinate) scan may cross a
    break only on INC tracks, a backward scan only on DEC tracks —
    crossing against a wire's direction is blocked (INF).  Within-span
    motion stays free in both scans (the span is one node)."""
    cost_x = crit_c * gm.delay_x + cc_x
    cost_y = crit_c * gm.delay_y + cc_y
    if gm.directional:
        inc = gm.inc_track[:, None, None]
        cfx = jnp.where(gm.brk_before_x, jnp.where(inc, cost_x, INF), 0.0)
        cbx = jnp.where(gm.brk_after_x, jnp.where(inc, INF, cost_x), 0.0)
        cfy = jnp.where(gm.brk_before_y, jnp.where(inc, cost_y, INF), 0.0)
        cby = jnp.where(gm.brk_after_y, jnp.where(inc, INF, cost_y), 0.0)
    else:
        cfx = jnp.where(gm.brk_before_x, cost_x, 0.0)
        cbx = jnp.where(gm.brk_after_x, cost_x, 0.0)
        cfy = jnp.where(gm.brk_before_y, cost_y, 0.0)
        cby = jnp.where(gm.brk_after_y, cost_y, 0.0)
    wfx = jnp.where(gm.brk_before_x, gm.delay_x, 0.0)
    wbx = jnp.where(gm.brk_after_x, gm.delay_x, 0.0)
    wfy = jnp.where(gm.brk_before_y, gm.delay_y, 0.0)
    wby = jnp.where(gm.brk_after_y, gm.delay_y, 0.0)
    return cfx, cbx, cfy, cby, wfx, wbx, wfy, wby


def _sweep_once(gm: PlanesGeom, s, crit_c, cc_x, cc_y, costs):
    """One relaxation sweep (2 x-scans, turn into y, 2 y-scans, turn
    into x) over the (dist, pred, wenter) state — THE shared body of
    planes_relax, planes_relax_cropped and the row-sharded
    planes_shard.planes_relax_sharded.  Scan-neighbor
    strides use gm.stride_x (the GLOBAL flat-index stride), so pred
    payloads stay in global cell-id space under cropping."""
    cfx, cbx, cfy, cby, wfx, wbx, wfy, wby = costs
    dx, dy, predx, predy, wx, wy = s
    with device_scope("route.dev.relax.scan"):
        dx, predx, wx = _scan_update(dx, predx, wx, cfx, wfx, gm.idxx,
                                     gm.stride_x, 2, False, gm.scan_guard)
        dx, predx, wx = _scan_update(dx, predx, wx, cbx, wbx, gm.idxx,
                                     gm.stride_x, 2, True, gm.scan_guard)
    with device_scope("route.dev.relax.turn"):
        tv, ts, tw = _turn_triples_into_y(gm, dx, crit_c, cc_y)
        imp = tv < dy
        dy = jnp.where(imp, tv, dy)
        predy = jnp.where(imp, ts, predy)
        wy = jnp.where(imp, tw, wy)
    with device_scope("route.dev.relax.scan"):
        dy, predy, wy = _scan_update(dy, predy, wy, cfy, wfy, gm.idxy,
                                     1, 3, False, gm.scan_guard)
        dy, predy, wy = _scan_update(dy, predy, wy, cby, wby, gm.idxy,
                                     1, 3, True, gm.scan_guard)
    with device_scope("route.dev.relax.turn"):
        tv, ts, tw = _turn_triples_into_x(gm, dy, crit_c, cc_x)
        imp = tv < dx
        dx = jnp.where(imp, tv, dx)
        predx = jnp.where(imp, ts, predx)
        wx = jnp.where(imp, tw, wx)
    return dx, dy, predx, predy, wx, wy


# Storage dtypes of the distance/backtrack planes.  "f32" is the
# bit-exact oracle.  "bf16" halves the bytes every sweep's loop-carried
# state moves: the dist/wenter canvases are CARRIED in bfloat16 between
# sweeps while every sweep body still runs in f32 — the wavefront-min
# reduction (the min-plus scans and turn folds) accumulates in f32 and
# only the per-sweep requantization rounds.  pred stays int32 (exact global cell
# indices) and crit stays f32; the congestion input is quantized ONCE
# through the plane dtype (see planes_relax), the third loop-carried
# set the byte model counts at the storage width.
PLANE_DTYPES = ("f32", "bf16")


def plane_jnp_dtype(plane_dtype: str):
    """jnp storage dtype of a plane-dtype name."""
    if plane_dtype not in PLANE_DTYPES:
        raise ValueError(
            f"plane_dtype must be one of {PLANE_DTYPES}, "
            f"got {plane_dtype!r}")
    return jnp.bfloat16 if plane_dtype == "bf16" else jnp.float32


def plane_itemsize(plane_dtype: str) -> int:
    """Storage bytes per plane cell — the multiplier of the modeled
    sweep traffic (xla_bytes_per_cell)."""
    return 2 if plane_dtype == "bf16" else 4


def xla_bytes_per_cell(itemsize: int = 4) -> int:
    """Modeled HBM bytes one USEFUL cell moves per XLA sweep: ~15
    canvas traversals, of which the three loop-carried storage sets
    (dist, wenter, congestion) take the plane dtype while the scan and
    turn intermediates XLA materialises stay f32 (60 B/cell in f32,
    54 in bf16).  A model no chip run has checked; it feeds the
    route.kernel.bytes_per_sweep gauge."""
    return 3 * int(itemsize) + 12 * 4


def quantize_plane_state(s, plane_dtype: str):
    """(dx, dy, predx, predy, wx, wy) -> storage dtypes: the dist and
    wenter payloads take the plane dtype (round-to-nearest), pred stays
    int32.  A no-op cast when the state already carries the dtype."""
    dt = plane_jnp_dtype(plane_dtype)
    dx, dy, px, py, wx, wy = s
    return (dx.astype(dt), dy.astype(dt), px, py,
            wx.astype(dt), wy.astype(dt))


def _dequantize_plane_state(s):
    dx, dy, px, py, wx, wy = s
    f32 = jnp.float32
    return (dx.astype(f32), dy.astype(f32), px, py,
            wx.astype(f32), wy.astype(f32))


def _run_relax(sweep_fn, state0, nsweeps: int, plane_dtype: str = "f32"):
    """Run ``sweep_fn`` to the fixpoint or ``nsweeps`` times, whichever
    comes first, via a bounded ``lax.while_loop``.

    The sweep is a monotone strict-improvement update (a cell's dist
    only changes by decreasing, and pred/wenter change iff dist does),
    so "no dx/dy cell improved" is an exact fixpoint test: once a sweep
    leaves the distances unchanged, every further sweep is an identity
    and the early exit is bit-identical to running the remaining trips.
    The static ``nsweeps`` stays as the trip-count ceiling so the
    device always sees a bounded loop.

    With ``plane_dtype="bf16"`` the loop-carried dist/wenter state is
    stored in bfloat16: each trip upcasts to f32, runs the f32 sweep
    body, and requantizes.  The fixpoint test compares the QUANTIZED
    distances — still exact, because round-to-nearest of a value below
    a bf16 number cannot round above it, so quantized distances stay
    monotone non-increasing and "unchanged" still implies every further
    trip is an identity.

    Returns (state, stats) with stats = int32[2] (sweeps executed,
    sweeps useful).  A sweep is "useful" if it changed some distance;
    the one extra sweep spent discovering the fixpoint is counted as
    executed-but-wasted.  When the loop hits the ceiling while still
    improving, every executed sweep was useful."""

    def cond(carry):
        i, go, _ = carry
        return go & (i < nsweeps)

    if plane_dtype != "f32":
        state0 = quantize_plane_state(state0, plane_dtype)

        def body(carry):
            i, _, s = carry
            s2 = quantize_plane_state(
                sweep_fn(_dequantize_plane_state(s)), plane_dtype)
            changed = (jnp.any(s2[0] < s[0]) | jnp.any(s2[1] < s[1]))
            return i + 1, changed, s2
    else:
        def body(carry):
            i, _, s = carry
            s2 = sweep_fn(s)
            changed = (jnp.any(s2[0] < s[0]) | jnp.any(s2[1] < s[1]))
            return i + 1, changed, s2

    i, go, state = lax.while_loop(
        cond, body, (jnp.int32(0), jnp.bool_(True), state0))
    useful = jnp.maximum(jnp.int32(0), i - jnp.where(go, 0, 1))
    return state, jnp.stack([i, useful]).astype(jnp.int32)


def planes_relax(pg: PlanesGraph, d0_flat, cc_flat, crit_c, wenter0,
                 nsweeps: int, mesh=None, plane_dtype: str = "f32"):
    """Fixed-sweep planes relaxation with predecessor tracking.

    d0_flat [B, Ncells] seeded initial distances (pred of a seeded cell is
    itself — the walk's stop condition); cc_flat congestion cost per cell
    (already (1-crit)-scaled, jittered, INF outside the net bb); crit_c
    [B, 1, 1, 1]; wenter0 [B, Ncells] true delay payload at seeds (entry
    edge delay for SOURCE-side entries, 0 for tree cells).

    The sweep count is a STATIC ceiling: the loop is a bounded
    ``lax.while_loop`` that exits as soon as a sweep improves no
    distance (see _run_relax — exact, because updates are strict
    improvements), and ``nsweeps`` — sized by the Router from the
    batch's bounding boxes (one sweep spans a whole row, so #turns+1
    sweeps suffice) — caps the trip count so the device always sees a
    bounded loop, with the unreached-sink widening retry as the safety
    net.

    With ``mesh`` (a (net, node) jax.sharding.Mesh), the [B, W, X, Y]
    canvases — the state that grows with device size — are constrained
    over the mesh: batch on the "net" axis, the X grid axis on the
    "node" axis (the planes analogue of the reference's spatial rr-graph
    partition, rr_graph_partitioner.h:840).  The x-direction min-plus
    scans then run as GSPMD segmented scans with cross-shard prefix
    exchange (the boundary-node messaging of route.h:330-365, inserted
    by the compiler), y-scans and track rolls stay shard-local.

    Returns (dist_flat, pred_flat, wenter_flat, stats) with stats =
    int32[2] (sweeps executed, sweeps useful)."""
    B = d0_flat.shape[0]
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def cshard(t):
            return lax.with_sharding_constraint(
                t, NamedSharding(mesh, P("net", None, "node", None)))
    else:
        def cshard(t):
            return t

    dx = cshard(d0_flat[:, :ncx].reshape(B, W, NX, NYp1))
    dy = cshard(d0_flat[:, ncx:].reshape(B, W, NXp1, NY))
    cc_x = cshard(cc_flat[:, :ncx].reshape(B, W, NX, NYp1))
    cc_y = cshard(cc_flat[:, ncx:].reshape(B, W, NXp1, NY))
    if plane_dtype != "f32":
        # quantize the congestion input ONCE through the plane dtype
        # (round trip back to f32 for the sweep body): the mode's
        # costs are the ones a storage-dtype congestion plane holds
        dt = plane_jnp_dtype(plane_dtype)
        cc_x = cc_x.astype(dt).astype(jnp.float32)
        cc_y = cc_y.astype(dt).astype(jnp.float32)

    gm = geom_full(pg)
    predx = jnp.broadcast_to(gm.idxx, dx.shape)
    predy = jnp.broadcast_to(gm.idxy, dy.shape)
    wx = wenter0[:, :ncx].reshape(B, W, NX, NYp1)
    wy = wenter0[:, ncx:].reshape(B, W, NXp1, NY)

    costs = _sweep_costs(gm, crit_c, cc_x, cc_y)

    def sweep(s):
        s = _sweep_once(gm, s, crit_c, cc_x, cc_y, costs)
        # keep the loop-carried canvases pinned to the mesh layout so
        # GSPMD doesn't migrate them between sweeps
        return tuple(cshard(t) for t in s)

    (dx, dy, predx, predy, wx, wy), stats = _run_relax(
        sweep, (dx, dy, predx, predy, wx, wy), nsweeps, plane_dtype)
    if plane_dtype != "f32":
        # downstream (sink extraction, traceback, delay accumulation)
        # consumes f32 flats regardless of the storage dtype
        dx, dy, wx, wy = (a.astype(jnp.float32)
                          for a in (dx, dy, wx, wy))

    def flat(a, b):
        return jnp.concatenate([a.reshape(B, -1), b.reshape(B, -1)],
                               axis=1)

    return flat(dx, dy), flat(predx, predy), flat(wx, wy), stats


@struct.dataclass
class CropCut:
    """What a cropped relaxation needs that its origins fix: the
    cropped geometry and the tiles of a congestion field.  A step cuts
    it ONCE (its origins hold for every wave) from the step's unscaled
    field and each wave scales the tiles by its own weight."""
    gm: PlanesGeom
    cc_x: jnp.ndarray               # [B, W, cnx, cny+1]
    cc_y: jnp.ndarray               # [B, W, cnx+1, cny]

    def scaled(self, cw):
        """The tiles of ``cw[:, None] * field``: the same multiply an
        element as scaling the field and cutting it after.  The
        products are held as values of their own, as a field scaled
        before the cut is: fused into the sum that takes them, a
        backend may round the two differently (XLA:CPU contracts one
        of a sum's two products into a fused multiply-add)."""
        cw = cw[:, None, None, None]
        cc_x, cc_y = lax.optimization_barrier(
            (cw * self.cc_x, cw * self.cc_y))
        return self.replace(cc_x=cc_x, cc_y=cc_y)


def _canvases(pg: PlanesGraph, flat):
    """[B, ncells] -> the ([B, W, NX, NY+1], [B, W, NX+1, NY]) planes."""
    B = flat.shape[0]
    ncx = pg.shape_x[0] * pg.shape_x[1] * pg.shape_x[2]
    return (flat[:, :ncx].reshape(B, *pg.shape_x),
            flat[:, ncx:].reshape(B, *pg.shape_y))


@device_scope("route.dev.relax.crop")
@functools.partial(jax.jit, static_argnames=("cnx", "cny"))
def crop_cut(pg: PlanesGraph, ox, oy, cnx: int, cny: int,
             cc_flat) -> CropCut:
    """The CropCut of the tiles at (ox, oy) and the field ``cc_flat``
    [B, ncells]."""
    ccxf, ccyf = _canvases(pg, cc_flat)
    return CropCut(gm=geom_cropped(pg, ox, oy, cnx, cny),
                   cc_x=cut_tiles(ccxf, ox, oy, cnx, cny + 1),
                   cc_y=cut_tiles(ccyf, ox, oy, cnx + 1, cny))


@device_scope("route.dev.relax.crop")
@functools.partial(jax.jit, static_argnames=("cnx", "cny"))
def crop_state(pg: PlanesGraph, d0_flat, wenter0, ox, oy,
               cnx: int, cny: int):
    """A wave's half of the crop scaffolding: reshape the [B, Ncells]
    seeds into canvases and cut each net's (cnx, cny) tile at its
    origin.  Returns (full canvases (dxf, dyf, wxf, wyf), tiles (dx,
    dy, wx, wy))."""
    dxf, dyf = _canvases(pg, d0_flat)
    wxf, wyf = _canvases(pg, wenter0)
    return ((dxf, dyf, wxf, wyf),
            (cut_tiles(dxf, ox, oy, cnx, cny + 1),
             cut_tiles(dyf, ox, oy, cnx + 1, cny),
             cut_tiles(wxf, ox, oy, cnx, cny + 1),
             cut_tiles(wyf, ox, oy, cnx + 1, cny)))


@device_scope("route.dev.relax.crop")
@jax.jit
def scatter_state(gm_full: PlanesGeom, fulls, tiles, ox, oy):
    """Shared scatter-back: write each net's relaxed tile into its full
    canvases (cells outside the tile keep d0 / SELF-pred / wenter0 —
    they are unreachable in the uncropped program too) and flatten to
    the planes_relax return contract."""
    dxf, dyf, wxf, wyf = fulls
    dx, dy, predx, predy, wx, wy = tiles
    B = dxf.shape[0]

    def put(full, tile):
        return put_tiles(full, tile, ox, oy)

    def flat(a, b):
        return jnp.concatenate([a.reshape(B, -1), b.reshape(B, -1)],
                               axis=1)

    return (flat(put(dxf, dx), put(dyf, dy)),
            flat(put(gm_full.idxx, predx), put(gm_full.idxy, predy)),
            flat(put(wxf, wx), put(wyf, wy)))


def planes_relax_cropped(pg: PlanesGraph, d0_flat, cc_flat, crit_c,
                         wenter0, nsweeps: int, ox, oy,
                         cnx: int, cny: int, plane_dtype: str = "f32",
                         cut: Optional[CropCut] = None):
    """planes_relax on per-net (cnx, cny) CROPPED canvases: net b sweeps
    only the tile starting at grid cell (ox[b], oy[b]) — work per net
    scales with its bounding box, not the device (the reference's
    per-net bb, route.h:70-165, realized as a static crop).

    EXACT under the caller contract: every finite-cc cell of net b (the
    bb mask plus bb-INTERSECTING wires whose spans overhang the box)
    and every seeded cell of d0 lies inside the tile — expand the bb by
    (max wire length - 1) and clamp origins to the grid.  Cells outside
    the tile return their d0 / self-pred / wenter0 unchanged (they are
    unreachable in the full program too: their cc is INF).

    ``cut``: the geometry and the tiles of ``cc_flat`` at (ox, oy),
    where the caller holds them already (a step's waves share their
    origins: _step_core); cut here otherwise.

    Same (dist, pred, wenter, stats) returns as planes_relax."""
    if cut is None:
        cut = crop_cut(pg, ox, oy, cnx, cny, cc_flat)
    gm, cc_x, cc_y = cut.gm, cut.cc_x, cut.cc_y
    fulls, (dx, dy, wx, wy) = crop_state(
        pg, d0_flat, wenter0, ox, oy, cnx, cny)
    if plane_dtype != "f32":
        # same one-time congestion quantization as planes_relax
        dt = plane_jnp_dtype(plane_dtype)
        cc_x = cc_x.astype(dt).astype(jnp.float32)
        cc_y = cc_y.astype(dt).astype(jnp.float32)
    predx = jnp.broadcast_to(gm.idxx, dx.shape)
    predy = jnp.broadcast_to(gm.idxy, dy.shape)

    costs = _sweep_costs(gm, crit_c, cc_x, cc_y)

    def sweep(s):
        return _sweep_once(gm, s, crit_c, cc_x, cc_y, costs)

    tiles, stats = _run_relax(sweep, (dx, dy, predx, predy, wx, wy),
                              nsweeps, plane_dtype)
    if plane_dtype != "f32":
        tiles = _dequantize_plane_state(tiles)
    # write the tiles back into the full canvases (one full-canvas
    # write per relaxation instead of ~15 traversals per sweep)
    return scatter_state(geom_full(pg), fulls, tiles, ox, oy) + (stats,)


# ---------------------------------------------------------------------------
# The fused batch step (device-resident contract of
# search.route_batch_resident, planes search inside, zero slow-class ops)
# ---------------------------------------------------------------------------


def _as_row_mesh(mesh):
    """The window programs' ``mesh`` static carries either a legacy
    (net, node) GSPMD Mesh or a planes_shard.RowMesh (explicit halo
    exchange).  Returns the RowMesh, or None for the GSPMD/absent
    cases — callers branch the relax dispatch on it."""
    if mesh is None:
        return None
    from .planes_shard import RowMesh
    return mesh if isinstance(mesh, RowMesh) else None


def traceback_walk(pred, wenter, noc_p1, pick_cell, done0, Kw: int):
    """The traceback's pointer chase, alone: from each ``pick_cell``
    [B, G] follow ``pred`` [B, ncells] until a cell that is its own
    predecessor, for at most ``Kw`` steps.  Step ``pos`` records, for
    every walk not yet ``done``, the cell it stands on, that cell's rr
    node (``noc_p1`` [ncells + 1], last entry = the node sentinel) and
    its entry weight (``wenter`` [B, ncells]); an ended walk keeps the
    fill (``ncells``, the sentinel, 0.0).  ``done0`` marks the walks
    that never start (invalid or direct picks).

    The loop ends when every walk of the batch has: a step taken with
    ``done`` all true writes the fill over the fill and moves nothing,
    so the outputs equal the full ``Kw``-step walk's.  A walk that
    overruns the budget keeps the loop going to ``Kw`` and ends not
    ``done``.  The records are kept time-major inside the loop, so a
    step writes one contiguous [1, B, G] slab in place instead of
    scattering B * G elements along the minor axis, and are transposed
    once after it.

    Returns (cur [B, G], done [B, G], cells_w, nodes_w, wst — each
    [B, G, Kw] — and the number of steps run, int32)."""
    B, G = pick_cell.shape
    ncells = pred.shape[1]

    def record(buf, pos, row):
        return lax.dynamic_update_slice(buf, row[None], (pos, 0, 0))

    def unfinished(ws):
        pos, _, done = ws[:3]
        return (pos < Kw) & ~done.all()

    def walk_step(ws):
        pos, cur, done, cells_w, nodes_w, wst = ws
        at = jnp.where(done, ncells, cur)
        cells_w = record(cells_w, pos, at)
        nodes_w = record(nodes_w, pos, jnp.take(noc_p1, at))
        here = jnp.clip(cur, 0, ncells - 1)
        w = jnp.take_along_axis(wenter, here, axis=1)
        wst = record(wst, pos, jnp.where(done, 0.0, w).astype(wst.dtype))
        nxt = jnp.take_along_axis(pred, here, axis=1)
        stop = done | (nxt == cur)
        return (pos + 1, jnp.where(stop, cur, nxt), stop, cells_w,
                nodes_w, wst)

    steps, cur, done, cells_w, nodes_w, wst = lax.while_loop(
        unfinished, walk_step,
        (jnp.int32(0), pick_cell, done0,
         jnp.full((Kw, B, G), ncells, jnp.int32),
         jnp.broadcast_to(noc_p1[ncells], (Kw, B, G)),
         jnp.zeros((Kw, B, G), jnp.float32)))
    cells_w, nodes_w, wst = (jnp.transpose(a, (1, 2, 0))
                             for a in (cells_w, nodes_w, wst))
    return cur, done, cells_w, nodes_w, wst, steps


def node_cost_field(congj_p1, node_of_cell):
    """Per-net node costs ``congj_p1`` [B, N + 1] laid over the canvas:
    [B, ncells], cell c of every net reading node ``node_of_cell[c]``.
    The index is ONE vector for all nets: ncells indices, each moving
    the B-net column of its node (the TPU compiler lays the table out
    node-major and fetches contiguous rows), never B * ncells element
    reads out of a per-net table."""
    return jnp.take(congj_p1, node_of_cell, axis=1)


def entry_fields(seed_cells, opin_du, cc_flat, crit_w, valid,
                 ecell, eoidx, edelay):
    """A wave's SOURCE-side fields over the canvas, from the nets' entry
    tables [B, Ko] (every OPIN -> wire edge as its wire cell ``ecell``,
    pad ncells; its OPIN's index ``eoidx`` into ``opin_du`` [B, O]; its
    edge delay ``edelay``):

    d0 [B, ncells]   0.0 on the tree (``seed_cells``), else the cheapest
                     entry cost into the cell, else INF
    entry_flag       the cells an entry beat the seed on
    wk (int32)       the winning entry of each cell (ties -> lowest k,
                     deterministic; Ko where no entry won)
    wenter0 (f32)    the winning entry's delay on flagged cells, else 0.0

    Only the B * Ko entries are ever looked up or written: a canvas is
    read AT the entries' cells and written by a scatter over them.  The
    winner of a cell is known in entry space -- entry k holds its cell
    iff ``wk`` there is k -- and a net's winners have distinct cells, so
    the store of their delays is deterministic.  An invalid or clean
    net's costs are all INF: it gets no flag and no weight, and its
    canvases never improve, so it neither extends the batch's
    convergence loop nor does any discoverable work (its results are
    discarded at the step's final scatter)."""
    B, ncells = seed_cells.shape
    Ko = ecell.shape[1]
    rows = jnp.arange(B)[:, None]
    ks = jnp.arange(Ko, dtype=jnp.int32)[None, :]
    padded = ecell >= ncells
    at_e = jnp.minimum(ecell, ncells - 1)

    def at_entries(field, pad):
        return jnp.where(padded, pad,
                         jnp.take_along_axis(field, at_e, axis=1))

    d_seed = jnp.where(seed_cells, 0.0, INF)
    e_du = jnp.take_along_axis(opin_du, eoidx, axis=1)           # [B, Ko]
    e_cost = jnp.where(
        valid[:, None],
        e_du + crit_w[:, None] * edelay + at_entries(cc_flat, INF), INF)
    d0 = d_seed.at[rows, ecell].min(e_cost, mode="drop")
    entry_flag = d0 < d_seed
    e_won = at_entries(d0, INF) == e_cost
    wk = jnp.full((B, ncells), Ko, jnp.int32).at[rows, ecell].min(
        jnp.where(e_won, ks, Ko), mode="drop")
    holds = (at_entries(wk, Ko) == ks) & at_entries(entry_flag, False)
    wenter0 = jnp.zeros((B, ncells), jnp.float32).at[
        rows, jnp.where(holds, ecell, ncells)].set(edelay, mode="drop")
    return d0, entry_flag, wk, wenter0


def sink_pin_costs(congj_p1, sink_tabs):
    """The node costs of every sink's input pins, [B, S, P]: once a
    step, one element read a PIN (each of a sink's hops through the pin
    shares it)."""
    b_upin = sink_tabs[1]
    B, S, P = b_upin.shape
    return jnp.take_along_axis(
        congj_p1, b_upin.reshape(B, -1), axis=1).reshape(B, S, P)


def sink_pick(dist, pin_congj, crit_w, cw, sink_tabs):
    """A wave's cheapest (wire cell -> IPIN -> SINK) hop of every sink
    slot, from the batch's factored sink tables ``sink_tabs`` =
    (ucell [B, S, C], upin [B, S, P], pcdel and pcrank [B, S, P, C];
    PlanesTerminals) and the relaxed distances ``dist`` [B, ncells]:

    sink_dist [B, S]   min over the sink's hops of
                       dist[cell] + crit_w * delay + cw * pin cost
    ent_cell, ent_ipin, ent_wdel   the winning hop's cell, IPIN node and
                       delay; equal costs -> the lowest rank, i.e. the
                       first in the pin-major enumeration

    A distance is read once per distinct CELL of a sink, [B, S, C]
    element reads, and the hops are formed dense over (pin, cell) and
    reduced in one pass, with no second gather.  This is the DENSE form:
    every slot of the batch, whatever it holds (sink_pick_wave reads
    the live slots only where few are)."""
    b_ucell = sink_tabs[0]
    B, S, C = b_ucell.shape
    dist_p1 = jnp.concatenate([dist, jnp.full((B, 1), INF)], axis=1)
    dist_c = jnp.take_along_axis(
        dist_p1, b_ucell.reshape(B, -1), axis=1).reshape(B, S, C)
    return _cheapest_hops(dist_c, pin_congj, crit_w, cw, sink_tabs)


def _cheapest_hops(dist_c, pin_congj, crit_w, cw, sink_tabs):
    """sink_pick's reduction: the hops of every sink slot formed from
    the distances of its cells ``dist_c`` [B, S, C] and reduced to the
    cheapest, (sink_dist, ent_cell, ent_ipin, ent_wdel) each [B, S]."""
    b_ucell, b_upin, b_pcdel, b_pcrank = sink_tabs
    B, S, P, C = b_pcrank.shape
    cand = jnp.where(
        b_pcrank < RANK_PAD,
        dist_c[:, :, None, :] + crit_w[:, None, None, None] * b_pcdel
        + cw[:, None, None, None] * pin_congj[:, :, :, None], INF)
    slots = jnp.broadcast_to(
        jnp.arange(P * C, dtype=jnp.int32).reshape(P, C), (B, S, P, C))

    def first(a, b):
        # the cheaper hop; of two at one cost, the lower rank.  Slots
        # order what is left, the hop-less pairs (INF, RANK_PAD): a
        # sink without a hop lands on slot 0, whose entries are pads
        (ca, ra, sa), (cb, rb, sb) = a, b
        a_first = (ca < cb) | ((ca == cb)
                               & ((ra < rb) | ((ra == rb) & (sa < sb))))
        return tuple(jnp.where(a_first, x, y) for x, y in zip(a, b))

    sink_dist, _, pc = lax.reduce(
        (cand, b_pcrank, slots),
        (jnp.float32(INF), jnp.int32(RANK_PAD), jnp.int32(P * C)),
        first, (2, 3))                                         # [B, S]
    pc = pc[:, :, None]
    ent_cell = jnp.take_along_axis(b_ucell, pc % C, axis=2)[:, :, 0]
    ent_ipin = jnp.take_along_axis(b_upin, pc // C, axis=2)[:, :, 0]
    ent_wdel = jnp.take_along_axis(b_pcdel.reshape(B, S, P * C), pc,
                                   axis=2)[:, :, 0]
    return sink_dist, ent_cell, ent_ipin, ent_wdel


def live_pick_rungs(B: int, S: int):
    """The live pick's list widths for a batch of B x S sink slots,
    ascending: an eighth, a quarter and a half of the slots, each
    rounded up to whole sublanes of 8, those that are narrower than the
    batch.  A function of the shape alone."""
    n = B * S
    return tuple(sorted({m for m in (-(-n // (8 * d)) * 8 for d in (8, 4, 2))
                         if m < n}))


def sink_pick_live(dist, pin_congj, crit_w, cw, sink_tabs, remaining,
                   M: int):
    """sink_pick over the batch's LIVE sink slots alone (``remaining``
    [B, S]: a real sink of a net being routed that no wave has reached;
    at most ``M`` of them): the live flat slots b * S + s are listed
    densely in slot order, their M rows of the tables fetched (row
    gathers), M x C distances read where the dense form reads
    B x S x C, the hops reduced by sink_pick's own reduction and the
    winners written back to [B, S].  A slot that is not live gets
    sink_dist INF (a wave picks live slots only) and zeros for the
    rest; a live slot what sink_pick gives it, bit for bit."""
    B, S, P, C = sink_tabs[3].shape
    n, ncells = B * S, dist.shape[1]
    # the m-th live slot is past as many slots as have fewer than m + 1
    # live up to and including themselves; n past the live count
    upto = jnp.cumsum(remaining.reshape(n), dtype=jnp.int32)
    slot = (upto[None, :] <= jnp.arange(M, dtype=jnp.int32)[:, None]).sum(
        axis=1, dtype=jnp.int32)                               # [M]
    at = jnp.minimum(slot, n - 1)
    net = at // S
    rows = tuple(t.reshape((n,) + t.shape[2:])[at][:, None]
                 for t in sink_tabs)                           # [M, 1, ...]
    ucell = rows[0][:, 0]                                      # [M, C]
    dist_c = jnp.where(
        ucell < ncells,
        jnp.take(dist.reshape(-1), net[:, None] * ncells
                 + jnp.minimum(ucell, ncells - 1), mode="clip"), INF)
    picked = _cheapest_hops(
        dist_c[:, None], pin_congj.reshape(n, P)[at][:, None],
        crit_w[net], cw[net], rows)                            # [M, 1] each
    return tuple(
        jnp.full((n,), fill, v.dtype).at[slot].set(
            v[:, 0], mode="drop").reshape(B, S)
        for v, fill in zip(picked, (INF, 0, 0, 0.0)))


def sink_pick_wave(dist, pin_congj, crit_w, cw, sink_tabs, remaining,
                   rungs):
    """The wave's sink pick at the width its live slots need: the
    narrowest of ``rungs`` (live_pick_rungs) that holds them all,
    sink_pick_live there, and past the widest the dense sink_pick.  The
    rung is one switch on the live count, which only falls within a
    step.  Returns sink_pick's four and the sink ROWS whose distances
    were read (C elements each): the rung's M, or B x S."""
    B, S = remaining.shape

    def dense(*a):
        return sink_pick(*a[:5]) + (jnp.int32(B * S),)

    def live(M, *a):
        return sink_pick_live(*a, M) + (jnp.int32(M),)

    args = (dist, pin_congj, crit_w, cw, sink_tabs, remaining)
    if not rungs:
        return dense(*args)
    count = remaining.sum(dtype=jnp.int32)
    return lax.switch(
        jnp.sum(count > jnp.array(rungs), dtype=jnp.int32),
        [functools.partial(live, M) for M in rungs] + [dense], *args)


# entries of _step_core's ledger vector (scal's SCAL_S_EXEC.. tail)
STEP_LEDGER_LEN = 8

# walk slots a trip of walk_scatters takes (tools/walk_forms.py times
# the candidates alone; PERF.md section 6, PR 42, has the table)
WALK_CHUNK = 16


def walk_slots_read(steps, Kw: int):
    """(trips, slots): the chunks of ``WALK_CHUNK`` walk slots that hold
    a wave's first ``steps`` (int32) of ``Kw``, and the slots those
    trips read a walk: ``steps`` rounded up to whole chunks, at most
    Kw."""
    chunk = min(WALK_CHUNK, Kw)
    trips = (steps + (chunk - 1)) // chunk
    return trips, jnp.minimum(trips * chunk, Kw)


def walk_scatters(buf, seg, walk_cells, walk_tdel, nodes_w, keep, posn):
    """A wave's two element scatters out of its walk records (each
    [B, G, Kw], slot k what step k of ``traceback_walk`` recorded):

    buf [B, ncells + 1]    min= ``walk_tdel`` at ``walk_cells`` (the
                           tree grows; column ncells is the dump)
    seg [B, G, max_len]    the kept nodes (``keep``) of ``nodes_w`` at
                           ``posn + 2``, in walk order behind the sink
                           and its IPIN

    taken from the slots up to the last one that holds a record of a
    KEPT walk ONLY (``walk_cells`` under ncells: the caller has sent
    every other walk's cells to the dump column, and ``keep`` is false
    wherever the cell is the dump), in trips of ``WALK_CHUNK``: past it
    every record is the fill, which goes to the dump column and is not
    kept.  A walk that overran its budget is not kept, so it costs no
    trip.  ``min`` is exact in any order and a walk's kept targets are
    distinct, so the trips write what one scatter over all Kw slots
    writes (walk_scatters_dense), bit for bit but for the dump column,
    which nobody reads; the last chunk of a Kw that is
    no multiple of the chunk starts at Kw - chunk and writes some slots
    a second time, the same values.  No kept walk, no trip.

    Returns (buf, seg, the slots the trips read a walk: int32)."""
    B, G, Kw = nodes_w.shape
    width, max_len = buf.shape[1], seg.shape[2]
    chunk = min(WALK_CHUNK, Kw)
    # both stores are carried FLAT, under flat indices: the v5e's
    # scatter works on a linear operand, and re-laying a [B, ncells + 1]
    # canvas into one and back again costs more than a trip's updates
    # (PERF.md section 6, PR 42); flat, a wave re-lays it once
    with device_scope("route.dev.tree_grow"):
        last = jnp.max(jnp.where(
            walk_cells < width - 1,
            jnp.arange(1, Kw + 1, dtype=jnp.int32), 0))
        trips, slots = walk_slots_read(last, Kw)
        row0 = (jnp.arange(B, dtype=jnp.int32) * width)[:, None, None]
        buf = buf.reshape(-1)
    with device_scope("route.dev.traceback"):
        seg0 = (jnp.arange(B * G, dtype=jnp.int32)
                * max_len).reshape(B, G, 1)
        seg = seg.reshape(-1)

    def cut(a, k):
        # the clamp spelled out: left to dynamic_slice, the v5e
        # compiler's program read the last chunk's records from
        # different slots (my chip run, PR 42)
        return lax.dynamic_slice_in_dim(
            a, jnp.minimum(k * chunk, Kw - chunk), chunk, axis=2)

    def trip(k, carry):
        buf, seg = carry
        with device_scope("route.dev.traceback"):
            seg = seg.at[jnp.where(cut(keep, k), seg0 + cut(posn, k) + 2,
                                   B * G * max_len).reshape(-1)].set(
                cut(nodes_w, k).reshape(-1), mode="drop")
        with device_scope("route.dev.tree_grow"):
            buf = buf.at[(row0 + cut(walk_cells, k)).reshape(-1)].min(
                cut(walk_tdel, k).reshape(-1))
        return buf, seg

    # the loop itself (its counter, its carry) is booked to the tree
    # grow; a trip's path-row ops name their own scope inside it
    with device_scope("route.dev.tree_grow"):
        buf, seg = lax.fori_loop(0, trips, trip, (buf, seg))
        buf = buf.reshape(B, width)
    with device_scope("route.dev.traceback"):
        return buf, seg.reshape(B, G, max_len), slots


def walk_scatters_dense(buf, seg, walk_cells, walk_tdel, nodes_w, keep,
                        posn):
    """walk_scatters' stores by ONE scatter each over all Kw slots, the
    batch a batch dimension of both (the program until PR 42; the fill
    goes to the dump column / is dropped): the form under a GSPMD mesh,
    whose 'net' axis shards B.  walk_scatters' flat stores hide B from
    the partitioner (two all-gathers and two all-reduces a wave on a
    2 x 2 mesh, none here: the v5e compiler off the chip, PR 42).
    Reads the budget; the reference of tests/walk_refs.py."""
    B, G, Kw = nodes_w.shape
    rows = jnp.arange(B)[:, None]
    with device_scope("route.dev.traceback"):
        seg = seg.at[rows[:, :, None], jnp.arange(G)[None, :, None],
                     jnp.where(keep, posn + 2, seg.shape[2])].set(
            nodes_w, mode="drop")
    with device_scope("route.dev.tree_grow"):
        buf = buf.at[rows, walk_cells.reshape(B, -1)].min(
            walk_tdel.reshape(B, -1))
    return buf, seg, jnp.int32(Kw)


def wave_segments(num_waves: int, group: int, doubling: bool):
    """The wave loop as runs of waves [(first, past the last, pick
    width)], in order.  A wave picks, walks, assembles and grows
    ``width`` sinks a net whatever it has to pick, and wave k of the
    doubling schedule picks at most 2^k: the early waves of a class of
    hundreds of sinks run at the fanout ladder's narrower widths
    (FANOUT_BASE, x FANOUT_STEP, ...), each run its own loop, and only
    the last at the class's.  A wave at a wider pick is the same wave
    (the slots past its picks are not valid), so the runs are the one
    loop's, bit for bit.  A class of at most FANOUT_BASE sinks, and
    every schedule that is not the doubling one, is ONE run at
    ``group``: the loop as it always was."""
    runs, first, width = [], 0, FANOUT_BASE
    while doubling and width < group and first < num_waves:
        # waves 0 .. log2(width) pick at most ``width`` sinks
        last = min(num_waves, width.bit_length())
        if last > first:
            runs.append((first, last, width))
        first, width = max(first, last), width * FANOUT_STEP
    if first < num_waves or not runs:
        runs.append((first, num_waves, group))
    return runs


def _step_core(pg: PlanesGraph, dev: DeviceRRGraph, occ, acc, pres_fac,
               paths, sink_delay, all_reached, bb,
               source_all, sinks_all, crit_all,
               opin_node_all, entry_cell_all, entry_oidx_all,
               entry_delay_all,
               sink_uid_all, uid_ucell, uid_upin, uid_pcdel, uid_pcrank,
               direct_oidx_all, direct_ipin_all, direct_delay_all,
               sel, valid, force, full_bb,
               nsweeps: int, max_len: int, num_waves: int, group: int,
               doubling: bool, mesh,
               crop_tile=None, bb0_all=None, widen_ok=None,
               plane_dtype: str = "f32", local=None):
    """One fused batch step (traceable body shared by the standalone
    per-batch wrapper and the window program): rip up the selected nets,
    re-route each against the occupancy view of everyone-but-itself with
    the planes kernel, commit, scatter back.  A selected net is a no-op
    unless it needs rerouting (an overused node on its tree or an
    unreached sink — route_timing.c should_route_net semantics) or
    `force` is true, so a static batch plan can cover all nets every
    iteration and the device skips the clean ones.

    Everything dense in the sink axis (``paths``, ``sink_delay``,
    ``sinks_all``, ``crit_all``, ``sink_uid_all``, the three
    ``direct_*``) is the table of ONE fanout class, [R_c, S_c, ...]: a
    batch holds nets of one class and S, the width every wave works at,
    is that class's.  ``local`` [R] is each net's row in its class's
    tables; None where the route has one class and a net's row is its
    index.  What is per net and not per sink (``all_reached``, ``bb``,
    the source-side tables, the jitter's net id) stays indexed by the
    net.

    Returns (paths, sink_delay, all_reached, bb, occ, n_active, st):
    ``st`` [STEP_LEDGER_LEN] int32 is the step's ledger — relaxation
    sweeps executed, sweeps that improved a distance, traceback walk
    steps run, walk steps budgeted, waves executed (one relaxation
    each), sink rows whose distances the picks read (sink_pick_wave;
    cells_per_sink elements a row), the B * S a wave of the dense
    pick reads and the walk slots the waves' scatters read
    (walk_scatters; of the budgeted ones) — in the order of scal's
    SCAL_S_EXEC.. tail."""
    N = dev.num_nodes
    R = all_reached.shape[0]
    B = sel.shape[0]
    S = sinks_all.shape[1]
    ncells = pg.ncells
    Kw = max_len - 4            # walk budget: sink+ipin+opin+source slots

    with device_scope("route.dev.ripup"):
        lsel = sel if local is None else local[sel]
        b_paths = paths[lsel]
        b_src = source_all[sel]
        b_sinks = sinks_all[lsel]
        b_bb = bb[sel]
        b_crit = crit_all[lsel]
        b_opin = opin_node_all[sel]                  # [B, O]
        b_ecell = entry_cell_all[sel]                # [B, Ko]
        b_eoidx = entry_oidx_all[sel]
        b_edelay = entry_delay_all[sel]
        b_uid = sink_uid_all[lsel]                   # [B, S]
        sink_tabs = (uid_ucell[b_uid], uid_upin[b_uid],  # [B, S, C], P
                     uid_pcdel[b_uid], uid_pcrank[b_uid])   # [B, S, P, C]
        b_doidx = direct_oidx_all[lsel]              # [B, S] (-1 = none)
        b_dipin = direct_ipin_all[lsel]
        b_ddel = direct_delay_all[lsel]
        gspmd = mesh is not None and _as_row_mesh(mesh) is None
        if gspmd:
            from jax.sharding import NamedSharding, PartitionSpec as P

            def c(x, *spec):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(*spec)))
            b_paths = c(b_paths, "net", None, None)
            b_src = c(b_src, "net")
            b_sinks = c(b_sinks, "net", None)
            b_bb = c(b_bb, "net", None)
            b_crit = c(b_crit, "net", None)
            b_opin = c(b_opin, "net", None)
            b_ecell = c(b_ecell, "net", None)
            b_eoidx = c(b_eoidx, "net", None)
            b_edelay = c(b_edelay, "net", None)
            sink_tabs = tuple(c(t, "net", *(None,) * (t.ndim - 1))
                              for t in sink_tabs)
            b_doidx = c(b_doidx, "net", None)
            b_dipin = c(b_dipin, "net", None)
            b_ddel = c(b_ddel, "net", None)

        # the sink pick compacts across the batch axis, which a mesh
        # shards: there the dense pick alone
        pick_rungs = live_pick_rungs(B, S) if mesh is None else ()
        arangeB = jnp.arange(B)
        O = b_opin.shape[1]
        Ko = b_ecell.shape[1]

        # device-side reroute predicate: skip clean nets unless forced
        over_now = jnp.append(occ > dev.capacity, False)
        dirty = over_now[b_paths].any(axis=(1, 2)) | ~all_reached[sel]
        valid = valid & (dirty | force)

        # --- rip up (identical to the ELL resident program) ---
        nodes_p1 = jnp.zeros(N + 1, dtype=jnp.float32)
        old_usage = usage_from_paths(b_paths, nodes_p1) & valid[:, None]
        occ_rip = occ - jnp.sum(old_usage, axis=0, dtype=jnp.int32)
        occ_view = occ[None, :] - old_usage.astype(jnp.int32)

    with device_scope("route.dev.cost_fields"):
        cong = congestion_cost(dev, occ_view, acc, pres_fac)      # [B, N]
        # deterministic per-(net, node) jitter — same hash as search.py so
        # the two programs negotiate identically
        h = (sel.astype(jnp.int32)[:, None]
             * jnp.int32(2654435761 & 0x7FFFFFFF)
             + jnp.arange(N, dtype=jnp.int32)[None, :] * jnp.int32(40503))
        jitter = 1.0 + JITTER_EPS * (
            (h & 0xFFFF).astype(jnp.float32) / 65536.0)
        inside = ((dev.xhigh[None, :] >= b_bb[:, 0, None])
                  & (dev.xlow[None, :] <= b_bb[:, 1, None])
                  & (dev.yhigh[None, :] >= b_bb[:, 2, None])
                  & (dev.ylow[None, :] <= b_bb[:, 3, None]))
        congj = jnp.where(inside, cong * jitter, INF)             # [B, N]
        congj_p1 = jnp.concatenate(
            [congj, jnp.full((B, 1), INF, jnp.float32)], axis=1)
        cc_flat_base = node_cost_field(congj_p1, pg.node_of_cell)
        if gspmd:
            cc_flat_base = c(cc_flat_base, "net", None)
        opin_congj = jnp.take_along_axis(
            congj_p1, jnp.clip(b_opin, 0, N), axis=1)              # [B, O]
        pin_congj = sink_pin_costs(congj_p1, sink_tabs)           # [B, S, P]

        # initial tree: empty in cell space; SOURCE entries come via opin_du
        seed0 = jnp.zeros((B, ncells), bool)

        # per-net crop origins (static (cnx, cny) tile, route.h:70-165 bb
        # semantics as a crop): anchored on the net's STATIC INITIAL bb
        # (bb0_all — terminal extent + bb_factor), NOT the live bb, so a
        # net whose bb widened device-side (unreached sink -> full_bb)
        # keeps a tile that COVERS ALL ITS TERMINALS and stays routable —
        # its search is tile-clamped until the host re-classifies it into
        # the full-canvas window at the next sync (the dev_wide summary
        # output).  The tile covers every bb0-intersecting wire (margin
        # max_span)
        if crop_tile is not None:
            cnx_t, cny_t = crop_tile
            NXg = pg.shape_x[1]
            NYg = pg.shape_y[2]
            Lm = pg.max_span
            bb_anchor = bb0_all[sel] if bb0_all is not None else b_bb
            crop_ox = jnp.clip(bb_anchor[:, 0] - Lm, 0, NXg - cnx_t
                               ).astype(jnp.int32)
            crop_oy = jnp.clip(bb_anchor[:, 2] - Lm, 0, NYg - cny_t
                               ).astype(jnp.int32)

    if crop_tile is not None:
        # the origins hold for the whole step: its geometry and the
        # tiles of the unscaled congestion field are cut here, once, and
        # not by every wave
        with device_scope("route.dev.relax"):
            crop_base = crop_cut(pg, crop_ox, crop_oy, cnx_t, cny_t,
                                 cc_flat_base)

    def wave_run(wave, state, group):
        (seed_cells, tdel_cells, opin_used, remaining, wpaths, delay,
         reached_all, st) = state
        with device_scope("route.dev.cost_fields"):
            crit_w = jnp.max(jnp.where(remaining, b_crit, 0.0), axis=1)  # [B]
            cw = 1.0 - crit_w
            cc_flat = cw[:, None] * cc_flat_base
            crit_c = crit_w[:, None, None, None]

            # --- seed + SOURCE-side entries ---
            opin_du = jnp.where(opin_used, 0.0, cw[:, None] * opin_congj)
            d0, entry_flag, wk, wenter0 = entry_fields(
                seed_cells, opin_du, cc_flat, crit_w, valid,
                b_ecell, b_eoidx, b_edelay)

        with device_scope("route.dev.relax"):
            if crop_tile is not None:
                dist, pred, wenter, rst = planes_relax_cropped(
                    pg, d0, cc_flat, crit_c, wenter0, nsweeps,
                    crop_ox, crop_oy, cnx_t, cny_t,
                    plane_dtype=plane_dtype, cut=crop_base.scaled(cw))
            elif _as_row_mesh(mesh) is not None:
                from .planes_shard import planes_relax_sharded
                dist, pred, wenter, rst = planes_relax_sharded(
                    pg, d0, cc_flat, crit_c, wenter0, nsweeps,
                    _as_row_mesh(mesh), plane_dtype=plane_dtype)
            else:
                dist, pred, wenter, rst = planes_relax(pg, d0, cc_flat,
                                                       crit_c, wenter0,
                                                       nsweeps, mesh,
                                                       plane_dtype)
            st = st.at[:2].add(rst)

        with device_scope("route.dev.sink_pick"):
            (sink_dist, ent_cell, ent_ipin, ent_wdel,
             sink_rows) = sink_pick_wave(dist, pin_congj, crit_w, cw,
                                         sink_tabs, remaining, pick_rungs)

            # --- dedicated direct candidate (OPIN->IPIN->SINK, bypassing
            # the fabric): competes with the relaxation candidates; the
            # fabric wins exact ties (strict <) for determinism ---
            has_d = b_doidx >= 0
            ddu = jnp.take_along_axis(
                opin_du, jnp.clip(b_doidx, 0, O - 1), axis=1)      # [B, S]
            dip_cong = jnp.take_along_axis(congj_p1, b_dipin, axis=1)
            dcost = jnp.where(has_d,
                              ddu + crit_w[:, None] * b_ddel
                              + cw[:, None] * dip_cong, INF)
            use_direct = dcost < sink_dist
            sink_dist = jnp.minimum(sink_dist, dcost)

            # --- pick up to `group` sinks: most critical, then nearest ---
            score = jnp.where(remaining & jnp.isfinite(sink_dist),
                              sink_dist - b_crit * 1e3, INF)
            order = jnp.argsort(score, axis=1)[:, :group]          # [B, G]
            pick_valid = (jnp.take_along_axis(remaining, order, axis=1)
                          & jnp.isfinite(jnp.take_along_axis(score, order,
                                                             axis=1)))
            if doubling:
                # doubling schedule: wave k routes <= 2^k sinks, so a trunk
                # forms before the bulk fan-out (the all-at-once variant
                # costs ~20% wirelength, measured; this costs ~3%)
                limit = jnp.int32(1) << jnp.minimum(wave, 30)
                pick_valid = pick_valid & (jnp.arange(group)[None, :] < limit)
            G = group
            pick_sink = jnp.where(
                pick_valid, jnp.take_along_axis(b_sinks, order, axis=1), -1)
            pick_ipin = jnp.take_along_axis(ent_ipin, order, axis=1)
            pick_cell = jnp.where(
                pick_valid, jnp.take_along_axis(ent_cell, order, axis=1), 0)
            pick_wdel = jnp.take_along_axis(ent_wdel, order, axis=1)
            # direct-connection picks: no canvas walk, 4-node path
            pick_direct = (jnp.take_along_axis(use_direct, order, axis=1)
                           & pick_valid)
            pick_dipin = jnp.take_along_axis(b_dipin, order, axis=1)
            pick_doidx = jnp.take_along_axis(jnp.clip(b_doidx, 0, O - 1),
                                             order, axis=1)
            pick_ddel = jnp.take_along_axis(b_ddel, order, axis=1)
            pick_ipin = jnp.where(pick_direct, pick_dipin, pick_ipin)
            pick_cell = jnp.where(pick_direct, 0, pick_cell)

        with device_scope("route.dev.traceback"):
            # --- pointer-chase traceback in cell space ---
            ar_b = arangeB[:, None]
            ar_g = jnp.arange(G)[None, :]
            noc_p1 = jnp.append(pg.node_of_cell, N)
            cur, _, cells_w, nodes_w, wst, wsteps = traceback_walk(
                pred, wenter, noc_p1, pick_cell,
                ~pick_valid | pick_direct, Kw)
            # a walk is complete iff it reached a pred==self cell in budget
            nxt_last = jnp.take_along_axis(
                pred, jnp.clip(cur, 0, ncells - 1), axis=1)
            okw = pick_valid & (nxt_last == cur)
            # direct picks skip the walk entirely
            ok = jnp.where(pick_direct, pick_valid, okw)          # [B, G]

            join = jnp.clip(cur, 0, ncells - 1)
            at_entry = (jnp.take_along_axis(entry_flag, join, axis=1) & ok
                        & ~pick_direct)
            tdel_base = jnp.where(
                at_entry, 0.0,
                jnp.take_along_axis(tdel_cells, join, axis=1))     # [B, G]
            wsum = jnp.flip(jnp.cumsum(jnp.flip(wst, 2), axis=2), 2)
            d_new = jnp.where(pick_direct, pick_ddel,
                              tdel_base + wsum[:, :, 0] + pick_wdel)

            # entry suffix: which OPIN fed the winning entry cell
            wk_join = jnp.take_along_axis(wk, join, axis=1)        # [B, G]
            eoidx_p1 = jnp.concatenate(
                [b_eoidx, jnp.zeros((B, 1), jnp.int32)], axis=1)
            oidx_join = jnp.take_along_axis(eoidx_p1,
                                            jnp.minimum(wk_join, Ko), axis=1)
            opin_join = jnp.take_along_axis(b_opin, oidx_join, axis=1)

            # --- assemble path rows:
            # [sink, ipin, nodes..., (opin, source)] ---
            dup = jnp.concatenate(
                [jnp.zeros((B, G, 1), bool),
                 nodes_w[:, :, 1:] == nodes_w[:, :, :-1]], axis=2)
            keep = ~dup & (nodes_w < N) & (ok & ~pick_direct)[:, :, None]
            posn = jnp.cumsum(keep, axis=2) - 1
            seg = jnp.full((B, G, max_len), N, jnp.int32)
            seg = seg.at[:, :, 0].set(jnp.where(ok, pick_sink, N))
            seg = seg.at[:, :, 1].set(jnp.where(ok, pick_ipin, N))
            nkeep = jnp.sum(keep, axis=2)                          # [B, G]
            put_e = at_entry & ok
            seg = seg.at[ar_b, ar_g,
                         jnp.where(put_e, nkeep + 2, max_len)].set(
                opin_join, mode="drop")
            seg = seg.at[ar_b, ar_g,
                         jnp.where(put_e, nkeep + 3, max_len)].set(
                jnp.broadcast_to(b_src[:, None], (B, G)), mode="drop")
            # direct picks: 4-node path [sink, ipin, opin, source]
            pdm = pick_direct & ok
            d_opin = jnp.take_along_axis(b_opin, pick_doidx, axis=1)
            seg = seg.at[ar_b, ar_g,
                         jnp.where(pdm, 2, max_len)].set(d_opin, mode="drop")
            seg = seg.at[ar_b, ar_g,
                         jnp.where(pdm, 3, max_len)].set(
                jnp.broadcast_to(b_src[:, None], (B, G)), mode="drop")

        with device_scope("route.dev.tree_grow"):
            walk_cells = jnp.where((ok & ~pick_direct)[:, :, None],
                                   cells_w, ncells)
            walk_tdel = tdel_base[:, :, None] + wsum

        # --- the walked nodes into the path rows and the walked cells
        # into the tree (cell space, deterministically via min): the
        # slots the kept walks ran, no others; a mesh that shards the
        # batch keeps it a dimension of both scatters ---
        buf, seg, wslots = (walk_scatters_dense if gspmd
                            else walk_scatters)(
            jnp.full((B, ncells + 1), INF, jnp.float32), seg,
            walk_cells, walk_tdel, nodes_w, keep, posn)

        with device_scope("route.dev.traceback"):
            # the ledger's walk half: steps this wave ran, of its
            # budget, the wave itself, the pick's sink rows read of
            # the batch's, and the walk slots the two scatters read
            st = st.at[2:].add(jnp.stack([
                wsteps, jnp.int32(Kw), jnp.int32(1), sink_rows,
                jnp.int32(B * S), wslots]))
            # --- store results at the picked sink slots ---
            old = jnp.take_along_axis(wpaths, order[:, :, None], axis=1)
            wpaths = wpaths.at[ar_b, order].set(
                jnp.where(ok[:, :, None], seg, old))
            old_d = jnp.take_along_axis(delay, order, axis=1)
            delay = delay.at[ar_b, order].set(jnp.where(ok, d_new, old_d))
            old_r = jnp.take_along_axis(reached_all, order, axis=1)
            reached_all = reached_all.at[ar_b, order].set(ok | old_r)
            old_rem = jnp.take_along_axis(remaining, order, axis=1)
            remaining = remaining.at[ar_b, order].set(old_rem & ~ok)

        with device_scope("route.dev.tree_grow"):
            newly = jnp.isfinite(buf[:, :ncells])
            tdel_cells = jnp.where(newly, buf[:, :ncells], tdel_cells)
            seed_cells = seed_cells | newly
            opin_used = opin_used.at[arangeB[:, None],
                                     jnp.where(put_e, oidx_join, O)].set(
                True, mode="drop") | opin_used
            opin_used = opin_used.at[arangeB[:, None],
                                     jnp.where(pdm, pick_doidx, O)].set(
                True, mode="drop") | opin_used
        return (seed_cells, tdel_cells, opin_used, remaining, wpaths,
                delay, reached_all, st)

    def wave_body(group, wave, state):
        # once every (valid) sink is reached the remaining waves are
        # identity passes — skip their relaxations entirely (exact: a
        # wave with no remaining sinks picks nothing and commits
        # nothing, verified against the unconditional body)
        with device_scope("route.dev.sink_pick"):
            pending = state[3].any()
        return lax.cond(pending, lambda s: wave_run(wave, s, group),
                        lambda s: s, state)

    with device_scope("route.dev.cost_fields"):
        state0 = (seed0, jnp.zeros((B, ncells), jnp.float32),
                  jnp.zeros((B, O), bool),
                  (b_sinks >= 0) & valid[:, None],
                  jnp.full((B, S, max_len), N, jnp.int32),
                  jnp.full((B, S), INF, jnp.float32),
                  jnp.zeros((B, S), bool),
                  jnp.zeros((STEP_LEDGER_LEN,), jnp.int32))
    state = state0
    for lo, hi, width in wave_segments(num_waves, group, doubling):
        state = lax.fori_loop(lo, hi, functools.partial(wave_body, width),
                              state)
    (_, _, _, _, p, delay, reached, st) = state

    with device_scope("route.dev.commit"):
        usage = usage_from_paths(p, nodes_p1) & valid[:, None]
        occ_new = occ_rip + jnp.sum(usage, axis=0, dtype=jnp.int32)

        smask = b_sinks >= 0
        ok = (reached | ~smask).all(axis=1)
        # unreached-sink widening retry — gated per net by widen_ok: a net
        # routed under a REDUCED sweep budget (RouterOpts.sweep_budget_div)
        # must not take a full-device bb for what may only be an
        # under-budgeted relaxation; the host promotes it to the full
        # budget first (the unreached summary output) and only a
        # full-budget failure widens
        if widen_ok is None:
            may_widen = jnp.ones((B,), bool)
        else:
            may_widen = widen_ok[sel]
        new_bb = jnp.where((ok | ~may_widen)[:, None], b_bb,
                           full_bb[None, :])

        sel_v = jnp.where(valid, sel, R).astype(jnp.int32)
        lsel_v = sel_v if local is None else jnp.where(
            valid, lsel, paths.shape[0]).astype(jnp.int32)
        paths = paths.at[lsel_v].set(p, mode="drop")
        sink_delay = sink_delay.at[lsel_v].set(delay, mode="drop")
        all_reached = all_reached.at[sel_v].set(ok, mode="drop")
        bb = bb.at[sel_v].set(new_bb, mode="drop")
        return (paths, sink_delay, all_reached, bb, occ_new,
                valid.sum(dtype=jnp.int32), st)


@functools.partial(
    jax.jit,
    static_argnames=("nsweeps", "max_len", "num_waves", "group",
                     "doubling", "mesh", "crop_tile", "plane_dtype"),
    donate_argnames=("occ", "paths", "sink_delay", "all_reached", "bb"))
def route_batch_resident_planes(
        pg: PlanesGraph, dev: DeviceRRGraph, occ, acc, pres_fac,
        paths, sink_delay, all_reached, bb,
        source_all, sinks_all, crit_all,
        opin_node_all, entry_cell_all, entry_oidx_all, entry_delay_all,
        sink_uid_all, uid_ucell, uid_upin, uid_pcdel, uid_pcrank,
        direct_oidx_all, direct_ipin_all, direct_delay_all,
        sel, valid, full_bb,
        nsweeps: int, max_len: int, num_waves: int, group: int,
        doubling: bool = False, mesh=None,
        crop_tile=None, bb0_all=None, plane_dtype: str = "f32"):
    """Standalone one-batch wrapper of _step_core (resident-state
    contract of search.route_batch_resident; the host picked the nets,
    so force=True)."""
    if crop_tile is not None and bb0_all is None:
        # the crop anchors on the STATIC initial bb; anchoring on the
        # live bb would corner-clamp a device-widened net's tile off
        # its own terminals (silently unroutable)
        raise ValueError("crop_tile requires bb0_all (static initial "
                         "bbs) as the crop anchor")
    paths, sink_delay, all_reached, bb, occ, _, st = _step_core(
        pg, dev, occ, acc, pres_fac, paths, sink_delay, all_reached, bb,
        source_all, sinks_all, crit_all,
        opin_node_all, entry_cell_all, entry_oidx_all, entry_delay_all,
        sink_uid_all, uid_ucell, uid_upin, uid_pcdel, uid_pcrank,
        direct_oidx_all, direct_ipin_all, direct_delay_all,
        sel, valid, jnp.bool_(True), full_bb,
        nsweeps, max_len, num_waves, group, doubling, mesh,
        crop_tile, bb0_all, plane_dtype=plane_dtype)
    return (paths, sink_delay, all_reached, bb, occ, st[0])


# the widest short list of overused nodes _mis_colors builds its
# conflict matrix from by dense compares (ids past it: the table and the
# scatter of _mis_colors_full).  Sized on the chip by
# tools/mis_colors_forms.py (PERF.md section 6 PR 46; ms a call, full |
# short at 0 / 32 / 256 nodes over): route_scale_6k's store of 11.5 M
# slots 171 | 0.38 / 0.67 / 2.87, route_hetero's 8.4 M 128 | 0.34 / 0.60 /
# 2.26, the K4N4 cells' 1.5 M 20.4 | 0.22 / 0.27 / 0.73.  A trip of the
# short form's loop is an overused node (7 to 10 us at the large stores),
# not a column, so the widths 64 / 128 / 256 cost the same at the same
# count and the widest candidate caps the list
MIS_SHORT_K = 256


def _mis_rounds(U, rrm, prio, n_colors: int):
    """The greedy colouring over a conflict matrix ``U`` [R, K] (rows in
    ``prio``'s order, any K): colour c goes to the still-uncoloured rows
    that hold the min ``prio`` down every column they stand in; what is
    left after n_colors - 1 rounds shares the last class.  Returns
    colors [R], in the rows' order."""
    R = prio.shape[0]
    color = jnp.full(R, n_colors - 1, jnp.int32)
    uncol = rrm
    for c in range(n_colors - 1):
        Uc = U & uncol[:, None]
        claim = jnp.min(jnp.where(Uc, prio[:, None], R), axis=0)
        conflict = (Uc & (claim[None, :] != prio[:, None])).any(axis=1)
        joins = uncol & ~conflict
        color = jnp.where(joins, c, color)
        uncol = uncol & ~joins
    return color


def _mis_rows(all_reached, fan):
    """(prio, reached) of U's rows: the nets in order, or class after
    class (``fan[1]``: a class's member nets)."""
    if fan is None:
        return jnp.arange(all_reached.shape[0], dtype=jnp.int32), all_reached
    prio = jnp.concatenate(fan[1])
    return prio, all_reached[prio]


def _mis_by_net(rrm, color, prio, fan):
    """Rows class after class -> by net."""
    if fan is None:
        return rrm, color
    R = prio.shape[0]
    return (jnp.zeros(R, bool).at[prio].set(rrm),
            jnp.zeros(R, jnp.int32).at[prio].set(color))


def _mis_colors_full(dev: DeviceRRGraph, occ, paths, all_reached,
                     topk: int, n_colors: int, fan=None, top=None):
    """_mis_colors over the top-K overused nodes, whatever is over
    (``top``: the caller's ``lax.top_k(over, topk)``, where it has one).

    A path slot finds its column of the conflict matrix U by ONE read
    of a node-indexed table ``code [N + 1]``, written once a call from
    what ``top_k`` returns: k < topk where the node is the k-th of the
    top-K overused, topk (the dump column) where it is overused outside
    them, topk + 1 where it is clean or the sentinel N.  The same read
    says whether the slot is overused at all (rrm).  U's columns stand
    in top_k's order; claim is a min down each column and conflict an
    any across them, so no order of the columns moves a colour
    (tests/mis_colors_refs.py keeps the searchsorted form this
    replaced: fifteen gather rounds over the path store).  On the chip
    the read is an element gather of R x S x L slots and U's scatter a
    sort of as many indices: 14.8 ns a slot (PERF.md section 6 PR 46)."""
    N = dev.num_nodes
    over = jnp.maximum(occ - dev.capacity, 0)
    val, ids = lax.top_k(over, topk) if top is None else top
    code = jnp.append(jnp.where(over > 0, topk, topk + 1),
                      topk + 1).astype(jnp.int32)
    code = code.at[jnp.where(val > 0, ids, N + 1)].set(
        jnp.arange(topk, dtype=jnp.int32), mode="drop")

    def rows(col):
        n = col.shape[0]
        return jnp.zeros((n, topk + 1), bool).at[
            jnp.arange(n)[:, None], jnp.minimum(col, topk)].set(
            True)[:, :topk]

    cols = [code[store.reshape(store.shape[0], -1)]
            for store in ((paths,) if fan is None else paths)]
    prio, reached = _mis_rows(all_reached, fan)
    rrm = jnp.concatenate([(col <= topk).any(axis=1)
                           for col in cols]) | ~reached
    U = jnp.concatenate([rows(col) for col in cols]) & rrm[:, None]
    return _mis_by_net(rrm, _mis_rounds(U, rrm, prio, n_colors), prio, fan)


def _mis_colors_short(dev: DeviceRRGraph, occ, paths, all_reached,
                      K: int, n_colors: int, fan=None, top=None):
    """_mis_colors where at most ``K`` nodes are overused (the caller's
    to see): column k of U is a dense compare of the path store with the
    k-th overused node's id, one trip of a loop an overused node, so a
    window that ends with nothing over reads no store at all.  No table,
    no gather, no scatter; U is [R, K].  The columns past the overused
    nodes are all-False here as they are in the full form's U and its
    dump column is empty, so rrm and colors are the full form's.
    ``top``: the caller's ``lax.top_k(over, k)`` of some k >= K."""
    over = jnp.maximum(occ - dev.capacity, 0)
    n_over = (over > 0).sum(dtype=jnp.int32)
    ids = (lax.top_k(over, K) if top is None else top)[1]
    flats = [store.reshape(store.shape[0], -1)
             for store in ((paths,) if fan is None else paths)]
    prio, reached = _mis_rows(all_reached, fan)

    def column(k, Ut):
        hit = jnp.concatenate([(flat == ids[k]).any(axis=1)
                               for flat in flats])
        return lax.dynamic_update_slice(Ut, hit[None, :], (k, 0))

    Ut = lax.fori_loop(0, jnp.minimum(n_over, K), column,
                       jnp.zeros((K, prio.shape[0]), bool))
    U = Ut.T
    rrm = U.any(axis=1) | ~reached
    return _mis_by_net(rrm, _mis_rounds(U, rrm, prio, n_colors), prio, fan)


def mis_short_width(topk: int) -> int:
    """The most overused nodes the short form takes: beyond ``topk``
    the full form drops nodes into its dump column."""
    return min(MIS_SHORT_K, topk)


def _mis_short_due(dev: DeviceRRGraph, occ, topk: int):
    """Whether so few nodes are over capacity that _mis_colors takes its
    short form (a traced bool)."""
    return ((occ > dev.capacity).sum(dtype=jnp.int32)
            <= mis_short_width(topk))


@device_scope("route.dev.mis_colors")
def _mis_colors(dev: DeviceRRGraph, occ, paths, all_reached,
                topk: int, n_colors: int, fan=None):
    """Device-side conflict scheduling: greedy parallel MIS coloring of
    the reroute set over the top-K MOST-OVERUSED nodes (the linear-work
    replacement for the host O(I^2) greedy coloring of round 2 — the
    reference's custom_vertex_coloring,
    partitioning_multi_sink_delta_stepping_route.cxx:3323, re-done as
    bitmap rounds: a net takes color c iff it holds the min net id on
    every contested node among the still-uncolored).  Nets left after
    n_colors-1 rounds share the last class.

    ONE conflict matrix U [net, overused node], built one of two ways by
    what the program sees in ``occ``: with at most
    ``mis_short_width(topk)`` nodes over, by dense compares of the store
    against that short list (_mis_colors_short); else through a
    node-indexed table, a gather of the store and a scatter
    (_mis_colors_full).  Same rrm, same colours, bit for bit
    (tests/test_mis_colors_forms.py).

    With fanout classes (``fan`` = (local, members), ``paths`` a store a
    class) each store is read at its own width and the rows of U
    stand class after class: a claim is a min of net ids down a column
    and a conflict an any across columns, so the order of the rows
    moves no colour either, and the net ids ride in ``prio``.  The
    conflict picture is ONE picture of all classes.

    Returns (rrm [R], colors [R])."""
    # the chip's top_k is a sort of the N nodes: once, for both forms
    top = lax.top_k(jnp.maximum(occ - dev.capacity, 0), topk)
    return lax.cond(
        _mis_short_due(dev, occ, topk),
        lambda: _mis_colors_short(dev, occ, paths, all_reached,
                                  mis_short_width(topk), n_colors, fan, top),
        lambda: _mis_colors_full(dev, occ, paths, all_reached, topk,
                                 n_colors, fan, top))


# what window_colours ran, the last entry of a window's ``scal``
MIS_SKIPPED, MIS_SHORT, MIS_FULL = 0, 1, 2


def window_colours(dev: DeviceRRGraph, occ, paths, all_reached, topk: int,
                   n_colors: int, read, fan=None):
    """A rung's (rrm, colors, form): _mis_colors where the host reads
    the answer (``read``: the rung is its window's last), zeros and no
    pass over the store where it does not (MIS_SKIPPED)."""
    R = all_reached.shape[0]
    form = jnp.where(_mis_short_due(dev, occ, topk), MIS_SHORT,
                     MIS_FULL).astype(jnp.int32)

    def coloured():
        return _mis_colors(dev, occ, paths, all_reached, topk, n_colors,
                           **({} if fan is None else {"fan": fan})) + (form,)

    return lax.cond(
        read, coloured,
        lambda: (jnp.zeros(R, bool), jnp.zeros(R, jnp.int32),
                 jnp.int32(MIS_SKIPPED)))


def repack_plan(sel_plan, seg_plan, live):
    """Re-pack a window plan's LIVE slots densely, in the plan's own
    order, inside each conflict-colour segment (a pure function: the
    window program calls it once an iteration of a rebuild's tail, on
    the device).

    ``sel_plan`` [G, B] net ids, ``seg_plan`` [G, B] ints: 0 on a pad
    slot, else the slot's segment (the host's _plan_groups writes
    1 + the index of the slot's colour class; a group holds one
    segment and a segment is a run of consecutive groups), ``live``
    [G, B] the slots that route this iteration.  The k-th live slot of a
    segment, counted row by row, lands in slot (first group of the
    segment) * B + k: the segment's leading groups fill up, the groups
    past its last live slot come back empty, and no net crosses into
    another segment's groups, so nets _mis_colors separated still
    commit in separate batches.  With every valid slot of a
    host-built plan live the plan comes back as it went in.

    Dense compares and gathers over [G, B, G] and [G, B, B]; no sort,
    no scatter.  Returns (sel [G, B] with 0 on an empty slot,
    valid [G, B] bool)."""
    G, B = sel_plan.shape
    gi = jnp.arange(G, dtype=jnp.int32)
    seg_g = seg_plan.max(axis=1)
    starts = jnp.append(True, seg_g[1:] != seg_g[:-1])
    g0 = lax.cummax(jnp.where(starts, gi, 0))      # my segment's first group
    rowcum = jnp.cumsum(live, axis=1, dtype=jnp.int32)       # inclusive
    cin = jnp.cumsum(rowcum[:, -1])        # live slots up to group g's end
    cex = cin - rowcum[:, -1]
    # slot (g, b) takes the live slot of this rank in plan order
    want = ((cex[g0] + (gi - g0) * B)[:, None]
            + jnp.arange(B, dtype=jnp.int32)[None, :])
    src_g = (cin[None, None, :] <= want[:, :, None]).sum(
        axis=2, dtype=jnp.int32)
    valid = (src_g < G) & (g0[jnp.minimum(src_g, G - 1)] == g0[:, None])
    src_g = jnp.minimum(src_g, G - 1)
    rank = want - cex[src_g]               # among the source group's live
    src_b = jnp.minimum(
        (rowcum[src_g] <= rank[:, :, None]).sum(axis=2, dtype=jnp.int32),
        B - 1)
    return jnp.where(valid, sel_plan[src_g, src_b], 0), valid


# the window program's static argnames — shared between the jit
# decoration below and serve/library.py's AOT export split: a
# jax.export'ed program BAKES its static values in, so the exported
# call receives only the remaining (array) args, filtered by these
# names against the function signature
WINDOW_STATIC_ARGNAMES = ("K_iters", "nsweeps", "max_len", "num_waves",
                          "group", "doubling", "topk", "n_colors",
                          "mesh", "sta_depth", "crit_exp", "max_crit",
                          "use_sdc", "crop_tile", "plane_dtype", "fclass")


class WindowOut(NamedTuple):
    """What route_window_planes returns, in order (a tuple still: a
    positional reader and jit's pytree of leaves see the 23 results)."""
    # the negotiation state, threaded to the next dispatch (the first
    # six and crit_all are donated in)
    occ: Any
    acc: Any
    paths: Any
    sink_delay: Any
    all_reached: Any
    bb: Any
    pres: Any           # the present factor after K_iters escalations
    rrm: Any            # [R] nets to re-route next window
    colors: Any         # [R] their conflict colours (_mis_colors)
    n_over: Any
    over_total: Any
    nroutes: Any        # net routes run / groups executed, this window
    nexec: Any
    crit_all: Any       # loop state (donated); the device STA's when tdev
    dmax_hist: Any      # [K_iters] crit-path delay an iteration (NaN: no STA)
    max_span: Any       # widest live bb half-perimeter of a dirty net
    dev_wide: Any       # [R] nets whose live bb widened to device scale
    live_wh: Any        # [R] uint16 (ceil(w/8) << 8) | ceil(h/8), live bb
    unreached: Any      # [R] nets that missed a sink
    # the MEASURED relaxation-sweep counters summed over every executed
    # group/wave of the window: executed trips of the bounded
    # while_loop, and the subset that improved some distance
    steps_exec: Any
    steps_useful: Any
    # the per-net mask/colour/bb fields and the scalar counters repacked
    # into two small int32 arrays, so the pipelined driver pulls the
    # whole window summary with one async copy each
    status: Any         # [R], unpack_window_status below
    scal: Any           # [SCAL_LEN], SCAL_* below: the five scalars,
    #                     then _step_core's ledger summed like steps_exec
    #                     (walk steps run and the Kw budgeted per
    #                     executed wave, the executed waves, the sink
    #                     pick's rows read and the B * S a dense pick
    #                     reads, the walk slots the two scatters read),
    #                     then the colouring's form (SCAL_MIS_FORM)


@functools.partial(
    jax.jit,
    static_argnames=WINDOW_STATIC_ARGNAMES,
    donate_argnames=("occ", "acc", "paths", "sink_delay", "all_reached",
                     "bb", "crit_all"))
def route_window_planes(
        pg: PlanesGraph, dev: DeviceRRGraph, occ, acc,
        paths, sink_delay, all_reached, bb,
        source_all, sinks_all, crit_all,
        opin_node_all, entry_cell_all, entry_oidx_all, entry_delay_all,
        sink_uid_all, uid_ucell, uid_upin, uid_pcdel, uid_pcrank,
        direct_oidx_all, direct_ipin_all, direct_delay_all,
        sel_plan, valid_plan, full_bb,
        pres0, pres_mult, max_pres, acc_fac, it0, force_until,
        K_iters: int, nsweeps: int, max_len: int, num_waves: int,
        group: int, doubling: bool = True, topk: int = 1024,
        n_colors: int = 5, mesh=None,
        tdev=None, req_seed=None, sta_depth: int = 0,
        crit_exp: float = 1.0, max_crit: float = 0.99,
        use_sdc: bool = False,
        crop_tile=None, bb0_all=None, widen_ok=None,
        plane_dtype: str = "f32", fan=None, fclass: int = 0,
        colours_read=True):
    """A WINDOW of K_iters complete PathFinder iterations as ONE device
    program: per iteration, every batch group in sel_plan [G, B] runs the
    fused rip-up/route/commit step (clean nets no-op via the device-side
    reroute predicate; in the TAIL of a full rebuild the plan's nets
    that need a re-route are first re-packed into dense groups inside
    their conflict-colour segments, repack_plan: valid_plan [G, B] holds
    0 on a pad slot, else the slot's segment, and a bool plan is one
    segment), then the PathFinder present/history update
    (congestion.h:177-193).  One host round trip per window instead of
    per batch — a host round trip costs a sync, and one per batch
    dominated every earlier design; the host fetches only this
    program's summary, decides convergence/widening,
    re-plans the groups from the device-computed coloring, and dispatches
    the next window.

    Pass ``tdev`` (a timing.sta.DeviceTimingGraph) to run the FULL STA
    between iterations ON DEVICE: each iteration ends with the forward/
    backward slack sweeps over the timing DAG and the criticality scatter
    back into crit_all, so timing-driven negotiation gets multi-iteration
    windows too (the reference reruns analyze_timing +
    update_sink_criticalities every router iteration,
    timing/path_delay.c:1994 via parallel_route/router.cxx:28,42 — here
    that loop closes inside one XLA program).  crit_all is loop state
    (donated) and the per-iteration crit-path delays come back in
    dmax_hist [K_iters].

    ``colours_read`` (a TRACED bool: no program of its own) says whether
    the host reads this dispatch's rrm / colors: it does of a window's
    LAST rung alone, and the driver passes False on the others, which
    then skip the conflict colouring (window_colours) and return rrm,
    colors and their bits of ``status`` as zeros, and max_span (taken
    over rrm, read of the last rung alone) as 0.  Every other entry of
    the summary is a rung's own either way.

    Returns a WindowOut."""
    G = sel_plan.shape[0]
    # valid_plan carries each slot's conflict-colour segment (0 = pad);
    # a bool plan is one segment
    seg_plan = valid_plan.astype(jnp.int32)
    local = None if fan is None else fan[0]

    def mine(x):
        # the plan's class's table of a table kept a class
        return x if fan is None else x[fclass]

    def with_mine(x, v):
        return v if fan is None else x[:fclass] + (v,) + x[fclass + 1:]
    sinks_c = mine(sinks_all)
    tabs_c = (mine(sink_uid_all), uid_ucell, uid_upin, uid_pcdel,
              uid_pcrank, mine(direct_oidx_all), mine(direct_ipin_all),
              mine(direct_delay_all))

    def it_body(it, st):
        (occ, acc, paths, sink_delay, all_reached, bb, pres, nroutes,
         nexec, crit_all, dmax_hist, led) = st
        with device_scope("route.dev.ripup"):
            force = (it0 + it) < force_until

            def repacked():
                # _step_core's own predicate, taken once for every net
                # (of the plan's class)
                over_it = jnp.append(occ > dev.capacity, False)
                if fan is None:
                    dirty = over_it[paths].any(axis=(1, 2)) | ~all_reached
                    return repack_plan(sel_plan, seg_plan,
                                       (seg_plan > 0) & dirty[sel_plan])
                over_c = over_it[mine(paths)].any(axis=(1, 2))
                return repack_plan(
                    sel_plan, seg_plan, (seg_plan > 0) & (
                        over_c[local[sel_plan]] | ~all_reached[sel_plan]))

            # the TAIL of a full rebuild (a restart, a finishing pass: a
            # forced window past the first, after its forced iteration):
            # the plan holds every net (the pass: every multi-sink net)
            # and a dozen of a group's 64 still fight, so the groups
            # are re-packed.  Elsewhere they are
            # the host's: re-packed in the negotiation proper too,
            # route_tight (W_min + 1) took ten more iterations to turn
            # legal (PERF.md PR 36)
            sel_it, valid_it = lax.cond(
                (force_until > it0) & (it0 > 0) & ~force, repacked,
                lambda: (sel_plan, seg_plan > 0))

        def g_step(g, st2):
            def run(st3):
                (occ2, paths2, sink_delay2, all_reached2, bb2, nr, ng,
                 led2) = st3
                with device_scope("route.dev.ripup"):
                    sel_g, valid_g = sel_it[g], valid_it[g]
                (paths2, sink_delay2, all_reached2, bb2, occ2,
                 n_act, led_g) = _step_core(
                    pg, dev, occ2, acc, pres,
                    paths2, sink_delay2, all_reached2, bb2,
                    source_all, sinks_c, mine(crit_all),
                    opin_node_all, entry_cell_all, entry_oidx_all,
                    entry_delay_all, *tabs_c,
                    sel_g, valid_g, force, full_bb,
                    nsweeps, max_len, num_waves, group, doubling, mesh,
                    crop_tile, bb0_all, widen_ok, plane_dtype, local)
                with device_scope("route.dev.commit"):
                    return (occ2, paths2, sink_delay2, all_reached2, bb2,
                            nr + n_act, ng + 1, led2 + led_g)

            # skip pow2-padding groups, the groups a re-pack emptied and
            # fully-clean groups outright
            # (the group plan is padded to a power of two to bound the
            # compiled-program count; without the cond every pad group
            # would still pay the full relax).  ng counts the groups that
            # actually executed, so relax-step stats reflect real work
            with device_scope("route.dev.ripup"):
                over_g = jnp.append(st2[0] > dev.capacity, False)
                sel_g = sel_it[g]
                lsel_g = sel_g if fan is None else local[sel_g]
                any_dirty = (valid_it[g]
                             & (over_g[st2[1][lsel_g]].any(axis=(1, 2))
                                | ~st2[3][sel_g] | force)).any()
            return lax.cond(any_dirty, run, lambda s: s, st2)

        (occ, paths_c, sink_delay_c, all_reached, bb, nroutes,
         nexec, led) = lax.fori_loop(
            0, G, g_step,
            (occ, mine(paths), mine(sink_delay), all_reached, bb,
             nroutes, nexec, led))
        paths = with_mine(paths, paths_c)
        sink_delay = with_mine(sink_delay, sink_delay_c)
        with device_scope("route.dev.history"):
            # PathFinder history/present escalation once per iteration
            acc = acc + acc_fac * jnp.maximum(
                occ - dev.capacity, 0).astype(jnp.float32)
            pres = jnp.minimum(max_pres, pres * pres_mult)
        if tdev is not None:
            # device-resident analyze_timing + update_sink_criticalities
            from ..timing.sta import sta_crit
            with device_scope("route.dev.sta"):
                if fan is None:
                    flat = jnp.append(
                        sink_delay.reshape(-1), jnp.float32(0.0))
                else:
                    flat = jnp.concatenate(
                        [sd.reshape(-1) for sd in sink_delay]
                        + [jnp.zeros(1, jnp.float32)])
                crit_flat, dmax, _, _ = sta_crit(
                    tdev, flat, sta_depth, crit_exp, max_crit,
                    req_seed=req_seed, use_sdc=use_sdc)
                if fan is None:
                    crit_all = crit_flat.reshape(sink_delay.shape)
                else:
                    ends = np.cumsum([sd.size for sd in sink_delay])
                    crit_all = tuple(
                        crit_flat[e - sd.size:e].reshape(sd.shape)
                        for e, sd in zip(ends.tolist(), sink_delay))
                dmax_hist = dmax_hist.at[it].set(dmax)
        return (occ, acc, paths, sink_delay, all_reached, bb, pres,
                nroutes, nexec, crit_all, dmax_hist, led)

    (occ, acc, paths, sink_delay, all_reached, bb, pres, nroutes,
     nexec, crit_all, dmax_hist, led) = lax.fori_loop(
        0, K_iters, it_body,
        (occ, acc, paths, sink_delay, all_reached, bb, pres0,
         jnp.int32(0), jnp.int32(0), crit_all,
         jnp.full(K_iters, jnp.nan, jnp.float32),
         jnp.zeros((STEP_LEDGER_LEN,), jnp.int32)))
    s_exec, s_useful = led[0], led[1]

    rrm, colors, mis_form = window_colours(
        dev, occ, paths, all_reached, topk, n_colors, colours_read, fan)
    with device_scope("route.dev.window_summary"):
        over = jnp.maximum(occ - dev.capacity, 0)
        # max bb half-perimeter of a still-dirty net: the host compares it
        # against the current path-slot budget and regrows the (bb-adaptive)
        # paths array when a device-side widening outgrew it
        span = (bb[:, 1] - bb[:, 0]) + (bb[:, 3] - bb[:, 2])
        max_span = jnp.max(jnp.where(rrm, span, 0))
        # nets whose live bb widened to device scale (unreached-sink
        # widening inside _step_core): the host folds this into its `wide`
        # classification so they take the full-canvas window next time
        NXg = pg.shape_x[1]
        NYg = pg.shape_y[2]
        dev_wide = span >= (NXg + NYg)
        # measured per-net live bb sizes, packed ((ceil(w/8) << 8) |
        # ceil(h/8), uint16 — 2 bytes/net of device->host traffic): the
        # host re-partitions the next window's narrow/wide split, crop tile
        # and sweep budget from MEASURED state, the analogue of the
        # reference's measured-cost re-partition between iterations
        # (mpi_route_load_balanced_nonblocking_send_recv_encoded.cxx:909-916)
        wb = jnp.clip(-(-(bb[:, 1] - bb[:, 0] + 1) // 8), 0, 255)
        hb = jnp.clip(-(-(bb[:, 3] - bb[:, 2] + 1) // 8), 0, 255)
        live_wh = ((wb << 8) | hb).astype(jnp.uint16)
        # per-net unreached flag: the host's sweep-budget promotion signal
        # (reduced-budget nets that missed a sink retry at full budget
        # before any widening)
        unreached = ~all_reached
        # packed per-net status word + scalar summary vector: EVERYTHING the
        # host control loop needs from a window, as two tiny int32 arrays a
        # single copy_to_host_async can stream while the host keeps working
        # (the async-pipeline replacement for the 13-array blocking
        # jax.device_get).  Layout (unpack_window_status is the only
        # reader): bit0 rrm, bit1 dev_wide, bit2 unreached, bits3-7 color,
        # bits8-15 live-h bucket, bits16-23 live-w bucket (same 8-tile
        # buckets as live_wh above).
        status = (rrm.astype(jnp.int32)
                  | (dev_wide.astype(jnp.int32) << 1)
                  | (unreached.astype(jnp.int32) << 2)
                  | ((colors.astype(jnp.int32) & 0x1F) << 3)
                  | (hb.astype(jnp.int32) << 8)
                  | (wb.astype(jnp.int32) << 16))
        n_over_s = (over > 0).sum(dtype=jnp.int32)
        over_tot_s = over.sum(dtype=jnp.int32)
        scal = jnp.concatenate([
            jnp.stack([n_over_s, over_tot_s, nroutes, nexec,
                       max_span.astype(jnp.int32)]).astype(jnp.int32),
            led, mis_form[None]])
    return WindowOut(
        occ, acc, paths, sink_delay, all_reached, bb, pres, rrm,
        colors, n_over_s, over_tot_s, nroutes, nexec, crit_all,
        dmax_hist, max_span, dev_wide, live_wh, unreached,
        s_exec, s_useful, status, scal)


# indices into the packed ``scal`` summary vector of route_window_planes
# (one async copy carries every scalar the host control loop consumes)
SCAL_N_OVER = 0
SCAL_OVER_TOTAL = 1
SCAL_NROUTES = 2
SCAL_NEXEC = 3
SCAL_MAX_SPAN = 4
SCAL_S_EXEC = 5       # 5..12: _step_core's ledger vector, in its order
SCAL_S_USEFUL = 6
SCAL_WALK_STEPS = 7
SCAL_WALK_BUDGET = 8
SCAL_WAVES = 9
SCAL_SINK_ROWS = 10
SCAL_SINK_ROWS_DENSE = 11
SCAL_WALK_SLOTS = 12
SCAL_MIS_FORM = 13    # what window_colours ran: MIS_SKIPPED / _SHORT / _FULL
SCAL_LEN = 14


def unpack_window_status(status):
    """Host-side decode of route_window_planes' packed per-net status
    word (see the packing comment at the end of route_window_planes).
    Returns (rrm, colors, dev_wide, unreached, live_w, live_h) as numpy
    arrays — the same values the unpacked outputs 7/8/16/17/18 carry,
    from ONE [R] int32 fetch instead of five."""
    s = np.asarray(status)
    rrm = (s & 1).astype(bool)
    dev_wide = ((s >> 1) & 1).astype(bool)
    unreached = ((s >> 2) & 1).astype(bool)
    colors = ((s >> 3) & 0x1F).astype(np.int32)
    live_h = (((s >> 8) & 0xFF).astype(np.int64)) * 8
    live_w = (((s >> 16) & 0xFF).astype(np.int64)) * 8
    return rrm, colors, dev_wide, unreached, live_w, live_h
