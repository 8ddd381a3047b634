"""Simulated-annealing placer with batched parallel moves on the TPU.

TPU-native re-design of the reference's serial annealer
(vpr/SRC/place/place.c:310 try_place, :246 try_swap hot loop): instead of
one swap at a time, every device step proposes M moves at once, resolves
conflicts so the surviving set is provably independent, evaluates all the
delta costs with one batched gather/reduce, and applies the accepted moves
with disjoint scatters.  M is the placer's analogue of the router's batch
size (and of --num_threads in the reference's parallel routers).

Move semantics match try_swap: pick a random block, pick a random legal
location within ``rlim`` (place.c adaptive range limit), swap with the
occupant if the target is full.  CLBs move in the interior window; IO
blocks move along the perimeter ring (the island model of rr.grid).

Conflict resolution replaces the annealer's inherent serialization: each
move claims its source and destination *sites*; a scatter-argmin keeps the
lowest-numbered claimant of every site and a move survives only if it owns
both its claims (the placement analogue of the router's conflict-coloring
commit groups).  Surviving moves touch pairwise-disjoint blocks and sites,
so their delta costs are exact except for nets shared between two surviving
moves (rare; the cost is recomputed exactly from scratch every step, so
acceptance noise never accumulates — unlike place.c which maintains
incremental cost and has to re-derive it periodically to bound drift,
place.c:654-683).

Cost is VPR's linear-congestion wirelength: for each net,
q(fanout) * (bb_width + bb_height) with the crossing-correction table
(place.c:197 cross_count); bounding boxes by scatter-min/max over net pins
(place.c:293 update_bb semantics, recomputed densely).

The adaptive schedule is a faithful port of place.c semantics:
t *= {0.5, 0.9, 0.95, 0.8} by success rate (update_t place.c:265),
rlim *= (1 - 0.44 + success_rate) (place.c update_rlim), exit when
t < 0.005 * cost / num_nets (exit_crit place.c:270), starting T = 20 x the
std-dev of num_blocks random-move deltas (starting_t place.c:506).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..netlist.packed import PackedNetlist
from ..obs import get_metrics, span
from ..rr.grid import DeviceGrid

# VPR's expected-crossing-count correction for the linear-congestion bb cost
# (place.c cross_count table, nets of 1..50 pins; beyond 50 extrapolated)
_CROSS_COUNT = [
    1.0, 1.0, 1.0, 1.0828, 1.1536, 1.2206, 1.2823, 1.3385, 1.3991, 1.4493,
    1.4974, 1.5455, 1.5937, 1.6418, 1.6899, 1.7304, 1.7709, 1.8114, 1.8519,
    1.8924, 1.9288, 1.9652, 2.0015, 2.0379, 2.0743, 2.1061, 2.1379, 2.1698,
    2.2016, 2.2334, 2.2646, 2.2958, 2.3271, 2.3583, 2.3895, 2.4187, 2.4479,
    2.4772, 2.5064, 2.5356, 2.5610, 2.5864, 2.6117, 2.6371, 2.6625, 2.6887,
    2.7148, 2.7410, 2.7671, 2.7933,
]


def crossing_factor(num_pins: np.ndarray) -> np.ndarray:
    """q per net of num_pins terminals: table entry num_pins-1 for 1..50
    pins, linear extrapolation beyond (place.c get_crossing_count
    semantics)."""
    n = np.asarray(num_pins)
    idx = np.clip(n - 1, 0, 49)
    q = np.where(n <= 50, np.array(_CROSS_COUNT)[idx],
                 2.7933 + 0.02616 * (n - 50))
    return q.astype(np.float32)


@struct.dataclass
class PlaceProblem:
    """Device-resident static placement data (pytree)."""
    # per-net pin ELL: blocks of each costed net, padded with -1.
    # slot 0 is the net driver; slots >= 1 are sink blocks (deduped)
    net_blk: jnp.ndarray       # int32 [NN, P]
    net_valid: jnp.ndarray     # bool  [NN, P]
    net_q: jnp.ndarray         # f32   [NN] crossing factor
    # per-block costed-net ELL (nets this block pins into), -1 padded
    blk_net: jnp.ndarray       # int32 [NB, F]
    # block/site model
    is_io: jnp.ndarray         # bool [NB]
    ring_xy: jnp.ndarray       # int32 [NRING, 2] perimeter ring tile coords
    # heterogeneous interior types (column-typed grids, SetupGrid.c):
    # moves propose a column from the block's OWN type's column list so a
    # RAM block can only land in RAM columns (io blocks: row 0, unused)
    type_id: jnp.ndarray       # int32 [NB] interior type index
    col_list: jnp.ndarray      # int32 [T, Cmax] interior columns per type
    ncols: jnp.ndarray         # int32 [T]
    type_h: jnp.ndarray        # int32 [T] rows a block occupies: its
    #                            anchors are the rows 1 + k * type_h
    col_idx_of_x: jnp.ndarray  # int32 [T, nx+2] nearest own-column index
    # timing model: delta-delay matrices (delay_lookup) padded to one
    # [4, nx+2, ny+2] stack ordered (clb_clb, io_clb, clb_io, io_io)
    delta: jnp.ndarray         # f32 [4, nx+2, ny+2]
    # placement macros (carry chains, place_macro.c): members are frozen
    # out of single-block moves and moved rigidly by macro_step
    movable: jnp.ndarray       # int32 [NBm] blocks eligible for singles
    frozen: jnp.ndarray        # bool [NB] macro members
    # static geometry (python ints; hashable side data)
    nx: int = struct.field(pytree_node=False)
    ny: int = struct.field(pytree_node=False)
    io_cap: int = struct.field(pytree_node=False)

    @property
    def num_blocks(self) -> int:
        return self.blk_net.shape[0]

    @property
    def num_sites(self) -> int:
        return self.nx * self.ny + self.ring_xy.shape[0] * self.io_cap


@dataclass
class PlacerOpts:
    """Annealing knobs (t_annealing_sched / t_placer_opts,
    vpr/SRC/base/vpr_types.h; defaults per SetupVPR.c / place.c)."""
    moves_per_step: int = 256      # M: concurrent proposed moves
    inner_num: float = 1.0         # moves/temp = inner_num * NB^(4/3)
    exit_t_frac: float = 0.005     # exit when t < frac * cost / num_nets
    max_temps: int = 500
    seed: int = 0
    # timing-driven knobs (PATH_TIMING_DRIVEN_PLACE, place.c comp_td_costs)
    timing_tradeoff: float = 0.5   # 0 = pure wirelength
    td_place_exp: float = 8.0      # criticality exponent (td_place_exp_last)
    recompute_crit_temps: int = 1  # STA recompute cadence (temperatures)


@dataclass
class PlaceStats:
    temps: List[Tuple[float, float, float, float]] = field(
        default_factory=list)   # (t, bb_cost, success_rate, rlim)
    initial_cost: float = 0.0
    final_cost: float = 0.0
    final_td_cost: float = 0.0
    est_crit_path: float = float("nan")  # lookup-delay STA estimate
    total_moves: int = 0


def build_place_problem(pnl: PackedNetlist, grid: DeviceGrid,
                        lookup=None, macros=None) -> PlaceProblem:
    """Extract the ELL tables the device step needs.  ``lookup`` is an
    optional place.delay_lookup.DelayLookup for timing-driven placement
    (zeros otherwise -> td cost identically 0).  ``macros``: block-id
    chains (place/macros.py) whose members are frozen out of
    single-block moves."""
    NB = pnl.num_blocks
    costed = [i for i, n in enumerate(pnl.nets)
              if not n.is_global and n.sinks]
    NN = max(1, len(costed))

    # per-net block lists (driver + sinks; a block pinned twice counts once)
    net_blocks = []
    for ni in costed:
        n = pnl.nets[ni]
        blks = [n.driver.block] + [p.block for p in n.sinks]
        seen, uniq = set(), []
        for b in blks:
            if b not in seen:
                seen.add(b); uniq.append(b)
        net_blocks.append(uniq)
    P = max(1, max((len(b) for b in net_blocks), default=1))
    net_blk = np.full((NN, P), -1, dtype=np.int32)
    for i, blks in enumerate(net_blocks):
        net_blk[i, :len(blks)] = blks
    net_valid = net_blk >= 0
    npins = np.array([len(b) for b in net_blocks] + [1] * (NN - len(costed)),
                     dtype=np.int32)[:NN]
    net_q = crossing_factor(npins)

    # per-block costed-net lists
    blk_nets = [[] for _ in range(NB)]
    for i, blks in enumerate(net_blocks):
        for b in blks:
            blk_nets[b].append(i)
    F = max(1, max((len(x) for x in blk_nets), default=1))
    blk_net = np.full((NB, F), -1, dtype=np.int32)
    for b, nets in enumerate(blk_nets):
        blk_net[b, :len(nets)] = nets

    is_io = np.array([pnl.block_type(i).is_io for i in range(NB)], dtype=bool)
    ring = np.array(grid.io_sites(), dtype=np.int32)

    # interior type tables (heterogeneous columns)
    itypes = ["clb"] + sorted({t for t in grid.col_types.values()})
    tid_of = {t: i for i, t in enumerate(itypes)}
    cols_by_t = {t: [x for x in range(1, grid.nx + 1)
                     if grid.interior_type_name(x) == t] for t in itypes}
    type_id = np.zeros(NB, dtype=np.int32)
    for i in range(NB):
        if not is_io[i]:
            t = pnl.blocks[i].type_name
            if t not in tid_of or not cols_by_t[t]:
                raise ValueError(f"block type '{t}' has no columns")
            type_id[i] = tid_of[t]
    Cmax = max(1, max(len(c) for c in cols_by_t.values()))
    col_list = np.zeros((len(itypes), Cmax), dtype=np.int32)
    ncols = np.zeros(len(itypes), dtype=np.int32)
    type_h = np.array([grid.height_of(t) for t in itypes], dtype=np.int32)
    col_idx_of_x = np.zeros((len(itypes), grid.nx + 2), dtype=np.int32)
    for t, cols in cols_by_t.items():
        ti = tid_of[t]
        cols = cols or [1]
        col_list[ti, :len(cols)] = cols
        col_list[ti, len(cols):] = cols[-1]
        ncols[ti] = len(cols)
        ca = np.array(cols)
        for x in range(grid.nx + 2):
            col_idx_of_x[ti, x] = int(np.abs(ca - x).argmin())

    # delta-delay stack [4, nx+2, ny+2]: (clb_clb, io_clb, clb_io, io_io);
    # the SAME array the host criticality path indexes (DelayLookup.stack)
    H, W = grid.nx + 2, grid.ny + 2
    if lookup is not None:
        delta = np.asarray(lookup.stack, dtype=np.float32)
        assert delta.shape == (4, H, W), (delta.shape, (4, H, W))
    else:
        delta = np.zeros((4, H, W), dtype=np.float32)

    frozen = np.zeros(NB, dtype=bool)
    for m in (macros or []):
        frozen[list(m)] = True
    movable = np.where(~frozen)[0].astype(np.int32)
    if len(movable) == 0:
        movable = np.zeros(1, dtype=np.int32)
    return PlaceProblem(
        net_blk=jnp.asarray(net_blk), net_valid=jnp.asarray(net_valid),
        net_q=jnp.asarray(net_q), blk_net=jnp.asarray(blk_net),
        is_io=jnp.asarray(is_io), ring_xy=jnp.asarray(ring),
        type_id=jnp.asarray(type_id), col_list=jnp.asarray(col_list),
        ncols=jnp.asarray(ncols), type_h=jnp.asarray(type_h),
        col_idx_of_x=jnp.asarray(col_idx_of_x),
        delta=jnp.asarray(delta),
        movable=jnp.asarray(movable), frozen=jnp.asarray(frozen),
        nx=grid.nx, ny=grid.ny, io_cap=grid.io_capacity,
    )


# ---------------------------------------------------------------- site maps

def _site_of(pp: PlaceProblem, pos: jnp.ndarray, ring_idx: jnp.ndarray
             ) -> jnp.ndarray:
    """Unified site id per block: CLB sites [0, nx*ny), then IO ring sites.
    ring_idx [NB] is the block's perimeter-ring tile index (-1 for CLBs)."""
    clb = (pos[:, 1] - 1) * pp.nx + (pos[:, 0] - 1)
    io = pp.nx * pp.ny + ring_idx * pp.io_cap + pos[:, 2]
    return jnp.where(pp.is_io, io, clb).astype(jnp.int32)


def _ring_index_host(grid: DeviceGrid) -> dict:
    return {xy: i for i, xy in enumerate(grid.io_sites())}


# ---------------------------------------------------------------- cost

def _conn_delay(pp: PlaceProblem, sx, sy, s_io, tx, ty, t_io):
    """Lookup delay source -> sink from the delta stack (broadcasting)."""
    sel = jnp.where(s_io & t_io, 3,
                    jnp.where(s_io, 1, jnp.where(t_io, 2, 0)))
    dx = jnp.clip(jnp.abs(tx - sx), 0, pp.nx + 1)
    dy = jnp.clip(jnp.abs(ty - sy), 0, pp.ny + 1)
    return pp.delta[sel, dx, dy]


def net_td_cost(pp: PlaceProblem, pos: jnp.ndarray, crit: jnp.ndarray):
    """Timing cost  sum_conn crit * delay(driver -> sink)  over all costed
    connections (comp_td_costs place.c semantics; slot 0 = driver)."""
    blk = jnp.clip(pp.net_blk, 0)
    x, y = pos[blk, 0], pos[blk, 1]
    iof = pp.is_io[blk]
    d = _conn_delay(pp, x[:, :1], y[:, :1], iof[:, :1], x, y, iof)
    P = pp.net_blk.shape[1]
    is_sink = (jnp.arange(P)[None, :] > 0) & pp.net_valid
    return jnp.where(is_sink, crit * d, 0.0).sum()


def net_bb_cost(pp: PlaceProblem, pos: jnp.ndarray):
    """Dense bb cost of all costed nets: (cost_total, bb [NN, 4])."""
    blk = jnp.clip(pp.net_blk, 0)
    x = jnp.where(pp.net_valid, pos[blk, 0], jnp.int32(10 ** 6))
    y = jnp.where(pp.net_valid, pos[blk, 1], jnp.int32(10 ** 6))
    xmin = x.min(axis=1)
    ymin = y.min(axis=1)
    x = jnp.where(pp.net_valid, pos[blk, 0], jnp.int32(-(10 ** 6)))
    y = jnp.where(pp.net_valid, pos[blk, 1], jnp.int32(-(10 ** 6)))
    xmax = x.max(axis=1)
    ymax = y.max(axis=1)
    cost = pp.net_q * ((xmax - xmin + 1) + (ymax - ymin + 1)).astype(
        jnp.float32)
    return cost.sum(), jnp.stack([xmin, xmax, ymin, ymax], axis=1)


# ---------------------------------------------------------------- one step

def _propose(pp: PlaceProblem, pos, ring_idx, key, rlim, M: int):
    """Propose M moves: (block [M], new_pos [M,3], new_ring [M])."""
    NB = pp.num_blocks
    NRING = pp.ring_xy.shape[0]
    k1, k2, k2b, k3, k4 = jax.random.split(key, 5)
    # draw from the movable set only (macro members move via macro_step)
    b = pp.movable[jax.random.randint(k1, (M,), 0, pp.movable.shape[0])]
    bio = pp.is_io[b]
    rl = jnp.maximum(1, rlim.astype(jnp.int32))

    # interior target: uniform window around the current position, but the
    # column is drawn from the block's own type's column list (type
    # legality by construction; rlim maps into column-index space so
    # sparse-column types keep a comparable move radius)
    tid = pp.type_id[b]
    nc = pp.ncols[tid]
    rl_col = jnp.maximum(1, (rl * nc) // jnp.int32(pp.nx))
    u = jax.random.uniform(k2, (M,), minval=-1.0, maxval=1.0)
    ci0 = pp.col_idx_of_x[tid, pos[b, 0]]
    ci = jnp.clip(ci0 + jnp.round(u * rl_col.astype(jnp.float32))
                  .astype(jnp.int32), 0, nc - 1)
    cx = pp.col_list[tid, ci]
    dy = jax.random.randint(k2b, (M,), -rl, rl + 1)
    # the row: dy tiles up or down counted in whole blocks of the type's
    # height, onto an anchor row 1 + k * h (height 1: pos + dy, clipped)
    h = pp.type_h[tid]
    k = (pos[b, 1] - 1) // h + jnp.sign(dy) * ((jnp.abs(dy) + h - 1) // h)
    cy = 1 + jnp.clip(k, 0, pp.ny // h - 1) * h

    # IO target: shift along the perimeter ring (ring distance ~ 2x
    # Manhattan distance for the same rlim), random subtile
    dr = jax.random.randint(k3, (M,), -2 * rl, 2 * rl + 1)
    nring = (ring_idx[b] + dr) % NRING
    nz = jax.random.randint(k4, (M,), 0, pp.io_cap)

    nxny = jnp.where(bio[:, None],
                     pp.ring_xy[jnp.clip(nring, 0)],
                     jnp.stack([cx, cy], axis=1))
    npos = jnp.concatenate(
        [nxny, jnp.where(bio, nz, 0)[:, None]], axis=1).astype(jnp.int32)
    nring = jnp.where(bio, nring, -1)
    return b, npos, nring


@functools.partial(jax.jit, static_argnames=("M", "timing"))
def sa_step(pp: PlaceProblem, pos, ring_idx, occ, crit, inv_bb, inv_td,
            tradeoff, key, t, rlim, M: int, timing: bool = False):
    """One batched SA step: M proposals -> conflict-free subset -> delta
    evaluation -> Metropolis on the normalized combined cost
    (1-tt)*dbb*inv_bb + tt*dtd*inv_td (place.c delta normalization) ->
    apply.  ``timing`` statically gates the per-connection delay gathers
    so pure-wirelength placement doesn't pay for them.  Returns (pos,
    ring_idx, occ, n_acc, n_valid, delta_sum, delta_sq)."""
    NB = pp.num_blocks
    NS = pp.num_sites
    kp, ka = jax.random.split(key)
    b, npos, nring = _propose(pp, pos, ring_idx, kp, rlim, M)

    site_all = _site_of(pp, pos, ring_idx)            # [NB]
    src = site_all[b]                                  # [M]
    clb_site = (npos[:, 1] - 1) * pp.nx + (npos[:, 0] - 1)
    io_site = pp.nx * pp.ny + nring * pp.io_cap + npos[:, 2]
    dst = jnp.where(pp.is_io[b], io_site, clb_site).astype(jnp.int32)

    occ_d = occ[dst]                                   # occupant block or -1
    self_move = dst == src
    # claims: lowest move index wins each site
    claim = jnp.full(NS, M, jnp.int32)
    claim = claim.at[src].min(jnp.arange(M, dtype=jnp.int32))
    claim = claim.at[dst].min(jnp.arange(M, dtype=jnp.int32))
    own = ((claim[src] == jnp.arange(M)) & (claim[dst] == jnp.arange(M))
           & ~self_move
           # a single-block swap must not displace a macro member
           & ~(pp.frozen[jnp.clip(occ_d, 0)] & (occ_d >= 0)))

    # ---- delta cost of each move (exact under `own` independence) ----
    o = occ_d                                          # [M] may be -1
    bnets = pp.blk_net[b]                              # [M, F]
    onets = jnp.where(o[:, None] >= 0, pp.blk_net[jnp.clip(o, 0)], -1)
    # drop duplicates: a net in o's list that is also in b's list
    dup = (onets[:, :, None] == bnets[:, None, :]).any(axis=2)
    onets = jnp.where(dup, -1, onets)
    nets = jnp.concatenate([bnets, onets], axis=1)     # [M, 2F]
    nvalid = nets >= 0
    netsc = jnp.clip(nets, 0)

    pblk = pp.net_blk[netsc]                           # [M, 2F, P]
    pvalid = pp.net_valid[netsc] & nvalid[:, :, None]
    # pin coords with the two blocks transposed
    px = pos[jnp.clip(pblk, 0), 0]
    py = pos[jnp.clip(pblk, 0), 1]
    is_b = pblk == b[:, None, None]
    is_o = (pblk == o[:, None, None]) & (o[:, None, None] >= 0)
    px = jnp.where(is_b, npos[:, None, None, 0],
                   jnp.where(is_o, pos[b, 0][:, None, None], px))
    py = jnp.where(is_b, npos[:, None, None, 1],
                   jnp.where(is_o, pos[b, 1][:, None, None], py))
    big = jnp.int32(10 ** 6)
    nxmin = jnp.where(pvalid, px, big).min(axis=2)
    nxmax = jnp.where(pvalid, px, -big).max(axis=2)
    nymin = jnp.where(pvalid, py, big).min(axis=2)
    nymax = jnp.where(pvalid, py, -big).max(axis=2)
    q = pp.net_q[netsc]
    new_c = q * ((nxmax - nxmin + 1) + (nymax - nymin + 1)).astype(
        jnp.float32)
    # old cost of the same nets from current positions
    opx = pos[jnp.clip(pblk, 0), 0]
    opy = pos[jnp.clip(pblk, 0), 1]
    oxmin = jnp.where(pvalid, opx, big).min(axis=2)
    oxmax = jnp.where(pvalid, opx, -big).max(axis=2)
    oymin = jnp.where(pvalid, opy, big).min(axis=2)
    oymax = jnp.where(pvalid, opy, -big).max(axis=2)
    old_c = q * ((oxmax - oxmin + 1) + (oymax - oymin + 1)).astype(
        jnp.float32)
    delta_bb = jnp.where(nvalid, new_c - old_c, 0.0).sum(axis=1)   # [M]

    # ---- timing delta: crit * lookup-delay per (driver -> sink) conn ----
    if timing:
        iofg = pp.is_io[jnp.clip(pblk, 0)]             # [M, 2F, P]
        critg = crit[netsc]                            # [M, 2F, P]
        P = pp.net_blk.shape[1]
        is_sink = (jnp.arange(P)[None, None, :] > 0) & pvalid
        d_new = _conn_delay(pp, px[:, :, :1], py[:, :, :1],
                            iofg[:, :, :1], px, py, iofg)
        d_old = _conn_delay(pp, opx[:, :, :1], opy[:, :, :1],
                            iofg[:, :, :1], opx, opy, iofg)
        delta_td = jnp.where(is_sink, critg * (d_new - d_old),
                             0.0).sum(axis=(1, 2))                 # [M]
        delta = ((1.0 - tradeoff) * delta_bb * inv_bb
                 + tradeoff * delta_td * inv_td)
    else:
        delta = delta_bb * inv_bb

    # ---- Metropolis ----
    u = jax.random.uniform(ka, (M,))
    accept = own & ((delta <= 0)
                    | (u < jnp.exp(-delta / jnp.maximum(t, 1e-30))))

    # ---- apply (accepted moves touch disjoint blocks & sites) ----
    bb = jnp.where(accept, b, NB)          # scatter-drop slot NB
    oo = jnp.where(accept & (o >= 0), o, NB)
    pos2 = jnp.concatenate([pos, jnp.zeros((1, 3), pos.dtype)], axis=0)
    pos2 = pos2.at[bb].set(npos)
    pos2 = pos2.at[oo].set(pos[b])         # occupant takes b's old site
    ring2 = jnp.concatenate([ring_idx, jnp.zeros((1,), ring_idx.dtype)])
    ring2 = ring2.at[bb].set(nring)
    ring2 = ring2.at[oo].set(ring_idx[b])
    occ2 = jnp.concatenate([occ, jnp.zeros((1,), occ.dtype)])
    ssrc = jnp.where(accept, src, NS)
    sdst = jnp.where(accept, dst, NS)
    occ2 = occ2.at[ssrc].set(o)            # -1 if target was empty
    occ2 = occ2.at[sdst].set(b)

    pos2, ring2, occ2 = pos2[:NB], ring2[:NB], occ2[:NS]
    dvalid = jnp.where(own, delta, 0.0)
    return (pos2, ring2, occ2, accept.sum(), own.sum(),
            dvalid.sum(), (dvalid * dvalid).sum())


@functools.partial(jax.jit, static_argnames=("M", "steps", "timing"))
def sa_temperature(pp: PlaceProblem, pos, ring_idx, occ, crit, inv_bb,
                   inv_td, tradeoff, key, t, rlim, M: int, steps: int,
                   timing: bool = False):
    """All steps of one temperature as a lax.scan (single dispatch)."""
    def body(carry, k):
        pos, ring_idx, occ = carry
        pos, ring_idx, occ, na, nv, _, _ = sa_step(
            pp, pos, ring_idx, occ, crit, inv_bb, inv_td, tradeoff,
            k, t, rlim, M, timing)
        return (pos, ring_idx, occ), (na, nv)
    keys = jax.random.split(key, steps)
    (pos, ring_idx, occ), (na, nv) = jax.lax.scan(
        body, (pos, ring_idx, occ), keys)
    bb_cost, _ = net_bb_cost(pp, pos)
    td_cost = net_td_cost(pp, pos, crit) if timing else jnp.float32(0.0)
    return pos, ring_idx, occ, na.sum(), nv.sum(), bb_cost, td_cost


@functools.partial(jax.jit,
                   static_argnames=("M", "steps", "n_temps", "timing"))
def sa_segment(pp: PlaceProblem, pos, ring_idx, occ, crit, tradeoff,
               key, t, rlim, exit_t, M: int, steps: int, n_temps: int,
               timing: bool = False):
    """A SEGMENT of n_temps whole temperatures as ONE device program:
    per temperature, all moves (inner scan), then the adaptive
    temperature/rlim update (update_t place.c:265) computed ON DEVICE
    from the segment's own success rate.  The host syncs once per
    segment instead of once per temperature — every device<->host round
    trip costs a sync, and one per temperature made the batched design
    sync-bound (not re-measured on the current chip).  Once t has fallen below exit_t the remaining
    temperatures no-op (t frozen at 0 accepts only improvements, and
    srat-based updates are skipped), so a segment can overshoot the exit
    criterion harmlessly.

    Returns (pos, ring_idx, occ, t, rlim, na [n_temps], nv [n_temps],
    bb [n_temps], td [n_temps])."""
    rmax = jnp.float32(max(pp.nx, pp.ny))

    def temp_body(carry, k):
        pos, ring_idx, occ, t, rlim, done, bb_cost = carry
        # bb_cost rides the carry: the exit cost of temperature k IS the
        # entry cost of k+1, so each temperature pays ONE full bb
        # reduction, not two
        td_cost = (net_td_cost(pp, pos, crit) if timing
                   else jnp.float32(1.0))
        inv_bb = 1.0 / jnp.maximum(bb_cost, 1e-30)
        inv_td = 1.0 / jnp.maximum(td_cost, 1e-30)
        t_eff = jnp.where(done, 0.0, t)

        def step(c2, kk):
            pos, ring_idx, occ = c2
            pos, ring_idx, occ, na, nv, _, _ = sa_step(
                pp, pos, ring_idx, occ, crit, inv_bb, inv_td, tradeoff,
                kk, t_eff, rlim, M, timing)
            return (pos, ring_idx, occ), (na, nv)

        keys = jax.random.split(k, steps)
        (pos, ring_idx, occ), (nas, nvs) = jax.lax.scan(
            step, (pos, ring_idx, occ), keys)
        na = nas.sum()
        nv = nvs.sum()
        srat = na.astype(jnp.float32) / jnp.maximum(1, nv)
        # update_t (place.c:265) on device
        fac = jnp.where(srat > 0.96, 0.5,
                        jnp.where(srat > 0.8, 0.9,
                                  jnp.where((srat > 0.15) | (rlim > 1.0),
                                            0.95, 0.8)))
        t2 = jnp.where(done, t, t * fac)
        rlim2 = jnp.where(done, rlim, jnp.clip(
            rlim * (1.0 - 0.44 + srat), 1.0, rmax))
        done2 = done | (t2 < exit_t)
        bb2, _ = net_bb_cost(pp, pos)
        return ((pos, ring_idx, occ, t2, rlim2, done2, bb2),
                (na, nv, bb2, jnp.where(done, 0.0, 1.0), t, rlim))

    bb0, _ = net_bb_cost(pp, pos)
    keys = jax.random.split(key, n_temps)
    (pos, ring_idx, occ, t, rlim, done, _), (na, nv, bb, live, ts, rls) = \
        jax.lax.scan(temp_body,
                     (pos, ring_idx, occ, t, rlim, jnp.bool_(False), bb0),
                     keys)
    return pos, ring_idx, occ, t, rlim, na, nv, bb, live, ts, rls


def _macro_delta_bb(pp: PlaceProblem, pos, blocks, occs, newpos, memv):
    """bb-cost delta of Mm RIGID macro moves evaluated jointly: all of a
    proposal's members sit at their NEW positions (and displaced
    occupants at the members' old positions) simultaneously, so
    intra-macro nets see a ~zero delta under pure translation — summing
    per-member pairwise deltas would over-charge every chain link by
    ~2*q*D and freeze the macros.

    blocks/occs [Mm, Lm] (pads -1), newpos [Mm, Lm, 2], memv [Mm, Lm].
    Returns delta [Mm]."""
    Mm, Lm = blocks.shape
    F = pp.blk_net.shape[1]
    bc = jnp.clip(blocks, 0)
    oc = jnp.clip(occs, 0)
    bnets = jnp.where(memv[:, :, None], pp.blk_net[bc], -1)
    onets = jnp.where((occs >= 0)[:, :, None], pp.blk_net[oc], -1)
    nets = jnp.concatenate([bnets, onets], axis=1).reshape(Mm, -1)
    # dedupe within a proposal (a net touching two members must count
    # its delta once): sort, mask repeats
    nets = jnp.sort(nets, axis=1)
    rep = jnp.concatenate(
        [jnp.zeros((Mm, 1), bool), nets[:, 1:] == nets[:, :-1]], axis=1)
    nets = jnp.where(rep, -1, nets)
    nvalid = nets >= 0
    netsc = jnp.clip(nets, 0)
    pblk = pp.net_blk[netsc]                       # [Mm, 2LmF, P]
    pvalid = pp.net_valid[netsc] & nvalid[:, :, None]
    px = pos[jnp.clip(pblk, 0), 0]
    py = pos[jnp.clip(pblk, 0), 1]
    # member / occupant membership with slot recovery
    eq_m = (pblk[:, :, :, None] == bc[:, None, None, :]) \
        & memv[:, None, None, :]
    is_m = eq_m.any(axis=3)
    mi = jnp.argmax(eq_m, axis=3)                  # member slot
    eq_o = (pblk[:, :, :, None] == oc[:, None, None, :]) \
        & (occs >= 0)[:, None, None, :]
    is_o = eq_o.any(axis=3) & ~is_m
    oi = jnp.argmax(eq_o, axis=3)
    m_new_x = jnp.take_along_axis(
        newpos[:, :, 0], mi.reshape(Mm, -1), axis=1).reshape(mi.shape)
    m_new_y = jnp.take_along_axis(
        newpos[:, :, 1], mi.reshape(Mm, -1), axis=1).reshape(mi.shape)
    # occupant i takes member i's OLD position
    o_old_x = jnp.take_along_axis(
        pos[bc, 0], oi.reshape(Mm, -1), axis=1).reshape(oi.shape)
    o_old_y = jnp.take_along_axis(
        pos[bc, 1], oi.reshape(Mm, -1), axis=1).reshape(oi.shape)
    npx = jnp.where(is_m, m_new_x, jnp.where(is_o, o_old_x, px))
    npy = jnp.where(is_m, m_new_y, jnp.where(is_o, o_old_y, py))
    big = jnp.int32(10 ** 6)

    def bbsum(ax, ay):
        xmin = jnp.where(pvalid, ax, big).min(axis=2)
        xmax = jnp.where(pvalid, ax, -big).max(axis=2)
        ymin = jnp.where(pvalid, ay, big).min(axis=2)
        ymax = jnp.where(pvalid, ay, -big).max(axis=2)
        q = pp.net_q[netsc]
        return q * ((xmax - xmin + 1) + (ymax - ymin + 1)).astype(
            jnp.float32)

    return jnp.where(nvalid, bbsum(npx, npy) - bbsum(px, py),
                     0.0).sum(axis=1)              # [Mm]


@functools.partial(jax.jit, static_argnames=("Mm", "Lm"))
def macro_step(pp: PlaceProblem, mac_blocks, mac_len, pos, ring_idx, occ,
               key, t, rlim, inv_bb, Mm: int, Lm: int):
    """Batched rigid macro moves (place_macro.c semantics): propose Mm
    vertical relocations of whole carry-chain macros; each member i
    pairwise-swaps with the occupant of target site (x', y0+i).
    Occupied-by-macro targets and site conflicts are rejected via the
    same lowest-index site-claim rule as single moves; Metropolis on the
    summed member deltas.  Interior (CLB-column) macros only — carry
    chains never contain IO blocks."""
    NM = mac_blocks.shape[0]
    NB = pp.num_blocks
    NS = pp.num_sites
    kp, kc, ky, ka = jax.random.split(key, 4)
    mi = jax.random.randint(kp, (Mm,), 0, NM)
    blocks = mac_blocks[mi]                            # [Mm, Lm] pad -1
    L = mac_len[mi]                                    # [Mm]
    memv = (jnp.arange(Lm)[None, :] < L[:, None]) & (blocks >= 0)
    b0 = jnp.clip(blocks[:, 0], 0)
    rl = jnp.maximum(1, rlim.astype(jnp.int32))

    tid = pp.type_id[b0]
    nc = pp.ncols[tid]
    rl_col = jnp.maximum(1, (rl * nc) // jnp.int32(pp.nx))
    u = jax.random.uniform(kc, (Mm,), minval=-1.0, maxval=1.0)
    ci0 = pp.col_idx_of_x[tid, pos[b0, 0]]
    ci = jnp.clip(ci0 + jnp.round(u * rl_col.astype(jnp.float32))
                  .astype(jnp.int32), 0, nc - 1)
    cx = pp.col_list[tid, ci]                          # [Mm]
    dy = jax.random.randint(ky, (Mm,), -rl, rl + 1)
    y0 = jnp.clip(pos[b0, 1] + dy, 1, pp.ny - L + 1)
    ty = y0[:, None] + jnp.arange(Lm)[None, :]         # [Mm, Lm]

    bc = jnp.clip(blocks, 0)
    src = (pos[bc, 1] - 1) * pp.nx + (pos[bc, 0] - 1)  # [Mm, Lm]
    dst = (ty - 1) * pp.nx + (cx[:, None] - 1)
    src = jnp.where(memv, src, NS)
    dst = jnp.where(memv, dst, NS)
    occ_p1 = jnp.concatenate([occ, jnp.full((1,), -1, occ.dtype)])
    o = jnp.where(memv, occ_p1[jnp.clip(dst, 0, NS)], -1)  # [Mm, Lm]
    # an occupant that IS a member of this macro means the runs overlap
    o_frozen = (o >= 0) & pp.frozen[jnp.clip(o, 0)]
    self_move = (dst == src).all(axis=1)

    idx = jnp.arange(Mm, dtype=jnp.int32)
    claim = jnp.full(NS + 1, Mm, jnp.int32)
    claim = claim.at[src].min(idx[:, None])
    claim = claim.at[dst].min(idx[:, None])
    won = jnp.where(memv,
                    (claim[src] == idx[:, None])
                    & (claim[dst] == idx[:, None]), True)
    own = (won.all(axis=1) & ~self_move & ~o_frozen.any(axis=1)
           & (jnp.where(memv, ty, 1) <= pp.ny).all(axis=1) & (L > 0))

    # joint rigid delta (intra-macro nets translate for free)
    newpos = jnp.stack([jnp.broadcast_to(cx[:, None], ty.shape), ty],
                       axis=2)                     # [Mm, Lm, 2]
    occs = jnp.where(memv, o, -1)
    delta = _macro_delta_bb(pp, pos, jnp.where(memv, bc, -1), occs,
                            newpos, memv)
    flat_b = jnp.where(memv, bc, 0).reshape(-1)
    flat_o = occs.reshape(-1)
    u2 = jax.random.uniform(ka, (Mm,))
    accept = own & ((delta * inv_bb <= 0)
                    | (u2 < jnp.exp(-delta * inv_bb
                                    / jnp.maximum(t, 1e-30))))

    accm = accept[:, None] & memv
    bb_sc = jnp.where(accm, bc, NB).reshape(-1)
    oo_sc = jnp.where(accm & (o >= 0), o, NB).reshape(-1)
    pos2 = jnp.concatenate([pos, jnp.zeros((1, 3), pos.dtype)], axis=0)
    newp = jnp.concatenate(
        [newpos, jnp.zeros((Mm, Lm, 1), pos.dtype)], axis=2).reshape(-1, 3)
    oldp = pos[bc].reshape(-1, 3)
    pos2 = pos2.at[bb_sc].set(newp)
    pos2 = pos2.at[oo_sc].set(oldp)
    ssrc = jnp.where(accm, src, NS).reshape(-1)
    sdst = jnp.where(accm, dst, NS).reshape(-1)
    occ2 = occ.at[ssrc].set(flat_o, mode="drop")
    occ2 = occ2.at[sdst].set(flat_b, mode="drop")
    return pos2[:NB], ring_idx, occ2, accept.sum()


class PlacerTiming:
    """Bundle wiring the placer to the timing subsystem: the delay-lookup
    matrices plus the STA machinery for criticality recomputation
    (alloc_lookups_and_criticalities, timing_place.c:121)."""

    def __init__(self, pnl: PackedNetlist, lookup, term, tg,
                 td_place_exp: float = 8.0):
        from ..timing.sta import TimingAnalyzer

        self.lookup = lookup
        self.term = term
        self.analyzer = TimingAnalyzer(tg, crit_exp=td_place_exp)
        R, Smax = term.sinks.shape
        # per-connection block endpoints for lookup-delay evaluation
        self.drv_blk = np.zeros(R, dtype=np.int32)
        self.snk_blk = np.zeros((R, Smax), dtype=np.int32)
        self.conn_valid = np.zeros((R, Smax), dtype=bool)
        # (r, s) -> (costed-net row, uniq-block slot) for crit scatter
        self.map_row = np.zeros((R, Smax), dtype=np.int64)
        self.map_slot = np.zeros((R, Smax), dtype=np.int64)
        is_io = [pnl.block_type(i).is_io for i in range(pnl.num_blocks)]
        self.is_io = np.array(is_io)
        for r, ni in enumerate(term.net_ids):
            net = pnl.nets[int(ni)]
            self.drv_blk[r] = net.driver.block
            uniq = {}
            uniq[net.driver.block] = 0
            for p in net.sinks:
                if p.block not in uniq:
                    uniq[p.block] = len(uniq)
            for s, p in enumerate(net.sinks):
                self.snk_blk[r, s] = p.block
                self.conn_valid[r, s] = True
                self.map_row[r, s] = r
                self.map_slot[r, s] = uniq[p.block]

    def criticalities(self, pos: np.ndarray, NN: int, P: int) -> tuple:
        """(crit [NN, P], crit_path_delay) for the current positions using
        lookup delays (load_criticalities timing_place.c:81)."""
        sx = pos[self.drv_blk, 0][:, None]
        sy = pos[self.drv_blk, 1][:, None]
        s_io = self.is_io[self.drv_blk][:, None]
        tx = pos[self.snk_blk, 0]
        ty = pos[self.snk_blk, 1]
        t_io = self.is_io[self.snk_blk]
        d = self.lookup.conn_delay(sx, sy, s_io, tx, ty, t_io)
        d = np.where(self.conn_valid, d, 0.0)
        crit_rs = self.analyzer.analyze(d)
        crit = np.zeros((NN, P), dtype=np.float32)
        np.maximum.at(crit, (self.map_row[self.conn_valid],
                             self.map_slot[self.conn_valid]),
                      crit_rs[self.conn_valid])
        return crit, self.analyzer.crit_path_delay


class Placer:
    """Host driver owning the annealing schedule (place.c:310 try_place)."""

    def __init__(self, pnl: PackedNetlist, grid: DeviceGrid,
                 opts: Optional[PlacerOpts] = None,
                 timing: Optional[PlacerTiming] = None,
                 macros=None):
        self.pnl, self.grid = pnl, grid
        self.opts = opts or PlacerOpts()
        self.timing = timing
        # a chain taller than the grid splits into column-height
        # segments (the reference's multi-column carry handling reduced
        # to its placement effect: each segment stays contiguous)
        self.macros = []
        for m in (macros or []):
            for lo in range(0, len(m), max(2, grid.ny)):
                seg = m[lo:lo + max(2, grid.ny)]
                if len(seg) >= 2:
                    self.macros.append(seg)
        self.pp = build_place_problem(
            pnl, grid, lookup=timing.lookup if timing else None,
            macros=self.macros)
        self._ring_of = _ring_index_host(grid)
        self._mac_blocks = self._mac_len = None
        if self.macros:
            Lm = max(len(m) for m in self.macros)
            mb = np.full((len(self.macros), Lm), -1, dtype=np.int32)
            for i, m in enumerate(self.macros):
                mb[i, :len(m)] = m
            self._mac_blocks = jnp.asarray(mb)
            self._mac_len = jnp.asarray(
                np.array([len(m) for m in self.macros], dtype=np.int32))

    def _state_from_pos(self, pos_np: np.ndarray):
        pp = self.pp
        NB = self.pnl.num_blocks
        ring = np.full(NB, -1, dtype=np.int32)
        for i in range(NB):
            if bool(np.asarray(pp.is_io)[i]):
                ring[i] = self._ring_of[(int(pos_np[i, 0]),
                                         int(pos_np[i, 1]))]
        pos = jnp.asarray(pos_np, dtype=jnp.int32)
        ring_j = jnp.asarray(ring)
        site = np.asarray(_site_of(pp, pos, ring_j))
        occ = np.full(pp.num_sites, -1, dtype=np.int32)
        if len(site) != len(set(site.tolist())):
            raise ValueError("initial placement has site collisions")
        occ[site] = np.arange(NB)
        return pos, ring_j, jnp.asarray(occ)

    def _crit(self, pos_np: np.ndarray):
        pp = self.pp
        NN, P = pp.net_blk.shape
        if self.timing is None:
            return jnp.zeros((NN, P), jnp.float32), float("nan")
        crit, cpd = self.timing.criticalities(pos_np, NN, P)
        return jnp.asarray(crit), cpd

    def place(self, pos0: np.ndarray) -> Tuple[np.ndarray, PlaceStats]:
        opts, pp = self.opts, self.pp
        NB = self.pnl.num_blocks
        NN = pp.net_blk.shape[0]
        tt = jnp.float32(opts.timing_tradeoff if self.timing else 0.0)
        M = min(opts.moves_per_step, max(8, NB))
        steps = max(1, math.ceil(opts.inner_num * NB ** (4 / 3) / M))
        if self.macros:
            # macro-align the initial placement (place_macro.c initial
            # macro placement): members occupy vertical runs
            from .macros import align_initial
            pos0 = align_initial(self.pnl, self.grid, pos0, self.macros)
        pos, ring, occ = self._state_from_pos(pos0)
        key = jax.random.PRNGKey(opts.seed)

        crit, _ = self._crit(pos0)
        bb_cost, _ = net_bb_cost(pp, pos)
        td_cost = net_td_cost(pp, pos, crit)
        bb_cost, td_cost = float(bb_cost), float(td_cost)
        stats = PlaceStats(initial_cost=bb_cost)

        def norms():
            # inverse-cost normalization, recomputed per temperature
            # (place.c inverse_prev_bb_cost / inverse_prev_timing_cost)
            return (jnp.float32(1.0 / max(bb_cost, 1e-30)),
                    jnp.float32(1.0 / max(td_cost, 1e-30)))

        # starting_t (place.c:506): std-dev of random-move deltas at t=inf
        key, k = jax.random.split(key)
        inv_bb, inv_td = norms()
        _, _, _, _, nv, dsum, dsq = sa_step(
            pp, pos, ring, occ, crit, inv_bb, inv_td, tt, k,
            jnp.float32(1e30), jnp.float32(max(pp.nx, pp.ny)), M,
            self.timing is not None)
        nv = max(1, int(nv))
        var = float(dsq) / nv - (float(dsum) / nv) ** 2
        t = 20.0 * math.sqrt(max(var, 1e-12))
        rlim = float(max(pp.nx, pp.ny))

        # segment size: with timing, criticalities must refresh every
        # recompute_crit_temps temperatures (host STA round trip); pure
        # wirelength anneals sync only once per SEG temperatures
        exit_t = opts.exit_t_frac / max(1, NN)
        SEG = (max(1, opts.recompute_crit_temps)
               if self.timing is not None else 8)
        temp_i = 0
        while temp_i < opts.max_temps:
            if self.timing is not None:
                crit, _ = self._crit(np.asarray(pos))
            n_temps = min(SEG, opts.max_temps - temp_i)
            key, k = jax.random.split(key)
            with span("place.segment", cat="place", n_temps=n_temps,
                      t=float(t)):
                (pos, ring, occ, t_d, rlim_d, na_a, nv_a, bb_a, live_a,
                 ts_a, rl_a) = sa_segment(
                    pp, pos, ring, occ, crit, tt, k,
                    jnp.float32(t), jnp.float32(rlim),
                    jnp.float32(exit_t), M, steps, n_temps,
                    self.timing is not None)
                # rigid macro relocations ride along once per segment
                # (place_macro.c try_swap-for-macros; async dispatches)
                if self._mac_blocks is not None:
                    Lm = int(self._mac_blocks.shape[1])
                    Mm = min(32, max(4, len(self.macros)))
                    inv_bb_m = jnp.float32(1.0 / max(bb_cost, 1e-30))
                    for _ in range(4):
                        key, k2 = jax.random.split(key)
                        pos, ring, occ, _ = macro_step(
                            pp, self._mac_blocks, self._mac_len, pos,
                            ring, occ, k2, jnp.float32(t),
                            jnp.float32(rlim), inv_bb_m, Mm, Lm)
                # ONE host sync per segment
                t, rlim, na_a, nv_a, bb_a, live_a, ts_a, rl_a = \
                    jax.device_get((t_d, rlim_d, na_a, nv_a, bb_a,
                                    live_a, ts_a, rl_a))
            t, rlim = float(t), float(rlim)
            reg = get_metrics()
            for i in range(n_temps):
                if live_a[i] == 0.0:
                    break
                srat = int(na_a[i]) / max(1, int(nv_a[i]))
                stats.temps.append((float(ts_a[i]), float(bb_a[i]), srat,
                                    float(rl_a[i])))
                stats.total_moves += int(nv_a[i])
                # per-temperature telemetry (try_place's per-temp print
                # row as registry instruments; snapshots give the full
                # schedule trajectory)
                reg.gauge("place.t").set(float(ts_a[i]))
                reg.gauge("place.bb_cost").set(float(bb_a[i]))
                reg.gauge("place.success_rate").set(srat)
                reg.gauge("place.rlim").set(float(rl_a[i]))
                reg.counter("place.moves").inc(int(nv_a[i]))
                reg.counter("place.accepted_moves").inc(int(na_a[i]))
                reg.histogram("place.acceptance_rate").record(srat)
                reg.snapshot(phase="place",
                             temperature=len(stats.temps) - 1)
            temp_i += n_temps
            bb_cost = float(bb_a[-1])
            # exit_crit (place.c:270) on the normalized combined cost
            if t < exit_t:
                break

        # final quench at t=0 (via sa_segment so the cost normalization
        # is computed fresh on device, not from pre-anneal values)
        if self.timing is not None:
            crit, _ = self._crit(np.asarray(pos))
        key, k = jax.random.split(key)
        pos, ring, occ, _, _, _, _, bb_a, _, _, _ = sa_segment(
            pp, pos, ring, occ, crit, tt, k, jnp.float32(0.0),
            jnp.float32(1.0), jnp.float32(exit_t), M, steps, 1,
            self.timing is not None)
        stats.final_cost = float(bb_a[-1])
        stats.final_td_cost = float(net_td_cost(pp, pos, crit)) \
            if self.timing is not None else 0.0
        if self.timing is not None:
            _, stats.est_crit_path = self._crit(np.asarray(pos))
        reg = get_metrics()
        reg.gauge("place.final_cost").set(stats.final_cost)
        reg.gauge("place.total_moves").set(int(stats.total_moves))
        if stats.est_crit_path == stats.est_crit_path:
            reg.gauge("place.est_crit_path").set(
                float(stats.est_crit_path))
        reg.snapshot(phase="place_final", temps=len(stats.temps))
        # final legality audit (check_place, place.c:253): an annealer
        # bug must never hand the router an illegal placement silently
        from .check import check_place

        pos_np = np.asarray(pos)
        check_place(self.pnl, self.grid, pos_np)
        return pos_np, stats
