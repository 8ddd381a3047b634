"""Batched shortest-path search on the TPU.

Replaces the reference's per-sink sequential A*/Dijkstra heap expansion
(vpr/SRC/parallel_route/dijkstra.h:15, SinkRouter
partitioning_multi_sink_delta_stepping_route.cxx:360-815) with a pull-based
Bellman-Ford relaxation vmapped over a *batch of nets*:

    dist[b, v] <- min(dist[b, v],
                      min_d dist[b, ell_src[v, d]] + w(b, v, d))

with  w = crit_b * edge_delay + (1 - crit_b) * cong_cost[b, v]
(the PathFinder cost of vpr/SRC/route/route_timing.c:603
timing_driven_expand_neighbours: crit * Tdel + (1-crit) * rr_cong_cost).

Multi-sink nets are routed *incrementally*, VPR-style: sinks are picked in
waves (most critical / nearest first), each wave's relaxation is seeded with
distance 0 on every node of the tree routed so far, so later sinks reuse the
existing tree (route_tree_timing.c semantics; the reference's sink-parallel
variant MultiSinkParallelRouter:975 maps to group>1 — several sinks per wave
share one relaxation).  Without this seeding, a net's sinks take independent
shortest paths and e.g. two nets driven by a 2-pin output class can each
grab both OPINs and livelock on overuse.

Search is confined to the net bounding box by masking (route.h:70-165
per-net boxes, SinkRouter::expand_node:466 pruning).  Everything is
fixed-shape and jit-compiled; inner loops are lax.while_loop / lax.scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from .device_graph import DeviceRRGraph

INF = jnp.inf

# relative magnitude of the symmetry-breaking congestion jitter: nets with
# identical terminals (bus nets) routed against the same frozen congestion
# snapshot would otherwise pick identical paths every iteration and livelock
# — the reference never hits this because it serialises congestion commits
# (coloring schedule / det_mutex); a stable multiplicative per-(net, node)
# perturbation restores negotiation while keeping runs bit-reproducible.
JITTER_EPS = 0.02


def congestion_cost_arrays(base, capacity, occ, acc, pres_fac):
    """base * pres * acc from explicit arrays (any matching shapes) —
    the ONE place the PathFinder present-cost formula lives; the global
    and windowed programs both call it so they can never diverge."""
    over = occ + 1 - capacity
    pres = jnp.where(over > 0, 1.0 + over.astype(jnp.float32) * pres_fac,
                     1.0)
    return base * pres * acc


def congestion_cost(dev: DeviceRRGraph, occ: jnp.ndarray, acc: jnp.ndarray,
                    pres_fac: jnp.ndarray) -> jnp.ndarray:
    """Per-node congestion cost  base * pres * acc.

    occ may be [N] (global) or [B, N] (per-net views — each net sees the
    occupancy of *everyone but itself*, which is how the serial reference
    negotiates: when net i reroutes, occ still contains all other nets'
    paths, route_timing.c rip-up-one-at-a-time semantics).  pres is the
    *speculative* present cost of adding one more user
    (vpr/SRC/route/route_common.c get_rr_cong_cost +
    parallel_route/congestion.h:177-193 update_costs semantics).
    """
    return congestion_cost_arrays(dev.cong_base, dev.capacity, occ, acc,
                                  pres_fac)


def _relax(dev: DeviceRRGraph, cong_c: jnp.ndarray, crit_c: jnp.ndarray,
           inside: jnp.ndarray, seed: jnp.ndarray, seed_tdel: jnp.ndarray,
           max_steps: int):
    """One seeded Bellman-Ford solve for a batch.

    cong_c [B, N] congestion term (already scaled by (1-crit) and jitter);
    crit_c [B, 1] delay-term weight; inside [B, N] bb mask; seed [B, N] tree
    nodes (dist 0); seed_tdel [B, N] true delay-from-source at tree nodes.
    Returns (dist, prev, tdel): tdel[b, v] is the accumulated *pure delay*
    from the net source along the chosen min-cost path (rides along with the
    cost minimisation; this is what STA consumes, t_net_timing
    vpr_types.h:1134).
    """
    B, N = cong_c.shape
    D = dev.max_in_degree

    dist0 = jnp.where(seed, 0.0, INF)
    tdel0 = jnp.where(seed, seed_tdel, 0.0)
    prev0 = jnp.full((B, N), -1, jnp.int32)

    # ELL slots are processed in blocks of DB: one [B, N, DB] gather +
    # min-reduce per block.  Per-slot fori_loop (DB=1) would issue D tiny
    # ops whose fixed device overhead dominates on small graphs; a single
    # [B, N, D] gather (DB=D) multiplies peak memory by D and OOMs large
    # graphs.  Blocks bound memory at [B, N, DB] while keeping the
    # sequential chain short (ceil(D/DB) ops).
    DB = min(8, D)
    nblocks = -(-D // DB)
    arangeN = jnp.arange(N)[None, :]

    def step(state):
        dist, prev, tdel, _, it = state

        def blk(b, carry):
            best0, bsrc0, btdel0 = carry
            # the last block is shifted to stay in range; the overlap
            # re-evaluates a few slots, harmless under min
            d0 = jnp.minimum(b * DB, D - DB)
            s = lax.dynamic_slice_in_dim(dev.ell_src, d0, DB, axis=1)
            w = lax.dynamic_slice_in_dim(dev.ell_delay, d0, DB, axis=1)
            valid = lax.dynamic_slice_in_dim(dev.ell_valid, d0, DB, axis=1)
            ds = dist[:, s]                                    # [B, N, DB]
            cand3 = ds + crit_c[:, :, None] * w[None] + cong_c[:, :, None]
            cand3 = jnp.where(valid[None], cand3, INF)
            bbest = jnp.min(cand3, axis=2)                     # [B, N]
            slot = jnp.argmin(cand3, axis=2)
            bsrc = s[arangeN, slot]
            w_pick = w[arangeN, slot]
            btdel = jnp.take_along_axis(tdel, bsrc, axis=1) + w_pick
            better = bbest < best0
            return (jnp.where(better, bbest, best0),
                    jnp.where(better, bsrc, bsrc0),
                    jnp.where(better, btdel, btdel0))

        best, bsrc, btdel = lax.fori_loop(
            0, nblocks, blk,
            (jnp.full((B, N), INF, jnp.float32),
             jnp.full((B, N), -1, jnp.int32),
             jnp.zeros((B, N), jnp.float32)))

        cand = jnp.where(inside, best, INF)
        improved = cand < dist
        dist2 = jnp.where(improved, cand, dist)
        prev2 = jnp.where(improved, bsrc, prev)
        tdel2 = jnp.where(improved, btdel, tdel)
        return dist2, prev2, tdel2, jnp.any(improved), it + 1

    def cond(state):
        return state[3] & (state[4] < max_steps)

    dist, prev, tdel, _, steps = lax.while_loop(
        cond, step, (dist0, prev0, tdel0, jnp.bool_(True), jnp.int32(0)))
    return dist, prev, tdel, steps


def _traceback(prev: jnp.ndarray, seed: jnp.ndarray, sink: jnp.ndarray,
               max_len: int):
    """Walk prev pointers from sink until a seed (tree) node; [B, G] sinks.

    Returns (path [B, G, L] node ids, sentinel N = pad; reached [B, G]).
    The joining tree node is included in the path (for wave 1 that is the
    SOURCE, so a sink's stored path always ends on the existing tree).
    """
    B, N = prev.shape

    def one(prev_b, seed_b, sk):
        valid0 = sk >= 0

        def body(carry, _):
            node, done = carry
            nc = jnp.clip(node, 0)
            at_tree = seed_b[nc]
            dead = node < 0
            emit = jnp.where(done | dead, N, node)
            nxt = jnp.where(done | at_tree | dead, node, prev_b[nc])
            return (nxt, done | at_tree | dead), emit

        (last, _), path = lax.scan(
            body, (jnp.where(valid0, sk, -1), ~valid0), None, length=max_len)
        reached = valid0 & (last >= 0) & seed_b[jnp.clip(last, 0)]
        path = jnp.where(reached, path, N)
        return path, reached

    return jax.vmap(jax.vmap(one, in_axes=(None, None, 0)),
                    in_axes=(0, 0, 0))(prev, seed, sink)


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "max_len", "num_waves",
                                    "group"))
def route_net_batch(dev: DeviceRRGraph, cong: jnp.ndarray,
                    source: jnp.ndarray, sinks: jnp.ndarray,
                    bb: jnp.ndarray, crit: jnp.ndarray,
                    net_key: jnp.ndarray,
                    max_steps: int, max_len: int, num_waves: int,
                    group: int):
    """Route a batch of B nets completely (all sinks, incremental tree).

    cong [B, N] per-net congestion cost; source [B]; sinks [B, S] (-1 pad);
    bb [B, 4]; crit [B, S] per-sink criticalities; net_key [B] stable ids
    for the symmetry-breaking jitter.

    The sink waves run as a device while_loop (one compiled wave body, not
    num_waves unrolled copies — compile time, and early exit when every
    net's sinks are done); num_waves only caps the trip count.

    Returns (paths [B, S, L] sentinel-N-padded sink->tree segments,
    reached [B, S], sink_delay [B, S], usage [B, N] tree-node masks,
    relax_steps scalar — total Bellman-Ford sweeps, the perf_t
    heap-pops/neighbor-visits analogue, route.h:12-20; one sweep visits
    every in-edge of every in-box node once).
    """
    B, S = sinks.shape
    N = dev.num_nodes

    inside = ((dev.xhigh[None, :] >= bb[:, 0, None])
              & (dev.xlow[None, :] <= bb[:, 1, None])
              & (dev.yhigh[None, :] >= bb[:, 2, None])
              & (dev.ylow[None, :] <= bb[:, 3, None]))           # [B, N]

    # deterministic per-(net, node) hash in [0, 1)
    h = (net_key[:, None] * jnp.int32(2654435761 & 0x7FFFFFFF)
         + jnp.arange(N, dtype=jnp.int32)[None, :] * jnp.int32(40503))
    jitter = 1.0 + JITTER_EPS * ((h & 0xFFFF).astype(jnp.float32) / 65536.0)

    arangeB = jnp.arange(B)
    # seed with one slot of slack so sentinel scatters drop cleanly
    seed0 = jnp.zeros((B, N + 1), bool).at[arangeB, source].set(True)

    def wave_body(state):
        (seed, tdel_tree, remaining, paths, delay, reached_all,
         relax_steps, wave) = state
        # wave criticality: strongest remaining sink drives the delay weight
        crit_w = jnp.max(jnp.where(remaining, crit, 0.0), axis=1)  # [B]
        cong_c = (1.0 - crit_w)[:, None] * cong * jitter
        dist, prev, tdel, steps = _relax(dev, cong_c, crit_w[:, None],
                                         inside, seed[:, :N], tdel_tree,
                                         max_steps)
        relax_steps = relax_steps + steps

        # pick up to `group` sinks: most critical first, nearest to the
        # current tree among equals (route_timing.c sorts sinks by
        # criticality; nearest-first minimises wirelength when crit == 0)
        sink_c = jnp.clip(sinks, 0)
        sd = dist[arangeB[:, None], sink_c]                       # [B, S]
        score = jnp.where(remaining & jnp.isfinite(sd),
                          sd - crit * 1e3, INF)
        order = jnp.argsort(score, axis=1)[:, :group]             # [B, G]
        pick_valid = (jnp.take_along_axis(remaining, order, axis=1)
                      & jnp.isfinite(jnp.take_along_axis(score, order,
                                                         axis=1)))
        pick_sink = jnp.where(pick_valid,
                              jnp.take_along_axis(sinks, order, axis=1), -1)

        seg, seg_reached = _traceback(prev, seed[:, :N], pick_sink, max_len)
        ok = pick_valid & seg_reached                             # [B, G]

        # store segments and delays at the picked sink slots
        old = jnp.take_along_axis(paths, order[:, :, None], axis=1)
        paths = _scatter_rows(paths, order,
                              jnp.where(ok[:, :, None], seg, old))
        d_new = tdel[arangeB[:, None], jnp.clip(pick_sink, 0)]
        old_d = jnp.take_along_axis(delay, order, axis=1)
        delay = _scatter_vals(delay, order, jnp.where(ok, d_new, old_d))
        old_r = jnp.take_along_axis(reached_all, order, axis=1)
        reached_all = _scatter_vals(reached_all, order, ok | old_r)
        old_rem = jnp.take_along_axis(remaining, order, axis=1)
        remaining = _scatter_vals(remaining, order, old_rem & ~ok)

        # grow the tree: segment nodes become seeds with their true delay
        flat = jnp.where(ok[:, :, None], seg, N).reshape(B, -1)
        newly = jnp.zeros((B, N + 1), bool).at[
            arangeB[:, None], flat].set(True)
        tdel_tree = jnp.where(newly[:, :N], tdel, tdel_tree)
        seed = seed | newly
        return (seed, tdel_tree, remaining, paths, delay, reached_all,
                relax_steps, wave + 1)

    def wave_cond(state):
        remaining, wave = state[2], state[7]
        # a sink whose score stayed INF (unreachable in-box) keeps
        # remaining true but can't make progress: the static wave cap
        # bounds the loop exactly like the old unrolled version
        return jnp.any(remaining) & (wave < num_waves)

    state0 = (seed0, jnp.zeros((B, N), jnp.float32), sinks >= 0,
              jnp.full((B, S, max_len), N, jnp.int32),
              jnp.full((B, S), INF, jnp.float32),
              jnp.zeros((B, S), bool), jnp.int32(0), jnp.int32(0))
    (seed, _, _, paths, delay, reached_all, relax_steps,
     _) = lax.while_loop(wave_cond, wave_body, state0)

    return paths, reached_all, delay, seed[:, :N], relax_steps


def _scatter_rows(arr, idx, vals):
    """arr [B, S, L], idx [B, G], vals [B, G, L] -> arr with rows replaced."""
    B = arr.shape[0]
    return arr.at[jnp.arange(B)[:, None], idx].set(vals)


def _scatter_vals(arr, idx, vals):
    """arr [B, S], idx [B, G], vals [B, G]."""
    B = arr.shape[0]
    return arr.at[jnp.arange(B)[:, None], idx].set(vals)


@functools.partial(
    jax.jit, static_argnames=("max_steps", "max_len", "num_waves", "group"))
def route_and_commit(dev: DeviceRRGraph, occ, acc, pres_fac,
                     prev_paths, source, sinks, bb, crit, net_key, valid,
                     max_steps: int, max_len: int, num_waves: int,
                     group: int):
    """One fused batch step: rip up the batch's previous paths, route every
    net against the occupancy view of everyone-but-itself, commit the new
    occupancy.  Single dispatch — the whole PathFinder inner step is one
    XLA program, so under a (net, node) mesh the cross-shard sums become
    psums and the serial Router pays one host round-trip per batch.

    Returns (paths, reached, delay, occ_new, relax_steps)."""
    N = dev.num_nodes
    nodes_p1 = jnp.zeros(N + 1, dtype=jnp.float32)
    old_usage = usage_from_paths(prev_paths, nodes_p1)
    old_usage = old_usage & valid[:, None]
    occ_rip = occ - jnp.sum(old_usage, axis=0, dtype=jnp.int32)
    # each net sees everyone else's occupancy: global minus its own usage
    # (serial rip-up-one-net view, route_timing.c:399 semantics)
    occ_view = occ[None, :] - old_usage.astype(jnp.int32)

    cong = congestion_cost(dev, occ_view, acc, pres_fac)
    paths, reached, delay, usage, relax_steps = route_net_batch(
        dev, cong, source, sinks, bb, crit, net_key,
        max_steps, max_len, num_waves, group)
    usage = usage & valid[:, None]
    occ_new = occ_rip + jnp.sum(usage, axis=0, dtype=jnp.int32)
    return paths, reached, delay, occ_new, relax_steps


@jax.jit
def usage_from_paths(path: jnp.ndarray, num_nodes_p1: jnp.ndarray):
    """Per-net deduplicated node usage mask.

    path [B, S, L] with sentinel N; returns bool [B, N].  A node used by
    several sink segments of the same net counts once (occupancy is per
    net, route_tree semantics of parallel_route/route_tree.c).
    num_nodes_p1: zeros [N+1] template (keeps N out of the traced shapes).
    """
    B = path.shape[0]
    flat = path.reshape(B, -1)
    u = jnp.zeros((B, num_nodes_p1.shape[0]), bool)
    u = u.at[jnp.arange(B)[:, None], flat].set(True)
    return u[:, :-1]


@jax.jit
def occupancy_delta(usage: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Sum per-net usage masks into an occupancy delta [N] (int32)."""
    return jnp.sum(usage & valid[:, None], axis=0, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Device-resident stepping.
#
# Host<->device transfers are the scarce resource, so the Router
# keeps ALL route state (paths, per-sink delays, reached flags, bounding
# boxes, occupancy, history) resident on the device for the whole route()
# call.  Each batch step transfers only the selected net indices in and one
# scalar out; the reference's analogue is that its routers never serialize
# route trees either — state lives in shared memory / MPI windows
# (route.h:70-165 trees, congestion_t[] occupancy).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("max_steps", "max_len", "num_waves", "group", "mesh"),
    donate_argnames=("occ", "paths", "sink_delay", "all_reached", "bb"))
def route_batch_resident(dev: DeviceRRGraph, occ, acc, pres_fac,
                         paths, sink_delay, all_reached, bb,
                         source_all, sinks_all, crit_all,
                         sel, valid, full_bb,
                         max_steps: int, max_len: int, num_waves: int,
                         group: int, mesh=None):
    """One fused batch step against device-resident whole-circuit state.

    paths [R, S, L] / sink_delay [R, S] / all_reached [R] / bb [R, 4] are
    the resident arrays; sel [B] picks this batch's nets (valid [B] masks
    padding).  Gathers the batch rows, rips up, routes every net against
    the occupancy view of everyone-but-itself, commits, scatters the rows
    back, and widens the bounding box of any net with an unreachable sink
    to the whole device (place_and_route.c bb relaxation).  Donation makes
    the update in-place on device.

    Returns (paths, sink_delay, all_reached, bb, occ, relax_steps).
    """
    N = dev.num_nodes
    R = paths.shape[0]

    b_paths = paths[sel]
    b_src = source_all[sel]
    b_sinks = sinks_all[sel]
    b_bb = bb[sel]
    b_crit = crit_all[sel]
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def c(x, *spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        b_paths = c(b_paths, "net", None, None)
        b_src = c(b_src, "net")
        b_sinks = c(b_sinks, "net", None)
        b_bb = c(b_bb, "net", None)
        b_crit = c(b_crit, "net", None)

    nodes_p1 = jnp.zeros(N + 1, dtype=jnp.float32)
    old_usage = usage_from_paths(b_paths, nodes_p1) & valid[:, None]
    occ_rip = occ - jnp.sum(old_usage, axis=0, dtype=jnp.int32)
    occ_view = occ[None, :] - old_usage.astype(jnp.int32)

    cong = congestion_cost(dev, occ_view, acc, pres_fac)
    p, reached, delay, usage, relax_steps = route_net_batch(
        dev, cong, b_src, b_sinks, b_bb, b_crit, sel.astype(jnp.int32),
        max_steps, max_len, num_waves, group)
    usage = usage & valid[:, None]
    occ_new = occ_rip + jnp.sum(usage, axis=0, dtype=jnp.int32)

    smask = b_sinks >= 0
    ok = (reached | ~smask).all(axis=1)
    new_bb = jnp.where(ok[:, None], b_bb, full_bb[None, :])

    # padded rows scatter out of range and are dropped
    sel_v = jnp.where(valid, sel, R).astype(jnp.int32)
    paths = paths.at[sel_v].set(p, mode="drop")
    sink_delay = sink_delay.at[sel_v].set(delay, mode="drop")
    all_reached = all_reached.at[sel_v].set(ok, mode="drop")
    bb = bb.at[sel_v].set(new_bb, mode="drop")
    return paths, sink_delay, all_reached, bb, occ_new, relax_steps


@jax.jit
def reroute_mask(dev: DeviceRRGraph, occ, paths, all_reached):
    """Nets that must reroute: any overused node on their tree, or an
    unreached sink (the reference's per-iteration rip-up predicate,
    route_timing.c should_route_net semantics)."""
    over_p1 = jnp.append(occ > dev.capacity, False)
    return over_p1[paths].any(axis=(1, 2)) | ~all_reached


@jax.jit
def overuse_summary(dev: DeviceRRGraph, occ):
    """(num overused nodes, total overuse) as device scalars."""
    over = jnp.maximum(0, occ - dev.capacity)
    return (over > 0).sum(dtype=jnp.int32), over.sum(dtype=jnp.int32)


@jax.jit
def iteration_summary(dev: DeviceRRGraph, occ, paths, all_reached,
                      steps_total):
    """Everything the host loop needs per iteration, in ONE fetch: the
    next iteration's reroute mask, reached flags, overuse summary, and
    the accumulated relax-step counter (the per-batch counters stay lazy
    device scalars — every separate device->host read costs a round
    trip)."""
    over = jnp.maximum(0, occ - dev.capacity)
    over_p1 = jnp.append(occ > dev.capacity, False)
    rrm = over_p1[paths].any(axis=(1, 2)) | ~all_reached
    return (rrm, all_reached, (over > 0).sum(dtype=jnp.int32),
            over.sum(dtype=jnp.int32), steps_total)


@functools.partial(jax.jit, static_argnames=("K",))
def conflict_subset(dev: DeviceRRGraph, occ, paths, idx_pad, K: int):
    """Conflict matrix among a padded subset of nets: C[i, j] = nets
    idx_pad[i] and idx_pad[j] share an overused node.  K bounds the number
    of overused nodes inspected (ascending node order; extras ignored —
    the coloring is a heuristic).  The MXU does the pairwise intersection.

    Replaces the host-side O(nets x path-length) dict pass of the old
    _color_schedule (the reference's overlap graph is build_overlap_graph,
    partitioning_multi_sink_delta_stepping_route.cxx:3563)."""
    N = dev.num_nodes
    I = idx_pad.shape[0]
    # the K MOST-OVERUSED nodes (not the K lowest ids): when overuse
    # exceeds K, the worst contention stays visible to the coloring
    over_amt = jnp.maximum(occ - dev.capacity, 0)
    val, ids = jax.lax.top_k(over_amt, K)
    over_ids = jnp.sort(jnp.where(val > 0, ids, N + 1))
    p = paths[jnp.clip(idx_pad, 0)].reshape(I, -1)
    pos = jnp.searchsorted(over_ids, p).astype(jnp.int32)
    posc = jnp.clip(pos, 0, K - 1)
    hit = over_ids[posc] == p
    U = jnp.zeros((I, K + 1), jnp.float32).at[
        jnp.arange(I)[:, None], jnp.where(hit, posc, K)].set(1.0)[:, :K]
    return (U @ U.T) > 0.5


@jax.jit
def wirelength_on_device(dev: DeviceRRGraph, paths):
    """Number of distinct CHANX/CHANY nodes used by any net."""
    N = dev.num_nodes
    used = jnp.zeros(N + 1, bool).at[paths.ravel()].set(True)[:N]
    return jnp.sum(used & dev.is_wire, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Bounding-box-windowed search.
#
# The reference bounds every sink search with a per-net bounding box
# (route.h:70-165, SinkRouter::expand_node pruning) so the working set is
# the box, not the device.  The dense-tensor analogue: gather each net's
# in-box nodes into a fixed [Nbox] window with a LOCALIZED in-edge table,
# and run the whole relaxation in window coordinates — [B, Nbox] state
# instead of [B, N].  Memory and per-sweep work scale with box area, which
# is what makes Titan-class graphs (N ~ 10^6-10^7) reachable at all
# (VPR's boxes exist for exactly this reason).  Search runs local; rip-up,
# commit, occupancy, and stored paths stay in global node ids.
# ---------------------------------------------------------------------------


@struct.dataclass
class WindowTables:
    """Per-net localized search windows (device arrays, built once per
    route() call; nets whose bb is later widened to the full device fall
    back to the global-space program instead)."""
    win_nodes: jnp.ndarray   # int32 [R, Nbox]  global node id (pad: N)
    lsrc: jnp.ndarray        # int32 [R, Nbox, D] local src idx (pad: Nbox)
    ldelay: jnp.ndarray      # f32   [R, Nbox, D] (pad: 0 — the sentinel
    #   src index already yields INF dist; an inf pad would make 0*inf
    #   NaN under crit=0 and poison the per-block min)
    # node spans for the A* interval distance (a length-L wire is near a
    # sink anywhere along its span, not just at xlow/ylow)
    xl: jnp.ndarray          # int16 [R, Nbox]
    xh: jnp.ndarray          # int16 [R, Nbox]
    yl: jnp.ndarray          # int16 [R, Nbox]
    yh: jnp.ndarray          # int16 [R, Nbox]

    @property
    def nbox(self) -> int:
        return self.win_nodes.shape[1]


@functools.partial(jax.jit, static_argnames=("Nbox",))
def build_windows(dev: DeviceRRGraph, bbs, Nbox: int) -> WindowTables:
    """bbs [R, 4] (xmin, xmax, ymin, ymax) -> localized window tables.

    win_nodes rows are ascending (jnp.nonzero order), so global->local
    translation is a searchsorted; an in-edge whose source lies outside
    the window maps to the sentinel Nbox (masked in the relaxation —
    exactly the reference's expand_node bb prune)."""
    N = dev.num_nodes

    def one(bb):
        inside = ((dev.xhigh >= bb[0]) & (dev.xlow <= bb[1])
                  & (dev.yhigh >= bb[2]) & (dev.ylow <= bb[3]))
        return jnp.nonzero(inside, size=Nbox, fill_value=N)[0]

    win = jax.vmap(one)(bbs).astype(jnp.int32)          # [R, Nbox]
    wn_c = jnp.clip(win, 0, N - 1)
    valid_node = win < N

    gsrc = dev.ell_src[wn_c]                            # [R, Nbox, D]
    gvalid = dev.ell_valid[wn_c] & valid_node[:, :, None]
    pos = jax.vmap(jnp.searchsorted)(
        win, gsrc.reshape(win.shape[0], -1)).reshape(gsrc.shape)
    pos = jnp.clip(pos, 0, Nbox - 1).astype(jnp.int32)
    hit = jnp.take_along_axis(
        win[:, :, None], pos, axis=1) == gsrc
    lsrc = jnp.where(gvalid & hit, pos, Nbox)
    ldelay = jnp.where(lsrc < Nbox, dev.ell_delay[wn_c], 0.0)
    return WindowTables(
        win_nodes=win, lsrc=lsrc, ldelay=ldelay,
        xl=dev.xlow[wn_c].astype(jnp.int16),
        xh=dev.xhigh[wn_c].astype(jnp.int16),
        yl=dev.ylow[wn_c].astype(jnp.int16),
        yh=dev.yhigh[wn_c].astype(jnp.int16))


@jax.jit
def window_sizes(dev: DeviceRRGraph, bbs):
    """Per-net in-box node count [R] (to size Nbox on the host)."""
    def one(bb):
        inside = ((dev.xhigh >= bb[0]) & (dev.xlow <= bb[1])
                  & (dev.yhigh >= bb[2]) & (dev.ylow <= bb[3]))
        return inside.sum(dtype=jnp.int32)
    return jax.vmap(one)(bbs)


def _relax_local(lsrc, ldelay, cong_c, crit_c, lb, seed, seed_tdel,
                 sink_loc, remaining, max_steps: int):
    """Seeded Bellman-Ford in window coordinates with A*-style pruning.

    lsrc [B, Nbox, D] local in-edge table (Nbox = outside-window sentinel);
    cong_c [B, Nbox] congestion term; crit_c [B, 1]; lb [B, Nbox]
    admissible lower bound on remaining cost from each node to the nearest
    remaining sink; seed [B, Nbox] tree mask; sink_loc [B, S] local sink
    indices; remaining [B, S] sinks still wanted.

    Pruning (get_timing_driven_expected_cost semantics, route_timing.c:693
    / parallel_route/router.cxx:445-640): once some remaining sink has
    distance bound_b, a relaxation that cannot beat it (cand + lb >=
    bound) is suppressed; with admissible lb the final sink paths are
    unaffected, and the loop's no-improvement exit fires much earlier."""
    B, Nbox, D = lsrc.shape
    DB = min(8, D)
    nblocks = -(-D // DB)

    dist0 = jnp.where(seed, 0.0, INF)
    tdel0 = jnp.where(seed, seed_tdel, 0.0)
    prev0 = jnp.full((B, Nbox), -1, jnp.int32)

    sink_c = jnp.clip(sink_loc, 0, Nbox - 1)

    def step(state):
        dist, prev, tdel, _, it = state
        dist_p = jnp.concatenate(
            [dist, jnp.full((B, 1), INF, jnp.float32)], axis=1)
        tdel_p = jnp.concatenate(
            [tdel, jnp.zeros((B, 1), jnp.float32)], axis=1)

        def blk(b, carry):
            best0, bsrc0, btdel0 = carry
            d0 = jnp.minimum(b * DB, D - DB)
            s = lax.dynamic_slice(lsrc, (0, 0, d0), (B, Nbox, DB))
            w = lax.dynamic_slice(ldelay, (0, 0, d0), (B, Nbox, DB))
            sf = s.reshape(B, -1)
            ds = jnp.take_along_axis(dist_p, sf, axis=1).reshape(s.shape)
            cand3 = ds + crit_c[:, :, None] * w + cong_c[:, :, None]
            bbest = jnp.min(cand3, axis=2)
            slot = jnp.argmin(cand3, axis=2)
            bsrc = jnp.take_along_axis(s, slot[:, :, None], axis=2)[:, :, 0]
            w_pick = jnp.take_along_axis(w, slot[:, :, None],
                                         axis=2)[:, :, 0]
            btdel = jnp.take_along_axis(
                tdel_p, bsrc, axis=1) + w_pick
            better = bbest < best0
            return (jnp.where(better, bbest, best0),
                    jnp.where(better, bsrc, bsrc0),
                    jnp.where(better, btdel, btdel0))

        best, bsrc, btdel = lax.fori_loop(
            0, nblocks, blk,
            (jnp.full((B, Nbox), INF, jnp.float32),
             jnp.full((B, Nbox), -1, jnp.int32),
             jnp.zeros((B, Nbox), jnp.float32)))

        # A* gate: the best distance any remaining sink has so far
        sd = jnp.take_along_axis(dist, sink_c, axis=1)
        bound = jnp.min(jnp.where(remaining, sd, INF), axis=1)  # [B]
        gate = best + lb < bound[:, None]

        improved = (best < dist) & gate
        dist2 = jnp.where(improved, best, dist)
        prev2 = jnp.where(improved, bsrc, prev)
        tdel2 = jnp.where(improved, btdel, tdel)
        return dist2, prev2, tdel2, jnp.any(improved), it + 1

    def cond(state):
        return state[3] & (state[4] < max_steps)

    dist, prev, tdel, _, steps = lax.while_loop(
        cond, step, (dist0, prev0, tdel0, jnp.bool_(True), jnp.int32(0)))
    return dist, prev, tdel, steps


@functools.partial(
    jax.jit,
    static_argnames=("max_steps", "max_len", "num_waves", "group", "mesh"),
    donate_argnames=("occ", "paths", "sink_delay", "all_reached"))
def route_batch_resident_win(dev: DeviceRRGraph, win: WindowTables,
                             occ, acc, pres_fac,
                             paths, sink_delay, all_reached,
                             source_all, sinks_all, crit_all,
                             sel, sel_win, valid, lb_scale,
                             max_steps: int, max_len: int, num_waves: int,
                             group: int, mesh=None):
    """Windowed variant of route_batch_resident: same fused
    rip-up/route/commit/scatter contract, but the search runs in [B, Nbox]
    window coordinates from WindowTables.  The tables hold only the
    windowABLE nets (born-wide device-spanning nets are excluded to keep
    the tables small), so each batch carries two index vectors: sel =
    net ids into the resident whole-circuit arrays, sel_win = rows into
    the compacted window tables.  lb_scale [4] = (min_cong*astar_fac,
    min_delay*astar_fac, astar_fac, ipin+sink delay tail) for the A*
    gate — flat per-tile floors in slots 0/1, slot 2 applied device-side
    to the per-cost-index delay bound, built by Router._lb_scale.  Nets
    on full-device boxes go through route_batch_resident instead.

    Returns (paths, sink_delay, all_reached, occ, relax_steps)."""
    N = dev.num_nodes
    R = paths.shape[0]
    B = sel.shape[0]
    Nbox = win.nbox
    S = sinks_all.shape[1]

    b_paths = paths[sel]                                  # [B, S, L] global
    b_src = source_all[sel]
    b_sinks = sinks_all[sel]
    b_crit = crit_all[sel]
    wn = win.win_nodes[sel_win]                           # [B, Nbox]
    lsrc = win.lsrc[sel_win]
    ldelay = win.ldelay[sel_win]
    xl = win.xl[sel_win].astype(jnp.int32)
    xh = win.xh[sel_win].astype(jnp.int32)
    yl = win.yl[sel_win].astype(jnp.int32)
    yh = win.yh[sel_win].astype(jnp.int32)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def c(x, *spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        b_paths = c(b_paths, "net", None, None)
        b_src = c(b_src, "net")
        b_sinks = c(b_sinks, "net", None)
        b_crit = c(b_crit, "net", None)
        wn = c(wn, "net", None)
        lsrc = c(lsrc, "net", None, None)
        ldelay = c(ldelay, "net", None, None)
        xl = c(xl, "net", None)
        xh = c(xh, "net", None)
        yl = c(yl, "net", None)
        yh = c(yh, "net", None)

    arangeB = jnp.arange(B)

    # --- rip up in global space (identical to route_batch_resident) ---
    nodes_p1 = jnp.zeros(N + 1, dtype=jnp.float32)
    old_usage = usage_from_paths(b_paths, nodes_p1) & valid[:, None]
    occ_rip = occ - jnp.sum(old_usage, axis=0, dtype=jnp.int32)
    occ_view = occ[None, :] - old_usage.astype(jnp.int32)

    # --- localize: congestion cost + terminals in window coordinates ---
    wn_c = jnp.clip(wn, 0, N - 1)
    node_ok = wn < N
    occ_l = jnp.take_along_axis(occ_view, wn_c, axis=1)
    cong_l = congestion_cost_arrays(dev.cong_base[wn_c], dev.capacity[wn_c],
                                    occ_l, acc[wn_c], pres_fac)
    # deterministic per-(net, global-node) jitter (same hash as the
    # global-space program so both negotiate identically)
    h = (sel.astype(jnp.int32)[:, None] * jnp.int32(2654435761 & 0x7FFFFFFF)
         + wn_c * jnp.int32(40503))
    jitter = 1.0 + JITTER_EPS * ((h & 0xFFFF).astype(jnp.float32) / 65536.0)
    cong_l = jnp.where(node_ok, cong_l, INF)

    def to_local(gids):
        """Global node ids [B, K] -> local window indices (Nbox if absent)."""
        p = jax.vmap(jnp.searchsorted)(wn, gids)
        p = jnp.clip(p, 0, Nbox - 1).astype(jnp.int32)
        ok = jnp.take_along_axis(wn, p, axis=1) == gids
        return jnp.where(ok, p, Nbox), ok

    src_loc, _ = to_local(b_src[:, None])
    sink_loc, sink_in = to_local(jnp.clip(b_sinks, 0))
    sink_loc = jnp.where(b_sinks >= 0, sink_loc, Nbox)

    # localized per-node lookahead params (loop-invariant gathers;
    # route_timing.c:693-760 expected-cost semantics via lookahead.py)
    la_ax = dev.la_axis[wn_c]                             # [B, Nbox]
    la_ls = dev.la_len_same[wn_c]
    la_lo = dev.la_len_ortho[wn_c]
    la_ts = dev.la_tlin_same[wn_c]
    la_to = dev.la_tlin_ortho[wn_c]

    # --- incremental multi-sink wave loop in window coordinates ---
    seed0 = (jnp.zeros((B, Nbox + 1), bool)
             .at[arangeB[:, None], src_loc].set(True))[:, :Nbox]

    def wave_body(state):
        (seed, tdel_tree, remaining, lpaths, delay, reached_all,
         relax_steps, wave) = state
        crit_w = jnp.max(jnp.where(remaining, b_crit, 0.0), axis=1)
        cong_c = (1.0 - crit_w)[:, None] * cong_l * jitter
        # A* lower bound: manhattan tiles from the node's SPAN to the
        # nearest remaining sink (interval distance — a length-L wire is
        # adjacent to the sink anywhere along its span, so point distance
        # from xlow/ylow would be inadmissible)
        sc = jnp.clip(sink_loc, 0, Nbox - 1)
        sx = jnp.take_along_axis(xl, sc, axis=1)
        sy = jnp.take_along_axis(yl, sc, axis=1)
        # per sink-chunk so the [B, Nbox, chunk] transient stays O(B*Nbox)
        # instead of a multi-GB [B, Nbox, S] blow-up at Titan-class Nbox.
        # lb = min over remaining sinks of the node's expected remaining
        # cost: flat per-tile congestion floor + per-cost-index same/
        # ortho segment-count DELAY bound (lookahead.py; non-wire nodes
        # fall back to the flat delay floor).  lb_scale [4] =
        # (min_cong*af, min_delay*af, af, ipin+sink delay tail)
        S_all = sink_loc.shape[1]
        CH = min(8, S_all)
        cwc = crit_w[:, None, None]
        lb = jnp.full((B, Nbox), INF, jnp.float32)
        for s0 in range(0, S_all, CH):
            sxc = sx[:, s0:s0 + CH]
            syc = sy[:, s0:s0 + CH]
            remc = remaining[:, s0:s0 + CH]
            dx = jnp.maximum(jnp.maximum(
                xl[:, :, None] - sxc[:, None, :],
                sxc[:, None, :] - xh[:, :, None]), 0)
            dy = jnp.maximum(jnp.maximum(
                yl[:, :, None] - syc[:, None, :],
                syc[:, None, :] - yh[:, :, None]), 0)
            man = (dx + dy).astype(jnp.float32)
            dsame = jnp.where(la_ax[:, :, None] == 0, dx, dy)
            dortho = jnp.where(la_ax[:, :, None] == 0, dy, dx)
            nsame = ((dsame + la_ls[:, :, None] - 1)
                     // la_ls[:, :, None]).astype(jnp.float32)
            northo = ((dortho + la_lo[:, :, None] - 1)
                      // la_lo[:, :, None]).astype(jnp.float32)
            lbd = (nsame * la_ts[:, :, None] + northo * la_to[:, :, None]
                   + lb_scale[3]) * lb_scale[2]
            lbd = jnp.where(la_ax[:, :, None] == 2,
                            man * lb_scale[1], lbd)
            cost = (1.0 - cwc) * man * lb_scale[0] + cwc * lbd
            lb = jnp.minimum(lb, jnp.min(
                jnp.where(remc[:, None, :], cost, INF), axis=2))
        dist, prev, tdel, steps = _relax_local(
            lsrc, ldelay, cong_c, crit_w[:, None], lb, seed, tdel_tree,
            sink_loc, remaining, max_steps)
        relax_steps = relax_steps + steps

        sd = jnp.take_along_axis(
            jnp.concatenate([dist, jnp.full((B, 1), INF)], axis=1),
            sink_loc, axis=1)
        score = jnp.where(remaining & jnp.isfinite(sd),
                          sd - b_crit * 1e3, INF)
        order = jnp.argsort(score, axis=1)[:, :group]
        pick_valid = (jnp.take_along_axis(remaining, order, axis=1)
                      & jnp.isfinite(jnp.take_along_axis(score, order,
                                                         axis=1)))
        pick_sink = jnp.where(
            pick_valid, jnp.take_along_axis(sink_loc, order, axis=1), -1)

        seg, seg_reached = _traceback(prev, seed, pick_sink, max_len)
        ok = pick_valid & seg_reached

        old = jnp.take_along_axis(lpaths, order[:, :, None], axis=1)
        lpaths = _scatter_rows(lpaths, order,
                               jnp.where(ok[:, :, None], seg, old))
        d_new = jnp.take_along_axis(
            jnp.concatenate([tdel, jnp.zeros((B, 1))], axis=1),
            jnp.clip(pick_sink, 0), axis=1)
        old_d = jnp.take_along_axis(delay, order, axis=1)
        delay = _scatter_vals(delay, order, jnp.where(ok, d_new, old_d))
        old_r = jnp.take_along_axis(reached_all, order, axis=1)
        reached_all = _scatter_vals(reached_all, order, ok | old_r)
        old_rem = jnp.take_along_axis(remaining, order, axis=1)
        remaining = _scatter_vals(remaining, order, old_rem & ~ok)

        flat = jnp.where(ok[:, :, None], seg, Nbox).reshape(B, -1)
        newly = jnp.zeros((B, Nbox + 1), bool).at[
            arangeB[:, None], flat].set(True)
        tdel_tree = jnp.where(newly[:, :Nbox], tdel, tdel_tree)
        seed = seed | newly[:, :Nbox]
        return (seed, tdel_tree, remaining, lpaths, delay, reached_all,
                relax_steps, wave + 1)

    def wave_cond(state):
        return jnp.any(state[2]) & (state[7] < num_waves)

    # sinks that are outside their own window can never be reached: drop
    # them from `remaining` so the wave loop doesn't spin on them (the
    # Router widens the net's bb and retries via the fallback program)
    remaining0 = (b_sinks >= 0) & sink_in
    state0 = (seed0, jnp.zeros((B, Nbox), jnp.float32), remaining0,
              jnp.full((B, S, max_len), Nbox, jnp.int32),
              jnp.full((B, S), INF, jnp.float32),
              jnp.zeros((B, S), bool), jnp.int32(0), jnp.int32(0))
    (seed, _, _, lpaths, delay, reached_all, relax_steps,
     _) = lax.while_loop(wave_cond, wave_body, state0)

    # --- back to global ids ---
    wn_p1 = jnp.concatenate(
        [wn, jnp.full((B, 1), N, jnp.int32)], axis=1)     # local pad -> N
    p = jnp.take_along_axis(
        wn_p1, lpaths.reshape(B, -1), axis=1).reshape(lpaths.shape)
    usage = (jnp.zeros((B, N + 1), bool)
             .at[arangeB[:, None], jnp.where(seed, wn, N).reshape(B, -1)]
             .set(True))[:, :N]
    usage = usage & valid[:, None]
    occ_new = occ_rip + jnp.sum(usage, axis=0, dtype=jnp.int32)

    smask = b_sinks >= 0
    ok = (reached_all | ~smask).all(axis=1)

    sel_v = jnp.where(valid, sel, R).astype(jnp.int32)
    paths = paths.at[sel_v].set(p, mode="drop")
    sink_delay = sink_delay.at[sel_v].set(delay, mode="drop")
    all_reached = all_reached.at[sel_v].set(ok, mode="drop")
    return paths, sink_delay, all_reached, occ_new, relax_steps
