"""Negotiated-congestion (PathFinder) routing driver.

TPU-native replacement for the reference's whole router family
(vpr/SRC/route/route_timing.c:85 try_timing_driven_route serial baseline and
the parallel_route/ drivers, flagship
partitioning_multi_sink_delta_stepping_route.cxx:5937-6330): the PathFinder
outer loop runs on the host, but every net in a *batch* is ripped up and
re-routed by one fixed-shape jitted device program (search.route_net_batch)
against a congestion snapshot, then the batch's occupancy is committed at
once.

Where the reference serialises congestion access (coloring schedules,
per-node spin locks, det_mutex logical clocks), the TPU design:
  - costs every net against the occupancy of everyone *but itself*
    (serial rip-up-one-net semantics, so batch peers' previous paths are
    visible),
  - schedules nets that fought over a node last iteration into different
    commit groups (the reference's coloring schedule,
    custom_vertex_coloring …cxx:3323),
  - breaks exact cost ties between bus-twin nets with a deterministic
    per-net jitter,
and relies on PathFinder present/history costs for the rest.  Determinism
is free: batch order and all reductions are fixed.  The batch size is the
analogue of --num_threads.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import os
import time
from dataclasses import asdict, dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_devprof, get_metrics, get_tracer, span
from ..obs.trace import compile_phases, enable_compile_capture
from ..rr.graph import RRGraph
from ..rr.terminals import NetTerminals, class_rows
from .device_graph import DeviceRRGraph, to_device
from .search import (build_windows, conflict_subset, iteration_summary,
                     route_batch_resident, route_batch_resident_win,
                     window_sizes, wirelength_on_device)

def normalize_crop(value) -> str:
    """Validate + normalize a crop knob ('auto' | 'off' | 'WxH').
    Shared by the CLI and Router.route so a typo'd programmatic value
    raises instead of silently degrading to full-canvas sweeps."""
    s = str(value).strip().lower()
    if s in ("auto", "off"):
        return s
    parts = s.split("x")
    try:
        if len(parts) == 2 and int(parts[0]) > 0 and int(parts[1]) > 0:
            return s
    except ValueError:
        pass
    raise ValueError(
        f"crop must be 'auto', 'off', or 'WxH' (got {value!r})")


@dataclass
class RouterOpts:
    """Knobs mirroring s_router_opts (vpr/SRC/base/vpr_types.h:708-770) with
    SetupVPR.c defaults: initial_pres_fac=0.5:401, pres_fac_mult=1.3:363,
    acc_fac=1, max_router_iterations=50:355, bb_factor=3:337."""
    max_router_iterations: int = 50
    initial_pres_fac: float = 0.5
    pres_fac_mult: float = 1.3
    acc_fac: float = 1.0
    bb_factor: int = 3
    batch_size: int = 64          # nets routed concurrently (≈ num_threads)
    # device search program: "planes" = structured scan/shift relaxation
    # over [B, W, X, Y] wire grids (route/planes.py — no gathers in the
    # sweep loop, the round-3 work-efficiency kernel); "ell" = the
    # gather-based pull Bellman-Ford over the ELL edge table
    # (route/search.py; any-graph fallback + cross-validation oracle).
    # Any other value is refused at Router construction
    program: str = "planes"
    # sinks per wave: 1 = exact VPR incremental tree reuse
    # (route_tree_timing.c); 0 = ALL sinks in one wave — every sink is
    # routed independently from the same relaxation and the deterministic
    # greedy-descent tracebacks merge into one tree (the reference's
    # sink-parallel virtual-net decomposition, MultiSinkParallelRouter
    # partitioning_multi_sink_delta_stepping_route.cxx:975 + merge :880,
    # taken to per-sink granularity).  0 is the planes-program default
    # path to single-wave batch steps; >1 = grouped middle ground
    sink_group: int = 0
    max_pres_fac: float = 1000.0
    # after this iteration, rip up & reroute only illegal nets
    # (reference phase-two style refinement, …cxx:6238-6267)
    incremental_after: int = 1
    # bb-windowed search (route.h:70-165 per-net boxes as gathered [Nbox]
    # windows): on unless the boxes cover most of the device anyway
    windowed: bool = True
    # windows are skipped when max box holds > this fraction of all nodes
    window_max_frac: float = 0.7
    # or when the localized tables would exceed this many bytes
    window_max_bytes: int = 4 << 30
    # A* aggressiveness: scales the admissible lower bound (VPR
    # --astar_fac, SetupVPR.c:332 default 1.2; 1.0 = provably optimal
    # per-sink paths, >1 prunes harder for speed at a QoR risk).  Only
    # the windowed search has the A* gate — this knob is inert for
    # full-device (global-program) routing
    astar_fac: float = 1.0
    # phase-two safety valve (…cxx:6238-6267 two-phase mode switch +
    # mpi plateau shrink): when the overused-node count improves < 5%
    # for this many consecutive iterations, the still-congested nets
    # get full-device bounding boxes so negotiation can detour globally
    plateau_iters: int = 8
    # per-run stats directory: writes iter_stats.txt / final_stats.txt in
    # the reference's schema (…cxx:5925-5935, 6344-6360); None = off
    stats_dir: Optional[str] = None
    # also dump every iteration's routes to routes_iter_N.txt in
    # stats_dir (…cxx:6167 diagnostics; pulls paths off-device each
    # iteration, debug only)
    dump_routes: bool = False
    # snapshot the full negotiation state every >= this many iterations
    # (at window boundaries) into result.checkpoint — the elastic
    # resume surface (RouteCheckpoint; planes program only).  0 = off
    checkpoint_every: int = 0
    # cooperative preemption (serve/ queue time-slicing): yield after
    # >= this many NEW iterations this call — checkpoint at the next
    # window boundary and return success=False + checkpoint.  Unlike
    # shrinking max_router_iterations, this leaves the iteration budget
    # (and therefore the per-window K clamp and the whole window
    # partition) untouched, so a sliced negotiation resumed to the end
    # is bit-identical to an unsliced run.  0 = off
    slice_iterations: int = 0
    # bb-cropped planes relaxation (route.h:70-165 per-net boxes as a
    # static crop tile; planes.planes_relax_cropped): "auto" crops a
    # window whenever the bucketed tile is meaningfully smaller than
    # the grid, "off" always sweeps full canvases, "WxH" (e.g. "8x8")
    # forces that tile regardless of the cost model (tuning/tests).
    # Work per net then scales with its bounding box, not the device
    crop: str = "auto"
    # Reduced first-try sweep budget (planes program): 1 = off (budget
    # = bb line-move span, the always-sufficient bound); d > 1
    # dispatches each net's first relaxation with span/d sweeps — most
    # paths need only a few direction changes, so the common case does
    # ~d times less sweep work.  A net that misses a sink under a
    # reduced budget is PROMOTED to the full budget for the next window
    # instead of taking the unreached->full-device bb widening (the
    # widen_ok gate in planes._step_core); only a full-budget miss
    # widens.  Default 3, measured at 600 LUTs/W=16 on XLA:CPU: relax
    # steps 14,560 -> 5,824 (2.5x), wall 983 -> 404 s, IDENTICAL
    # wirelength and window count (BENCHMARKS.md round-5; div=4 gave
    # 2.9x with the same parity)
    sweep_budget_div: int = 3
    # wirelength finishing pass (planes program, sink_group=0 only):
    # at the first legal window, snapshot the state on the device and
    # rip up and re-route the MULTI-SINK nets once with the exact
    # incremental sink schedule against the converged congestion
    # picture (a single-sink traceback is an exact path already).  The
    # fast doubling-schedule trees cost ~3% wirelength (measured mult8:
    # dwl 3.10% -> 0.52% under the precise schedule); the reference's
    # serial baseline always builds exact trees (route_tree_timing.c),
    # so parity needs the cleanup.  What follows it: the nets the pass
    # left fighting are re-legalised, precisely, window by window until
    # legal or out of iterations -- never a phase-2 restart, which
    # would route every net a second time (_phase2_restart_due) -- and
    # a route that does not land restores the snapshot: the pass and
    # its tail then bought nothing (RouteResult.total_relax_steps_
    # discarded, route.endgame.finish_restored_total).  NOT a cheap
    # window: one sink a wave makes it the dearest of a route, 12.97 s
    # of route_hetero's 45.7 s on the chip (PERF.md, PR 32 and 33).
    finish_precise: bool = True
    # two-stage host/device software pipeline for the planes window
    # driver: while window k executes on device, the host consumes
    # window k-1's summary (deferred bookkeeping off a packed status
    # word streamed with copy_to_host_async) and plans/stages the later
    # rungs of window k.  Bit-identical to pipeline=False by
    # construction — every dispatch is planned from the SAME fully
    # consumed summary in both modes; only the blocking points move.
    # False (the CLI's --sync) drains every rung with block_until_ready
    # before any further host work: the tracing/debugging escape hatch,
    # and the reference for the parity suite (tests/test_pipeline.py)
    pipeline: bool = True
    # per-window congestion telemetry (the observatory corpus feed,
    # obs/runstore.py): after every committed window, record the top-k
    # overused rr-node ids into result.congestion — in --sync from the
    # live occupancy before the next dispatch donates it, in pipelined
    # mode from a non-donated device snapshot whose D2H readback
    # overlaps the next window's execution.  Also the top_overused
    # source for the mdclog congestion records.  0 disables the
    # capture (mdclog records then carry an empty list)
    congestion_topk: int = 8
    # AOT program library directory (serve/library.py): dispatch
    # variants found in the library are served from deserialized
    # jax.export executables — a fresh process routes its first window
    # with ZERO compiles (route.dispatch.compiles == 0) — and unknown
    # variants fall back to the jit path and are noted for
    # Router.export_program_library().  None = off.  Single-device
    # planes programs only (exported modules bake one partitioning)
    program_library_dir: Optional[str] = None
    # Resilience runtime (resil.Resilience, duck-typed: .plan/.guard/
    # .ladder).  When set, every window dispatch runs under the
    # watchdog guard through a chain of bit-identical rungs (AOT ->
    # jit) with retry/backoff/quarantine, and fault-injection sites
    # are armed.  None = off (the default path is byte-for-byte the
    # non-resil dispatch)
    resil: Optional[object] = None
    # Distance-plane storage dtype (planes.PLANE_DTYPES), and the dtype
    # the route COMMITS: "f32" is the bit-exact oracle; "bf16" stores
    # and relaxes the distance/backtrack planes at half width (f32
    # accumulation inside every sweep — planes._run_relax), halving
    # the bytes each relaxation sweep moves.  A bf16 route is legal
    # but is a DIFFERENT result (the benchmark's negative control of
    # `correct`), not a faster f32 one.
    plane_dtype: str = "f32"
    # Vestigial: the one accepted value is "off" (anything else is a
    # ValueError at route start).  The shadow-replay guards it once
    # selected are gone; the field stays only because
    # benchmark/tools/control_runs.py and tests/benchmark/ pass the
    # key (ROADMAP.md Queue 3 names the remedy).
    dtype_guard: str = "off"
    # Multi-chip halo-exchange routing (route/planes_shard.py): shard
    # the relaxation canvases over a 1-D device mesh on the canvas row
    # axis, each chip relaxing its own column block and exchanging
    # only the boundary halo columns between sweeps.  1 = single-chip
    # (default).  N > 1 needs N visible devices (on CPU hosts set
    # XLA_FLAGS=--xla_force_host_platform_device_count=N before jax
    # initializes) and program="planes" — the legacy (net, node)
    # GSPMD mesh is mutually exclusive with it.  Rides the resil
    # ladder's "mesh" dimension
    # (pallas_halo -> ppermute -> single_chip): the overlapped
    # remote-DMA transport engages on TPU backends, ppermute is the
    # portable rung, and a lost mesh member (backend.loss) demotes to
    # the single-chip floor so the route still completes.
    mesh_shards: int = 1


@dataclass
class RouteStats:
    """Per-iteration stats (iter_stats.txt schema,
    partitioning_multi_sink…cxx:5925-5931: route time, heap
    pops/visits/pushes -> relax_steps, overuse count/%, crit path)."""
    iteration: int
    overused_nodes: int
    overuse_total: int
    rerouted_nets: int
    route_time_s: float          # perf_counter: the route.window span
    relax_steps: int = 0         # Bellman-Ford sweeps (heap-pops analogue)
    batches: int = 0             # device dispatches this iteration
    overuse_pct: float = 0.0     # overused nodes / all rr nodes
    crit_path_delay: float = float("nan")
    # the window ledger (planes window driver; the ELL rows keep the
    # defaults, kind "" = not a window of a kind)
    window: int = 0              # the window's index, 1..n over a route
    kind: str = ""               # one of WINDOW_KINDS
    precise: bool = False        # the schedule the window ran under
    sweep_boost: int = 1
    waves: int = 0               # relaxations run to a fixpoint
    relax_steps_cropped: int = 0  # of relax_steps, on cropped rungs
    waves_cropped: int = 0       # of waves: cropped relaxation CALLS
    # canvas cells the sweeps covered: relax_steps x the rung's batch
    # width x the cells of the canvas it ran on (full, or its tile)
    cell_sweeps: int = 0
    net_routes: int = 0          # nets routed, once an iteration each
    stall_s: float = 0.0         # host blocked on the device
    plan_s: float = 0.0          # host planning, staging, dispatching
    dispatch_ms: float = 0.0     # of it, in route.pipeline.dispatch
    control_s: float = 0.0       # the host's control step AFTER it
    kept: bool = True            # False: computed and thrown away
    # the window's relaxations ran with the scans' guard against a
    # predecessor 2-cycle (PlanesGraph.scan_guard): on from the window
    # after one that ended with nothing over capacity, a sink unreached
    # and no snapshot to return
    scan_guard: bool = False
    # fanout classes: the class of the widest net of the window's
    # batches (0: the first class, the only one of most circuits), and
    # of waves / relax_steps what batches of a class above it spent
    fanout_class: int = 0
    waves_wide: int = 0
    relax_steps_wide: int = 0
    # distance elements the waves' sink picks read, and what dense
    # picks read in the same waves (RouteResult.total_sink_reads*)
    sink_reads: int = 0
    sink_reads_dense: int = 0


@dataclass
class RouteCheckpoint:
    """Host snapshot of the COMPLETE negotiation state at a window
    boundary — the checkpoint/resume + elastic-recovery surface (SURVEY
    §5.3/§5.4).  The reference's closest mechanism is the MPI router's
    communicator halving (mpi_route_load_balanced_nonblocking_send_recv_
    encoded.cxx:1560-1680), which re-partitions live route state onto
    fewer ranks when progress stalls; here the state is fetched once and
    can be re-uploaded under ANY mesh layout — resume the same
    negotiation on a smaller mesh (device loss), a bigger one, or a
    single chip, deterministically."""
    occ: np.ndarray
    acc: np.ndarray
    # paths / sink_delay / crit: the device's tables as they stand, so
    # one array of a route of one fanout class and a tuple of one array
    # a class ([R_c, S_c, ...]) of a route of several
    paths: np.ndarray
    sink_delay: np.ndarray
    all_reached: np.ndarray
    bb: np.ndarray
    crit: np.ndarray
    it_done: int
    pres: float
    driver: dict                  # host scheduling state (widx, wide, ...)
    # pre-finish legal snapshot (occ, paths, sink_delay, all_reached,
    # bb, it_done), present iff the wirelength finishing pass was live
    # when the checkpoint was taken: a resumed run restores it so a
    # negotiation that already produced a legal route can never end as
    # a reported failure, exactly like the un-resumed driver
    fin_save: Optional[tuple] = None


@dataclass
class RouteResult:
    success: bool
    iterations: int
    # [R, Smax, Lmax] int32, sentinel N = pad; of a route of several
    # fanout classes a ClassedPaths, which answers paths[r][s],
    # paths[r, s], .shape and np.asarray() from the stores a class
    paths: np.ndarray
    sink_delay: np.ndarray       # [R, Smax] f32
    occ: np.ndarray              # [N] int32 final occupancy
    wirelength: int
    stats: List[RouteStats] = field(default_factory=list)
    # search effort counters (perf_t analogue, route.h:12-20)
    total_net_routes: int = 0
    total_relax_steps: int = 0
    # work-efficiency ledger: of the executed sweeps, how many improved
    # some distance (useful) vs ran as fixpoint-discovery / ceiling
    # overhead (wasted).  useful + wasted == total_relax_steps.
    total_relax_steps_useful: int = 0
    total_relax_steps_wasted: int = 0
    # of which: sweeps over bb-CROPPED canvases (tile area, not grid
    # area — the two cost very different device time; bench projections
    # need the split)
    total_relax_steps_cropped: int = 0
    # the waves of cropped rungs: the calls of the cropped relaxation,
    # what its cut and write-back are paid by (tools/crop_forms.py
    # times one)
    total_waves_cropped: int = 0
    # canvas cells the sweeps covered, dead batch slots included: each
    # rung's sweeps x its plan width x the cells of its canvas (the
    # whole grid's, or its crop tile's).  Over total_net_routes it is
    # what a net's route costs in relaxing work, the number a serial
    # router's heap pops a net stand against
    total_cell_sweeps: int = 0
    # of which: sweeps of the windows whose result was thrown away, the
    # stats rows past the iteration of the pre-finish snapshot when the
    # finishing pass could not re-legalise and the snapshot was restored
    # (0 when the finished route is kept)
    total_relax_steps_discarded: int = 0
    # of which: sweeps of the batches of a fanout class above the first
    total_relax_steps_wide: int = 0
    # traceback ledger (windowed planes program): pointer-chase steps
    # the walks ran, and the steps budgeted (max_len - 4 per executed
    # wave).  A share near 1 means paths are pressing on the budget.
    total_walk_steps: int = 0
    total_walk_budget: int = 0
    # of the budgeted slots, those the waves' two element scatters
    # (tree grow, path assembly) read: the steps of each wave's longest
    # KEPT walk rounded up to whole chunks (planes.walk_scatters); a
    # program that scatters every slot (a GSPMD mesh's) reads the budget
    total_walk_slots_read: int = 0
    # sink-pick ledger (windowed planes program): distance elements the
    # waves' picks read (the live rung's M sink rows, or all B * S on
    # the dense rung, x cells_per_sink), and what a dense pick reads in
    # the same waves.  Their ratio is how much of the read the live
    # list saves.
    total_sink_reads: int = 0
    total_sink_reads_dense: int = 0
    # executed waves of the windowed planes program (one relaxation to
    # a fixpoint each): total_relax_steps over it is sweeps a wave
    total_waves: int = 0
    # the route's wall by named interval (planes window driver), in
    # perf_counter seconds: prologue_s (route()'s entry to the first
    # window), windows_s (the sum of the rows' route_time_s), control_s
    # (of their control_s), epilogue_s (the last control step's end to
    # the return).  They add up to the `route` stage.  route_id is the
    # `route` arg of the route's spans (0: the ELL program).
    wall: dict = field(default_factory=dict)
    route_id: int = 0
    # nets whose bb was widened to the full device (left the windowed
    # program; 0 on a healthy windowed run of a routable circuit)
    widened_nets: int = 0
    # nets the windowed program handled at the start (0 = windows off)
    windowed_nets: int = 0
    # latest window-boundary state snapshot (opts.checkpoint_every > 0)
    checkpoint: Optional["RouteCheckpoint"] = None
    # per-window congestion records (opts.congestion_topk > 0, planes
    # program): [{window, iteration, overused_nodes, overuse_total,
    # pres_fac, top_overused: [[node, overuse], ...]}, ...] — the
    # spatial telemetry obs/runstore.py rasterizes into the corpus
    # heatmaps.  Captured in BOTH pipelined and --sync modes.
    congestion: List[dict] = field(default_factory=list)


def _color_schedule(idx: np.ndarray, conflict: np.ndarray):
    """Greedy-color the net conflict graph (nets sharing an overused node;
    conflict [I, I] bool from search.conflict_subset); each color class
    becomes its own commit group, serialising exactly the nets that are
    fighting while keeping independent nets concurrent (the reference's
    coloring schedule, custom_vertex_coloring …cxx:3323)."""
    n = len(idx)
    color = np.zeros(n, dtype=np.int64)
    for i in range(1, n):
        taken = np.unique(color[:i][conflict[i, :i]])
        c = 0
        for t in taken:          # taken is sorted: first gap wins
            if t != c:
                break
            c += 1
        color[i] = c
    ncolors = int(color.max()) + 1
    if ncolors == 1:
        return [idx]
    return [idx[color == c] for c in range(ncolors)]


def write_stats_files(stats_dir: str, result: "RouteResult") -> None:
    """Emit iter_stats.txt / final_stats.txt in the reference's schema
    (partitioning_multi_sink_delta_stepping_route.cxx:5925-5935 header +
    :6307-6318 rows; :6344-6360 final) so runs can be diffed against the
    reference's own output files (BASELINE.md comparison surface)."""
    import os

    os.makedirs(stats_dir, exist_ok=True)
    with open(os.path.join(stats_dir, "iter_stats.txt"), "w") as f:
        f.write("iteration route_time relax_steps batches rerouted_nets "
                "overused_nodes overuse_total overuse_pct crit_path_delay\n")
        for s in result.stats:
            f.write(f"{s.iteration} {s.route_time_s:.6f} {s.relax_steps} "
                    f"{s.batches} {s.rerouted_nets} {s.overused_nodes} "
                    f"{s.overuse_total} {s.overuse_pct:.4f} "
                    f"{s.crit_path_delay:.6e}\n")
    with open(os.path.join(stats_dir, "final_stats.txt"), "w") as f:
        f.write(f"routed {int(result.success)}\n")
        f.write(f"num_iterations {result.iterations}\n")
        f.write(f"total_route_time "
                f"{sum(s.route_time_s for s in result.stats):.6f}\n")
        f.write(f"total_relax_steps {result.total_relax_steps}\n")
        f.write(f"total_relax_steps_useful "
                f"{result.total_relax_steps_useful}\n")
        f.write(f"total_relax_steps_wasted "
                f"{result.total_relax_steps_wasted}\n")
        f.write(f"total_net_routes {result.total_net_routes}\n")
        f.write(f"wirelength {result.wirelength}\n")
        # the converged iteration breaks out before its timing callback,
        # so report the last stamped crit-path value
        cpd = float("nan")
        for s in reversed(result.stats):
            if s.crit_path_delay == s.crit_path_delay:
                cpd = s.crit_path_delay
                break
        f.write(f"final_crit_path_delay {cpd:.6e}\n")
    from .report import format_window_table
    with open(os.path.join(stats_dir, "window_table.txt"), "w") as f:
        f.write(format_window_table(result) + "\n")


def _median_cut_bins(pts_x: np.ndarray, pts_y: np.ndarray,
                     depth: int = 4) -> np.ndarray:
    """Recursive median cuts over net centers (new_partitioner.cxx /
    split_nets_recursive semantics): alternate x/y cuts at the median,
    so every leaf holds ~the same NUMBER of nets regardless of placement
    density — a fixed grid starves bins on clustered placements.
    Returns a leaf id per point; deterministic (stable half-splits on
    degenerate medians)."""
    n = len(pts_x)
    bins = np.zeros(n, dtype=np.int64)

    def cut(sel: np.ndarray, d: int, vert: bool) -> None:
        if d == 0 or sel.size <= 1:
            return
        vals = pts_x[sel] if vert else pts_y[sel]
        left = vals <= np.median(vals)
        if left.all() or not left.any():
            order = np.argsort(vals, kind="stable")
            left = np.zeros(sel.size, dtype=bool)
            left[order[: sel.size // 2]] = True
        bins[sel[~left]] += 1 << (d - 1)
        cut(sel[left], d - 1, not vert)
        cut(sel[~left], d - 1, not vert)

    cut(np.arange(n), depth, True)
    return bins


def _spatial_order(idx: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                   depth: int = 4) -> np.ndarray:
    """Order nets so consecutive ones come from DIFFERENT regions of the
    device: median-cut-partition net centers into 2^depth balanced
    leaves and deal round-robin across them.  Consecutive nets become
    one batch, so batch peers are spatially spread — less overlap, fewer
    congestion conflicts per commit (the net-axis load-balancing role of
    the reference's spatial net partitioning, split_nets_recursive
    partitioning_multi_sink_delta_stepping_route.cxx:2648 +
    new_partitioner.cxx median cuts, re-aimed at batches instead of
    threads)."""
    if len(idx) <= 1:
        return idx
    bins = _median_cut_bins(cx[idx], cy[idx], depth)
    # stable sort by bin, then deal one net per bin per round
    order = np.argsort(bins, kind="stable")
    sorted_bins = bins[order]
    # position of each net within its bin
    _, starts = np.unique(sorted_bins, return_index=True)
    within = np.arange(len(order)) - starts[
        np.searchsorted(sorted_bins[starts], sorted_bins)]
    deal = np.lexsort((sorted_bins, within))
    return idx[order[deal]]


def _order_and_chunk(g, nsinks, cx, cy, B):
    """Shared batch formation: fanout classes (similar wave depth),
    spatial round-robin within a class, chunked to B (used by both the
    window planner and the ELL per-iteration loop)."""
    if len(g) == 0:
        return []
    cls = np.ceil(np.log2(np.maximum(
        1, nsinks[g]).astype(float))).astype(np.int64)
    ordered = np.concatenate([
        _spatial_order(g[cls == c], cx, cy)
        for c in sorted(set(cls.tolist()), reverse=True)])
    return [ordered[lo:lo + B] for lo in range(0, len(ordered), B)]


def _pad_to(a: np.ndarray, B: int, fill) -> np.ndarray:
    n = a.shape[0]
    if n == B:
        return a
    pad = np.full((B - n,) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _crop_ladder(nx: int, ny: int, base: int = 8,
                 full_frac: float = 0.8):
    """The crop tiles of an nx x ny grid, ascending: base, 2*base,
    4*base, ... clamped to the grid, stopping at the first rung whose
    tile covers the grid or whose area reaches ``full_frac`` of the
    grid area (a crop that big saves nothing over the full canvas, and
    the full-canvas program is the one the mesh path shards).  A fixed
    function of the grid: 11x11 has the one rung (8, 8), and a 16x16
    rung exists from 18x18 up."""
    ladder = []
    s = base
    while True:
        cw, ch = min(nx, s), min(ny, s)
        if cw * ch >= full_frac * nx * ny or (cw == nx and ch == ny):
            return ladder
        ladder.append((cw, ch))
        s *= 2


def _size_class_buckets(need_w: np.ndarray, need_h: np.ndarray,
                        nx: int, ny: int, min_count: int = 1,
                        base: int = 8, full_frac: float = 0.8):
    """Bin nets into pow-2 size-class crop buckets.

    ``need_w``/``need_h`` are the per-net canvas requirements (live bb
    span + crop margin, in grid cells).  Each net gets the SMALLEST
    rung of ``_crop_ladder`` that fits both of its spans; nets that fit
    no rung take the full canvas.  Rungs holding fewer than
    ``min_count`` nets are merged upward (a near-empty bucket costs a
    whole program launch for a handful of nets).

    Returns (classes, assign): ``classes`` is a list of (cw, ch) crop
    tiles, ascending; ``assign[i] == len(classes)`` means net i routes
    on the full canvas.  Deterministic — pure function of the spans and
    the grid."""
    n = len(need_w)
    ladder = _crop_ladder(nx, ny, base, full_frac)
    assign = np.full(n, len(ladder), dtype=np.int64)
    for k in range(len(ladder) - 1, -1, -1):
        cw, ch = ladder[k]
        assign[(need_w <= cw) & (need_h <= ch)] = k
    # merge under-populated rungs upward (into the next rung, or the
    # full-canvas class off the top of the ladder)
    for k in range(len(ladder)):
        cnt = int((assign == k).sum())
        if 0 < cnt < min_count:
            assign[assign == k] = k + 1
    # compact the populated rungs to dense ids, full class last
    used = [k for k in range(len(ladder)) if (assign == k).any()]
    lut = np.full(len(ladder) + 1, len(used), dtype=np.int64)
    for j, k in enumerate(used):
        lut[k] = j
    return [ladder[k] for k in used], lut[assign]


def path_budget(span: int, cap: int) -> int:
    """Path-slot budget for a bb half-perimeter `span`: ~2x the span plus
    winding slack, bucketed to 64 to bound compile variants, capped at
    the device budget.  THE single definition — the allocator, both
    regrowth sites, and scale_bench's memory model all use it."""
    return min(cap, ((2 * span + 64 + 63) // 64) * 64)


def _grow_paths(paths, L_new: int, N: int):
    return jax.tree.map(
        lambda p: jnp.pad(p, ((0, 0), (0, 0), (0, L_new - p.shape[2])),
                          constant_values=N), paths)


def _by_class(dense: np.ndarray, classes):
    """A host table dense in the sink axis [R, Smax, ...] as the device
    holds it: the array itself where the route has one fanout class,
    else a tuple of one [R_c, S_c, ...] array a class."""
    if len(classes) == 1:
        return jnp.asarray(dense)
    return tuple(jnp.asarray(dense[c.nets, :c.width]) for c in classes)


def _dense(tables, classes, fill) -> np.ndarray:
    """_by_class's inverse on the host: [R, Smax, ...] with ``fill`` in
    the slots a class's table does not have."""
    if not isinstance(tables, tuple):
        return np.asarray(tables)
    parts = [np.asarray(t) for t in tables]
    R = sum(len(c.nets) for c in classes)
    out = np.full((R, classes[-1].width) + parts[0].shape[2:], fill,
                  dtype=parts[0].dtype)
    for c, t in zip(classes, parts):
        out[c.nets, :c.width] = t
    return out


class ClassedPaths:
    """The host's view of a routed path store kept a fanout class:
    what ``RouteResult.paths`` is for a route of several classes.  It
    answers what the oracles ask of the dense [R, Smax, L] array --
    ``paths[r]`` ([S_c, L]: every sink slot the net has), ``paths[r,
    s]``, ``.shape``, ``len()`` -- from the stores as they came off the
    device, and ``np.asarray(paths)`` builds the dense array (pad N)
    for whoever needs all of it at once."""

    def __init__(self, parts, classes, pad: int):
        self.parts = [np.asarray(p) for p in parts]
        self.classes = classes
        self.pad = int(pad)
        self._cls, self._row = class_rows(classes)
        self.shape = (len(self._cls), classes[-1].width,
                      self.parts[0].shape[2])
        self.dtype = self.parts[0].dtype

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, key):
        r, rest = (key[0], key[1:]) if isinstance(key, tuple) \
            else (key, ())
        if not isinstance(r, (int, np.integer)):
            return np.asarray(self)[key]
        net = self.parts[self._cls[r]][self._row[r]]
        return net[rest] if rest else net

    def __array__(self, dtype=None, copy=None):
        out = _dense(tuple(self.parts), self.classes, self.pad)
        return out if dtype is None else out.astype(dtype)


def _phase2_restart_due(precise: bool, full_reroute_done: bool,
                        finish_done: bool, n_over: int, widx: int) -> bool:
    """When the planes window driver rips up and re-routes EVERY net
    precisely (the phase-2 restart, once a route): a STALLED endgame --
    overuse left under the precise schedule, from the fifth window on --
    that has not seen the wirelength finishing pass.  The pass sets
    ``precise`` too, but it has just rebuilt the multi-sink trees
    precisely against the converged congestion: what is over after it
    is its own transient, and the nets ``_mis_colors`` marks re-legalise
    it; a restart there routes every net a second time."""
    return (precise and not full_reroute_done and not finish_done
            and n_over > 0 and widx >= 4)


def _scan_guard_due(n_over: int, unreached: bool, has_snapshot: bool,
                    max_span: int) -> bool:
    """When the planes window driver switches the scans' guard on
    (``PlanesGraph.scan_guard``, for the rest of the route): a window
    ended with NOTHING over capacity and a sink unreached, on wires
    longer than a tile, and no legal snapshot stands behind the route.
    Nothing fights, so no further window of the same programs reaches
    the sink: it is the relaxation's predecessor 2-cycle inside a wire
    span (``planes._scan_update``), and the route would run out its
    iterations and be returned NOT legal.  A finishing pass that meets
    the state has ``fin_save`` to return and keeps the programs it has
    (it loses its seconds, not the route: ROADMAP Queue 1 item 2)."""
    return (n_over == 0 and unreached and not has_snapshot
            and max_span > 1)


# what a window of the planes driver IS, which decides what it costs:
# window 1 (every net, no history), a negotiation window (the nets
# _mis_colors marked), the phase-2 restart (every net, precisely), the
# wirelength finishing pass (the multi-sink nets, one sink a wave), a
# window after the pass (the nets that fight the finished trees)
WINDOW_KINDS = ("first", "negotiate", "restart", "finish", "relegalise")


def _window_kind(widx: int, force_all: bool, full_reroute_done: bool,
                 finish_done: bool) -> str:
    """The kind of window ``widx`` (1-based) from the flags the control
    step that plans it holds -- the same flags a RouteCheckpoint's
    ``driver`` carries, so a resumed route names its first window by
    this rule too (never ``first``: it resumes at widx >= 2).
    ``force_all`` (force_all_next) is set by the two full rebuilds
    only, each with its own done-flag, and they exclude each other."""
    if widx == 1:
        return "first"
    if force_all and finish_done:
        return "finish"
    if force_all and full_reroute_done:
        return "restart"
    return "relegalise" if finish_done else "negotiate"


_COMPILE_CACHE_DIR = None      # what this process last set (no-op guard)

# <checkout>/.jax_cache (git-ignored): the fixed default — the path is
# part of the cache key, so a directory that moves never hits
_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compile_cache(cache_dir: Optional[str] = None,
                                    worker: str = "") -> str:
    """THE compile-cache rule, called by every entry point (CLI, bench,
    serve, daemon, fleet workers, chip_smoke): if
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache is there and no code
    sets another; else the explicit ``cache_dir``; else
    ``<checkout>/.jax_cache``.  Never a temporary, pid- or time-derived
    path.  ``worker`` fences fleet members into ``<base>/<worker>``
    (fixed names) when the variable is unset — under the variable the
    operator owns placement and every process shares it.  Drops the
    entry-size/compile-time floors so every route window program is
    cached.  Returns the directory in use."""
    global _COMPILE_CACHE_DIR
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = cache_dir or _DEFAULT_COMPILE_CACHE_DIR
        if worker:
            cache_dir = os.path.join(cache_dir, worker)
    if _COMPILE_CACHE_DIR == cache_dir:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache singleton initializes lazily at the FIRST compile: a
    # flow that already ran jax work (synth/pack/place) before this
    # call has an initialized no-dir cache that would ignore the new
    # dir — reset so the next compile picks it up
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    _COMPILE_CACHE_DIR = cache_dir
    return cache_dir


# canonical route_window_planes dispatch signatures seen by THIS
# process: mirrors the (process-wide) jit cache, so it is module state
# on purpose — bench's post-warmup metrics reset clears the counters
# but must not forget warm variants, or the measured run would report
# phantom compiles
_DISPATCH_VARIANTS = set()


# planes.MIS_SKIPPED / MIS_SHORT / MIS_FULL -> route.mis_colors.<counter>
_MIS_FORM_COUNTERS = ("skipped_total", "short_total", "full_total")


def _note_dispatch_variant(key) -> bool:
    """Record one canonicalized dispatch signature; returns True when
    the variant is NEW (this dispatch pays an XLA compile, or a
    persistent-cache load on warm runs).  Feeds the
    route.dispatch.{compiles,cache_hits} counters."""
    reg = get_metrics()
    if key in _DISPATCH_VARIANTS:
        reg.counter("route.dispatch.cache_hits").inc()
        return False
    _DISPATCH_VARIANTS.add(key)
    reg.counter("route.dispatch.compiles").inc()
    return True


# the per-route pipeline gauges, zeroed when a route starts (the serve
# loop routes many times in one process: a job that never reaches a
# gauge must not inherit the previous job's value)
_PIPELINE_GAUGES = (
    "route.pipeline.dispatch_ms",
    "route.pipeline.stall_ms",
    "route.pipeline.overlap_frac",
    "route.pipeline.host_overlap_frac",
    "route.pipeline.host_plan_ms_total",
    "route.pipeline.dispatch_ms_total",
    "route.pipeline.device_exec_ms_total",
    "route.pipeline.stall_ms_total",
    "route.pipeline.host_serial_ms_total",
)

# routes of this process, numbered: the id the route's spans share
_ROUTE_IDS = itertools.count(1)


@contextlib.contextmanager
def dispatching(**args):
    """The hand-off of a window's program(s) to the runtime, as a
    ``route.pipeline.dispatch`` span, timed where the work happens: its
    host milliseconds go to ``route.pipeline.dispatch_ms_total`` and,
    when a dispatch variant was new inside it (tracing, lowering,
    compile or cache read, executable load), to the never-reset counter
    ``route.dispatch.first_call_ms_total``; such a span carries
    ``first=True`` and jax.monitoring's phase seconds."""
    enable_compile_capture()
    reg = get_metrics()
    compiles = reg.counter("route.dispatch.compiles")
    first_ms = reg.counter("route.dispatch.first_call_ms_total")
    n0, ph0 = compiles.value, compile_phases()
    t0 = time.perf_counter()
    with span("route.pipeline.dispatch", cat="route", **args) as sp:
        try:
            yield
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            if compiles.value > n0:
                first_ms.inc(ms)
                sp.set(first=True, **{
                    k + "_s": round(v - ph0.get(k, 0.0), 6)
                    for k, v in compile_phases().items()
                    if v > ph0.get(k, 0.0)})
            total = reg.gauge("route.pipeline.dispatch_ms_total")
            total.set((total.value or 0.0) + ms)


# how many overused rr-node ids each window's congestion record lists
_CONGESTION_TOPK = 8


def _top_overused(occ, capacity, k: int = _CONGESTION_TOPK) -> list:
    """Top-k overused rr-node ids for the mdclog congestion record:
    [[node_id, overuse], ...] sorted by overuse descending, only nodes
    with occ > capacity.  The reference dumped per-node congestion into
    its stats files; this is the spatial-telemetry seed for heatmaps."""
    over = np.asarray(occ).astype(np.int64) - np.asarray(capacity)
    k = min(int(k), over.size)
    if k <= 0:
        return []
    idx = np.argpartition(over, -k)[-k:]
    idx = idx[np.argsort(-over[idx], kind="stable")]
    return [[int(i), int(over[i])] for i in idx if over[i] > 0]


class _PlanStaging:
    """Named device staging slots for the per-rung plan tensors
    (sel/valid/widen masks).  put() hash-skips the upload when the slot
    already holds an identical array — PathFinder endgames redispatch
    near-identical plans for many windows — and otherwise stages the
    new value with a NON-BLOCKING jax.device_put, so the dispatch
    itself is upload-free.  Safe to reuse across dispatches because
    route_window_planes never donates its plan arguments."""
    __slots__ = ("_slots",)

    def __init__(self):
        self._slots = {}

    def put(self, name: str, host_arr):
        host_arr = np.asarray(host_arr)
        slot = self._slots.get(name)
        if (slot is not None and slot[0].shape == host_arr.shape
                and slot[0].dtype == host_arr.dtype
                and np.array_equal(slot[0], host_arr)):
            get_metrics().counter("route.pipeline.upload_skips").inc()
            return slot[1]
        dev_arr = jax.device_put(host_arr)
        self._slots[name] = (host_arr.copy(), dev_arr)
        return dev_arr


class Router:
    """Holds device state across a route() call; reusable across calls
    (e.g. the placer's delay-lookup routing, timing_place_lookup.c:981).

    Pass ``mesh`` (a 2-D jax.sharding.Mesh with axes ("net", "node")) to
    run the SAME negotiation loop multi-chip: the rr-graph/congestion
    arrays are sharded over rr-nodes, each batch of nets over the net
    axis, and the occupancy commit becomes a psum over ICI — the
    reference's MPI net-partitioned router with async congestion
    broadcast (mpi_route_load_balanced_nonblocking_send_recv_encoded.cxx)
    collapsed into GSPMD sharding annotations.  Results are bit-identical
    to the single-device run: every cross-shard reduction is an integer
    occupancy sum or an elementwise min with fixed order."""

    def __init__(self, rr: RRGraph, opts: Optional[RouterOpts] = None,
                 mesh=None):
        self.rr = rr
        self.opts = opts or RouterOpts()
        # host-side lookahead tables (route/lookahead.py): shared by
        # to_device's per-node arrays, the windowed A* gate's delay
        # bound, and the planes sweep budget (built ONCE — the pass is
        # O(N+E) and Titan-class graphs are multi-million nodes)
        from .lookahead import build_lookahead
        self._la_host = la = build_lookahead(rr)
        self.dev: DeviceRRGraph = to_device(rr, la=la)
        self._lmin_seg = tuple(
            int(la.len_same[la.axis == a].min())
            if (la.axis == a).any() else 1 for a in (0, 1))
        nx, ny = rr.grid.nx, rr.grid.ny
        # path-length / BF-step bound: a bb-confined path can wind, give slack
        self.max_len = 4 * (nx + ny) + 64
        self.pg = None
        if self.opts.program not in ("planes", "ell"):
            raise ValueError(
                "program must be 'planes' or 'ell' "
                f"(got {self.opts.program!r})")
        if self.opts.program == "planes":
            from .planes import build_planes
            if rr.wire_switch_of_track is None:
                raise ValueError(
                    f"program={self.opts.program!r} needs a graph built "
                    f"by rr.graph.build_rr_graph (track switch map); use "
                    f"program='ell' for foreign graphs")
            self.pg = build_planes(rr)
        self.mesh = mesh
        # multi-chip halo-exchange sharding (opts.mesh_shards > 1):
        # one RowMesh per transport impl — the ladder's "mesh"
        # dimension picks which one a window dispatches under
        self._row_meshes = None
        self._mesh_lost = False
        if self.opts.mesh_shards > 1:
            if mesh is not None:
                raise ValueError(
                    "mesh_shards > 1 and a legacy (net, node) mesh are "
                    "mutually exclusive — the halo-exchange sharding "
                    "owns the device mesh")
            if self.pg is None:
                raise ValueError(
                    "mesh_shards > 1 needs a planes program "
                    "(program='planes')")
            from .planes_shard import make_row_mesh
            self._row_meshes = {
                impl: make_row_mesh(self.opts.mesh_shards, impl)
                for impl in ("ppermute", "pallas_halo")}
        # reusable plan staging slots (hash-skipped non-blocking
        # uploads) + persistent compile cache, both for the pipelined
        # window driver
        self._staging = _PlanStaging()
        self._cap_np = None    # host capacity copy for congestion top-k
        # AOT program library (serve/library.py): loaded keys are
        # pre-registered as SEEN dispatch variants — a warm serve's
        # first window is a cache hit, not a compile — and the library
        # object serves those variants from deserialized executables
        # at the dispatch site
        self._library = None
        if self.opts.program_library_dir and mesh is None \
                and self.opts.mesh_shards <= 1 and self.pg is not None:
            from ..serve.library import ProgramLibrary
            self._library = lib = ProgramLibrary(
                self.opts.program_library_dir)
            lib.load()
            for key in lib.keys():
                _DISPATCH_VARIANTS.add(key)
            reg = get_metrics()
            reg.gauge("route.serve.library_variants").set(
                len(lib.keys()))
            reg.gauge("route.serve.library_stale").set(
                0 if lib.stale_reason is None else 1)
        self._s_batch = self._s_node = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.shard import NET, NODE, shard_graph
            self.dev = shard_graph(self.dev, mesh)
            self._s_batch = NamedSharding(mesh, P(NET))
            self._s_node = NamedSharding(mesh, P(NODE))
            self._net_axis = mesh.shape[NET]

    def export_program_library(self) -> int:
        """Serialize every dispatch variant noted since the last save
        into opts.program_library_dir (serve/library.py).  Pays one
        trace+lower+compile per new variant — call after a warm-up
        route(), never mid-serve.  Returns entries written."""
        if self._library is None:
            return 0
        n = self._library.save()
        get_metrics().gauge("route.serve.library_variants").set(
            len(self._library.keys()))
        return n

    def _active_row_mesh(self, lad):
        """The RowMesh the next window should relax under, per the
        resil ladder's "mesh" dimension (None = the single-chip
        floor).  Level 0 (pallas_halo, the overlapped remote-DMA
        transport) only engages where that transport exists — TPU
        backends; elsewhere ppermute is the top working rung."""
        if self._row_meshes is None or self._mesh_lost:
            return None
        lvl = 0 if lad is None else lad.level("mesh")
        if lvl >= 2:
            return None
        if lvl == 0 and jax.default_backend() == "tpu":
            return self._row_meshes["pallas_halo"]
        return self._row_meshes["ppermute"]

    def _check_mesh_member(self, resil_rt, rm):
        """backend.loss injection point for the sharded rungs: fires
        BEFORE the jitted call (donated buffers survive, the retry is
        safe) and is STICKY — a lost device stays lost, so the
        watchdog's same-rung retry fails too and the chain descends
        to the single-chip rung instead of flapping."""
        from ..resil.faults import BackendLostError, Fault
        if self._mesh_lost:
            raise BackendLostError(Fault(
                "backend.loss", -1,
                f"mesh member lost earlier (n_shards={rm.n_shards})"))
        plan = getattr(resil_rt, "plan", None)
        if plan is not None:
            try:
                plan.raise_if(
                    "backend.loss",
                    detail=f"shard of row mesh n={rm.n_shards}")
            except BackendLostError:
                self._mesh_lost = True
                raise

    def _mesh_demote(self, resil_rt, reason: str) -> None:
        """Quarantine hook for a sharded rung.  A lost mesh member
        makes EVERY sharded impl unrunnable, so the ladder lands
        straight on the single-chip floor; any other quarantine cause
        (watchdog budget, injected dispatch fault) steps one level
        like the kernel dimension does."""
        from ..resil.ladder import DIMS
        lad = getattr(resil_rt, "ladder", None)
        moved = False
        if lad is not None:
            floor = len(DIMS["mesh"]) - 1
            if self._mesh_lost:
                while lad.level("mesh") < floor:
                    lad.step("mesh", reason)
                    moved = True
            else:
                moved = lad.step("mesh", reason)
        if moved or lad is None:
            get_metrics().counter("route.mesh.mesh_demotions").inc()

    def _guarded_dispatch_mesh(self, resil_rt, vkey, wp_args,
                               wp_kwargs, rm):
        """Window dispatch chain when a RowMesh is active: the planned
        transport rung first, then (for pallas_halo) the portable
        ppermute transport, then the single-chip floor.  All rungs are
        route-level QoR-identical (the sharded fixpoint equals the
        single-device one; see planes_shard).  The AOT library rung
        never appears here — the library is not loaded with
        mesh_shards > 1."""
        from ..resil.watchdog import Rung
        from .planes import route_window_planes

        def mesh_run(label, rm_):
            def run():
                _note_dispatch_variant(
                    vkey if label == rm.impl else vkey + (label,))
                self._check_mesh_member(resil_rt, rm_)
                return route_window_planes(
                    *(wp_args[:-1] + (rm_,)), **wp_kwargs)
            return run

        def quar(reason):
            self._mesh_demote(resil_rt, reason)

        rungs = [Rung(rm.impl, mesh_run(rm.impl, rm), quar)]
        if rm.impl == "pallas_halo":
            rungs.append(Rung(
                "ppermute",
                mesh_run("ppermute", rm.with_impl("ppermute")), quar))

        def run_single():
            _note_dispatch_variant(vkey + ("single_chip",))
            return route_window_planes(
                *(wp_args[:-1] + (None,)), **wp_kwargs)

        rungs.append(Rung("single_chip", run_single))
        return resil_rt.guard.run(vkey, rungs)

    def _guarded_dispatch(self, resil_rt, vkey, wp_args, wp_kwargs):
        """Window dispatch under the resilience guard: an ordered
        chain of BIT-IDENTICAL execution rungs, fastest first, handed
        to DispatchGuard.run (retry with capped backoff, per-variant
        quarantine, descent).  Rung set per the degradation ladder:
        AOT library -> live jit.  Each rung notes its own variant key
        so route.dispatch.{compiles,cache_hits} stays honest about
        which program actually ran."""
        from ..resil.watchdog import Rung
        from .planes import _as_row_mesh, route_window_planes
        rm = _as_row_mesh(wp_args[-1])
        if rm is not None:
            return self._guarded_dispatch_mesh(resil_rt, vkey, wp_args,
                                               wp_kwargs, rm)
        ladder = resil_rt.ladder
        rungs = []
        if (self._library is not None
                and ladder.level("program") == 0):
            def run_aot():
                _note_dispatch_variant(vkey)
                return self._library.dispatch(
                    vkey, route_window_planes, wp_args, wp_kwargs)

            def evict_aot(reason):
                # blacklist the variant from the AOT cache so a later
                # library process never serves the quarantined entry
                self._library.evict(vkey, reason)

            rungs.append(Rung("aot", run_aot, evict_aot))

        def run_jit():
            _note_dispatch_variant(vkey)
            return route_window_planes(*wp_args, **wp_kwargs)

        rungs.append(Rung("jit", run_jit))
        return resil_rt.guard.run(vkey, rungs)

    @staticmethod
    def _dump_routes(stats_dir: str, it: int, paths: np.ndarray,
                     N: int) -> None:
        """routes_iter_N.txt per-iteration dump (…cxx:6167 diagnostics):
        one line per (net, sink) with the node path sink->tree."""
        import os

        os.makedirs(stats_dir, exist_ok=True)
        with open(os.path.join(stats_dir, f"routes_iter_{it}.txt"),
                  "w") as f:
            R, S, _ = paths.shape
            for r in range(R):
                for s in range(S):
                    seg = paths[r, s]
                    seg = seg[seg < N]
                    if seg.size:
                        f.write(f"{r} {s}: " +
                                " ".join(str(v) for v in seg) + "\n")

    @staticmethod
    def _obs_window(tw0: float, it_done: int, K: int, n_over: int,
                    over_total: int, rerouted: int, relax_steps: int,
                    pres: float, cpd: float, batches: int,
                    relax_useful: Optional[int] = None,
                    bucket_occ=(), compaction: float = 1.0,
                    kernel_plans=(), tw1: Optional[float] = None,
                    win_ev: Optional[dict] = None,
                    row: Optional[RouteStats] = None) -> None:
        """Trace + metrics for one committed window: the window's
        ledger on its route.window span, a route.iter child span where
        the window IS one iteration, and the per-iteration registry
        snapshot.  Iteration boundaries inside a K>1 window are not
        host-visible, so such a window carries first_iter / last_iter
        and no route.iter spans — the stats_dir / host-callback paths
        force K=1 and get exact per-iteration spans.

        ``win_ev`` is the tracer's event of the window's live
        route.window span (the planes driver opens one; None without a
        tracer): the ledger is added to its args, and ``row``, the
        window's stats row, field for field (what was known at the
        span's open is there already; control_s and kept follow at the
        route's end).  The ELL driver has no live span and gets the
        event recorded here.

        ``relax_useful`` / ``bucket_occ`` / ``compaction`` feed the
        work-efficiency ledger: sweeps that improved a distance vs.
        total executed, per-dispatch batch-slot occupancy, and the
        compacted/full plan-width ratio.  ``kernel_plans`` (one dict
        per dispatch, from _plan_block_nets) feeds the
        hardware-efficiency ledger: a route.kernel span per dispatch
        plus the route.kernel.* gauges, set from the dispatch covering
        the most nets (the dominant rung).

        ``tw1`` is the window's end time (perf_counter seconds); the
        pipelined driver defers this whole call until the NEXT window
        is in flight, so "now" would be wrong there — it passes the
        measured summary-ready time instead."""
        if tw1 is None:
            tw1 = time.perf_counter()
        useful = relax_steps if relax_useful is None else relax_useful
        tr = get_tracer()
        ledger = dict(
            first_iter=it_done - K + 1, last_iter=it_done, K=K,
            rerouted=rerouted, overused_nodes=n_over,
            relax_steps=relax_steps, relax_steps_useful=int(useful),
            relax_steps_wasted=int(relax_steps - useful))
        if win_ev is not None:
            if row is not None:
                # every field that holds a value (a NaN critical path
                # is no JSON)
                ledger.update({k: v for k, v in asdict(row).items()
                               if v == v})
            win_ev["args"].update(ledger)
        elif tr is not None:
            tr.add_complete("route.window", tw0, tw1 - tw0, cat="route",
                            **ledger)
        if tr is not None:
            for kp in kernel_plans:
                tr.add_complete("route.kernel", tw0, 0.0, cat="route",
                                **kp)
            if K == 1:
                tr.add_complete("route.iter", tw0, tw1 - tw0,
                                cat="route", it=it_done,
                                overused=int(n_over),
                                pres_fac=round(float(pres), 4))
        reg = get_metrics()
        reg.counter("route.iterations").inc(K)
        reg.counter("route.relax_steps").inc(relax_steps)
        reg.counter("route.relax_steps_useful").inc(int(useful))
        reg.counter("route.relax_steps_wasted").inc(
            int(relax_steps - useful))
        for occ_frac in bucket_occ:
            reg.histogram("route.bucket_occupancy").record(
                float(occ_frac))
        reg.gauge("route.compaction_ratio").set(round(float(compaction),
                                                      6))
        if kernel_plans:
            dom = max(kernel_plans, key=lambda kp: kp.get("nets", 0))
            reg.set_gauges({
                "route.kernel.lane_occupancy": dom["lane_occupancy"],
                "route.kernel.bytes_per_sweep": dom["bytes_per_sweep"],
            })
        reg.counter("route.batches").inc(batches)
        reg.gauge("route.overused_nodes").set(int(n_over))
        reg.gauge("route.overuse_total").set(int(over_total))
        reg.gauge("route.dirty_nets").set(int(rerouted))
        reg.gauge("route.pres_fac").set(round(float(pres), 6))
        if cpd == cpd:
            reg.gauge("route.crit_path_delay").set(float(cpd))
        reg.histogram("route.window_wall_s").record(tw1 - tw0)
        reg.snapshot(phase="route", iteration=int(it_done))

    def _book_window(self, bk: dict, result, mlog) -> None:
        """Deferred bookkeeping for one committed window: consume the
        per-rung packed scal vectors (already streamed host-side by the
        copy_to_host_async started at dispatch), accumulate the work
        ledger, append the stats row, and emit obs/mlog records.  None
        of this feeds the control loop, so the pipelined driver runs it
        while the NEXT window executes on device; pipeline=False runs
        it inline at the old program point.  Every field of ``bk`` is a
        value captured at that window's control step — later control
        mutations (pres, plateau state, widened_nets) cannot leak in."""
        from .planes import (SCAL_MIS_FORM, SCAL_NEXEC, SCAL_NROUTES,
                             SCAL_S_EXEC, SCAL_S_USEFUL, SCAL_SINK_ROWS,
                             SCAL_SINK_ROWS_DENSE, SCAL_WALK_BUDGET,
                             SCAL_WALK_SLOTS, SCAL_WALK_STEPS,
                             SCAL_WAVES)

        w_steps = w_useful = w_steps_crop = w_waves_crop = 0
        nroutes = nexec = w_waves = 0
        w_steps_wide = w_waves_wide = 0
        w_sink_rows = w_sink_rows_dense = w_cell_sweeps = 0
        rung_classes = bk.get("rung_classes") or [0] * len(
            bk["rung_scals"])
        mesh_info = bk.get("mesh")
        halo_b = halo_ex = 0
        reg = get_metrics()
        for ri, (scal_d, cropped) in enumerate(bk["rung_scals"]):
            v = np.asarray(scal_d)
            # the form of the rung's conflict colouring
            # (planes.window_colours): the three sum to calls_total
            reg.counter("route.mis_colors." + _MIS_FORM_COUNTERS[
                int(v[SCAL_MIS_FORM])]).inc()
            nroutes += int(v[SCAL_NROUTES])
            nexec += int(v[SCAL_NEXEC])
            w_steps += int(v[SCAL_S_EXEC])
            w_useful += int(v[SCAL_S_USEFUL])
            result.total_walk_steps += int(v[SCAL_WALK_STEPS])
            result.total_walk_budget += int(v[SCAL_WALK_BUDGET])
            result.total_walk_slots_read += int(v[SCAL_WALK_SLOTS])
            w_waves += int(v[SCAL_WAVES])
            w_sink_rows += int(v[SCAL_SINK_ROWS])
            w_sink_rows_dense += int(v[SCAL_SINK_ROWS_DENSE])
            if cropped:
                w_steps_crop += int(v[SCAL_S_EXEC])
                w_waves_crop += int(v[SCAL_WAVES])
            w_cell_sweeps += int(v[SCAL_S_EXEC]) * bk["rung_cells"][ri]
            if rung_classes[ri]:
                w_steps_wide += int(v[SCAL_S_EXEC])
                w_waves_wide += int(v[SCAL_WAVES])
            if mesh_info is not None and mesh_info[0] > 1 \
                    and ri < len(bk["kplans"]):
                # halo ledger: every executed sweep exchanged one halo
                # round per internal boundary, at the rung's modeled
                # per-sweep byte volume (dtype-aware, planes_shard)
                kp = bk["kplans"][ri]
                halo_b += (kp.get("halo_bytes_per_sweep", 0)
                           * int(v[SCAL_S_EXEC]))
                halo_ex += (mesh_info[0] - 1) * int(v[SCAL_S_EXEC])
        result.total_net_routes += nroutes
        result.total_relax_steps += w_steps
        result.total_relax_steps_useful += w_useful
        result.total_relax_steps_wasted += w_steps - w_useful
        result.total_relax_steps_cropped += w_steps_crop
        result.total_waves_cropped += w_waves_crop
        result.total_cell_sweeps += w_cell_sweeps
        result.total_relax_steps_wide += w_steps_wide
        result.total_waves += w_waves
        # the device counts sink rows; a row is sink_cells elements
        sink_reads = w_sink_rows * bk["sink_cells"]
        sink_reads_dense = w_sink_rows_dense * bk["sink_cells"]
        result.total_sink_reads += sink_reads
        result.total_sink_reads_dense += sink_reads_dense
        row = RouteStats(
            bk["it_done"], bk["n_over"], bk["over_total"], bk["ndirty"],
            bk["tw1"] - bk["tw0"], relax_steps=w_steps, batches=nexec,
            overuse_pct=100.0 * bk["n_over"] / max(1, self.rr.num_nodes),
            crit_path_delay=bk["cpd"], window=bk["widx"], kind=bk["kind"],
            precise=bk["precise"], sweep_boost=bk["sweep_boost"],
            waves=w_waves, relax_steps_cropped=w_steps_crop,
            waves_cropped=w_waves_crop, cell_sweeps=w_cell_sweeps,
            scan_guard=bk["scan_guard"],
            net_routes=nroutes, stall_s=bk["stall_s"],
            plan_s=bk["plan_s"], dispatch_ms=bk["dispatch_ms"],
            fanout_class=max(rung_classes),
            waves_wide=w_waves_wide, relax_steps_wide=w_steps_wide,
            sink_reads=sink_reads, sink_reads_dense=sink_reads_dense)
        result.stats.append(row)
        reg.counter(f"route.window.seconds_total.{row.kind}").inc(
            row.route_time_s)
        reg.counter(f"route.window.sweeps_total.{row.kind}").inc(w_steps)
        reg.counter(f"route.window.count_total.{row.kind}").inc()
        self._obs_window(bk["tw0"], bk["it_done"], bk["K"], bk["n_over"],
                         bk["over_total"], bk["ndirty"], w_steps,
                         bk["pres"], bk["cpd"], nexec,
                         relax_useful=w_useful,
                         bucket_occ=bk["bucket_occ"],
                         compaction=bk["compaction"],
                         kernel_plans=bk["kplans"], tw1=bk["tw1"],
                         win_ev=bk["win_ev"], row=row)
        if mesh_info is not None:
            reg.counter("route.mesh.halo_bytes").inc(halo_b)
            reg.counter("route.mesh.halo_exchanges").inc(halo_ex)
            # overlap_frac per window: the dominant rung's modeled
            # hide of the halo exchange behind sweep compute (0.0 on
            # the critical-path ppermute transport and on single_chip)
            ov = 0.0
            if mesh_info[0] > 1 and bk["kplans"]:
                dom = max(bk["kplans"],
                          key=lambda kp: kp.get("nets", 0))
                ov = dom.get("mesh_overlap_frac", 0.0)
            reg.set_gauges({
                "route.mesh.n_shards": mesh_info[0],
                "route.mesh.overlap_frac": ov,
            })
        # congestion record (corpus + mdclog): in pipelined mode the
        # occ_ref is a non-donated snapshot whose copy_to_host_async
        # was started at the control point — by now (the NEXT window is
        # executing) the np.asarray below consumes an already-streamed
        # host copy, so --sync is not required for congestion telemetry
        top = []
        if bk.get("occ_ref") is not None:
            if self._cap_np is None:
                self._cap_np = np.asarray(self.dev.capacity)
            k = self.opts.congestion_topk
            top = _top_overused(bk["occ_ref"], self._cap_np,
                                k=k if k > 0 else _CONGESTION_TOPK)
            result.congestion.append({
                "window": bk["widx"], "iteration": bk["it_done"],
                "overused_nodes": bk["n_over"],
                "overuse_total": bk["over_total"],
                "pres_fac": round(bk["pres"], 6),
                "top_overused": top})
        if mlog.enabled:
            mlog.set_mdc(bk["widx"])
            mlog.log("route", iteration=bk["it_done"], K=bk["K"],
                     rerouted=bk["ndirty"], groups=nexec,
                     relax_steps=w_steps)
            mlog.log("congestion", overused_nodes=bk["n_over"],
                     overuse_total=bk["over_total"],
                     pres_fac=round(bk["pres"], 4),
                     widened=bk["widened"],
                     top_overused=top)
            mlog.log("schedule", colors=bk["colors_max"],
                     dirty_next=bk["dirty_next"],
                     precise=bk["precise"],
                     sweep_boost=bk["sweep_boost"])
            if bk["cpd"] == bk["cpd"]:
                mlog.log("timing", crit_path_delay=bk["cpd"],
                         dmax_hist=[None if d != d else float(d)
                                    for d in bk["dmax_hist"].tolist()])

    def _occ_snapshot(self, occ, pipelined: bool, mlog):
        """Occupancy reference for one window's congestion record
        (None = telemetry off).  --sync returns the live array — the
        record is booked inline, before the next dispatch donates it.
        Pipelined mode takes a NON-donated device copy and starts its
        host readback immediately: the copy streams D2H while the next
        window executes, and _book_window consumes it without a sync
        (occ itself is donated into the next dispatch; reading the
        donated buffer later would fail)."""
        if self.opts.congestion_topk <= 0 and not mlog.enabled:
            return None
        if not pipelined:
            return occ
        snap = occ + 0
        if hasattr(snap, "copy_to_host_async"):
            snap.copy_to_host_async()
        return snap

    def _obs_final(self, result: "RouteResult") -> None:
        """End-of-route registry state: the converged numbers every
        report derives from.  overused_wire_nodes uses the SAME helper
        as route_report, so the metrics sink and the human-readable
        report cannot drift (stats.c wire-only overuse semantics)."""
        from .report import overused_wire_nodes

        reg = get_metrics()
        reg.gauge("route.success").set(bool(result.success))
        reg.gauge("route.wirelength").set(int(result.wirelength))
        reg.gauge("route.widened_nets").set(int(result.widened_nets))
        reg.gauge("route.net_routes").set(int(result.total_net_routes))
        # end-of-route work-efficiency ledger (per-window counters
        # accumulate in route.relax_steps_{useful,wasted}): the wasted
        # fraction is THE lever-attribution number for bench runs
        total = max(1, result.total_relax_steps)
        reg.gauge("route.relax_wasted_frac").set(
            round(result.total_relax_steps_wasted / total, 6))
        reg.gauge("route.overused_wire_nodes").set(
            overused_wire_nodes(self.rr, result.occ))
        reg.snapshot(phase="route_final", iteration=result.iterations)

    def _lb_scale(self):
        """[4] scale vector for the windowed A* gate: flat (congestion,
        delay) per-tile floors x astar_fac, astar_fac itself (applied
        device-side to the per-cost-index delay bound), and the
        IPIN+SINK delay tail (lookahead.py; route_timing.c:693-760)."""
        from .device_graph import wire_cost_floor

        min_cong, min_delay, _ = wire_cost_floor(self.rr)
        af = self.opts.astar_fac
        return (min_cong * af, min_delay * af, af,
                self._la_host.term_delay)

    def _put_batch(self, a: np.ndarray):
        x = jnp.asarray(a)
        if self._s_batch is not None:
            x = jax.device_put(x, self._s_batch)
        return x

    def _put_node(self, x):
        if self._s_node is not None:
            x = jax.device_put(x, self._s_node)
        return x

    def _plan_groups(self, dirty: np.ndarray, colors: Optional[np.ndarray],
                     nsinks: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                     B: int, R: int):
        """Static batch plan [G, B] for a window: dirty nets split by the
        device-computed conflict color (each class commits separately,
        custom_vertex_coloring semantics), then by fanout class
        (similar-depth wave loops), spatially round-robined (split_nets
        load-spreading role), chunked to B.

        Returns (sel_plan, valid_plan): valid_plan is 0 on a pad slot
        and 1 + the index of its colour class on a net's, the segment
        inside which the window program re-packs the nets that still
        need a re-route in a rebuild's tail (planes.repack_plan)."""
        batches, seg = [], []
        if colors is None or len(dirty) <= 1:
            groups = [dirty]
        else:
            cd = colors[dirty]
            groups = [dirty[cd == c] for c in np.unique(cd)]
        for s, g in enumerate(groups):
            chunks = _order_and_chunk(g, nsinks, cx, cy, B)
            batches.extend(chunks)
            seg.extend([s + 1] * len(chunks))
        if not batches:
            batches, seg = [np.zeros(0, dtype=np.int64)], [1]
        # converged-net compaction: once most nets are clean the per-
        # color chunks are far shorter than B — narrow the PLAN WIDTH to
        # the largest chunk (pow2-bucketed, floor 8, so the compiled
        # window-program variants stay O(log B)) instead of shipping
        # B-wide plans that are mostly masked-off padding.  Chunking
        # stays at B, so batch membership — and the negotiation — is
        # unchanged; only the dead slots are dropped.  Under a mesh the
        # width must stay B (the batch axis is sharded over "net", whose
        # size need not divide a narrower pow2).
        B_g = B
        if self.mesh is None:
            B_g = min(B, max(8, _pow2_at_least(
                max(len(b) for b in batches))))
        # pad the group count to a power of two: G is a traced shape, so
        # padding keeps the set of compiled window programs small
        G = _pow2_at_least(len(batches))
        sel_plan = np.zeros((G, B_g), dtype=np.int32)
        valid_plan = np.zeros((G, B_g), dtype=np.int8)
        for i, b in enumerate(batches):
            sel_plan[i, :len(b)] = b
            valid_plan[i, :len(b)] = seg[i]
        return sel_plan, valid_plan

    def _canvas_shapes(self, tile):
        """(shape_x, shape_y) of one net's canvas pair as a rung relaxes
        it: the whole grid's, or its (cnx, cny) crop tile's."""
        if tile is None:
            return self.pg.shape_x, self.pg.shape_y
        W, (cnx, cny) = self.pg.shape_x[0], tile
        return (W, cnx, cny + 1), (W, cnx + 1, cny)

    def _canvas_cells(self, tile) -> int:
        return sum(int(np.prod(s)) for s in self._canvas_shapes(tile))

    def _plan_block_nets(self, tile, nnets: int, nsw: int,
                         plane_dtype: str = "f32") -> dict:
        """Modeled layout row of one dispatch (companion of
        _plan_groups; feeds the route.kernel.* gauges, the
        route.kernel trace spans and devprof's modeled side): the
        unpadded one-net-per-step layout's vector-register occupancy
        and the XLA relaxation's modeled HBM bytes per sweep
        (planes.xla_bytes_per_cell, dtype-aware).  Nothing here is
        cached — a Router reused across route() calls with a different
        plane_dtype re-plans from scratch every dispatch."""
        from ..serve.batcher import (packed_layout,
                                     unpacked_lane_occupancy)
        from .planes import plane_itemsize, xla_bytes_per_cell

        shx, shy = self._canvas_shapes(tile)
        n = max(1, int(nnets))
        return dict(
            variant="xla",
            lane_occupancy=round(unpacked_lane_occupancy(shx, shy), 4),
            bytes_per_sweep=int(
                xla_bytes_per_cell(plane_itemsize(plane_dtype))
                * packed_layout(shx, shy).cells * n),
            tile=(None if tile is None else list(tile)),
            nets=n, nsweeps=int(nsw), plane_dtype=plane_dtype)

    # escalating sync schedule: window sizes between host round trips
    # (a host round trip costs a sync; the values were tuned on an
    # earlier backend and are not re-measured on the current chip)
    _WINDOWS = (2, 2, 3, 4, 5, 6, 8, 10, 10)

    def _route_planes_windows(self, term, crit, timing_cb, analyzer,
                              occ, acc,
                              paths, sink_delay, all_reached, bb, full_bb,
                              source_d, sinks_d, planes_tbl, nsinks_np,
                              cx_np, cy_np, result, B, mlog,
                              crop="auto", resume=None, rid=0,
                              t_enter=None, fan_d=None):
        """Window-fused PathFinder driver for the planes program: the
        negotiation runs as a sequence of multi-iteration device programs
        (planes.route_window_planes) with ONE host sync per window — the
        fetch returns the reroute mask, the device-computed conflict
        coloring, and the overuse summary, from which the host decides
        convergence, plateau widening, and the next window's batch plan.
        Replaces the per-iteration loop (whose per-batch and per-summary
        round trips dominated wall time) and the host O(I^2)
        coloring.

        With ``analyzer`` (timing.sta.TimingAnalyzer), the per-iteration
        STA runs INSIDE the window program (sta.sta_crit fused into
        route_window_planes), so timing-driven routing keeps K>1
        multi-iteration windows — criticalities never visit the host
        during negotiation; only the per-iteration crit-path scalars
        come back with each window's summary fetch (the reference reruns
        analyze_timing every iteration, router.cxx:28,42).

        With ``opts.pipeline`` (default), the driver is a two-stage
        software pipeline: each window's summary comes back as a packed
        [R] status word + [SCAL_LEN] scal vector whose
        copy_to_host_async starts at dispatch, later rungs are planned
        and staged (hash-skipped non-blocking device_put) while earlier
        rungs execute, and the previous window's bookkeeping
        (_book_window) runs while the current window is in flight.
        Every dispatch is still planned from a fully consumed summary —
        lag-0 — so results are bit-identical to pipeline=False, which
        drains each rung before any further host work (the --sync
        escape hatch).

        ``rid`` is the route's id (the ``route`` arg of all its spans)
        and ``t_enter`` the perf_counter second route() was entered:
        the route's prologue runs from there to the first window's
        open, its epilogue from the last control step's end to the
        return.  Both are in ``RouteResult.wall`` and, measured
        intervals, on the installed Tracer as route.prologue /
        route.epilogue; they are NOT profiler annotations yet
        (tests/benchmark/test_scope_reduce.py holds the names a device
        gap may fall in to a closed list, PERF.md section 7)."""
        from .planes import (PLANE_DTYPES, SCAL_MAX_SPAN, SCAL_N_OVER,
                             SCAL_OVER_TOTAL, route_window_planes,
                             unpack_window_status)

        opts = self.opts
        rr, dev = self.rr, self.dev
        R, Smax = term.sinks.shape
        N = rr.num_nodes
        # the fanout classes: a batch holds nets of one class and the
        # window program runs it at that class's width.  A class above
        # the first (a net of hundreds of sinks spans the device) takes
        # the full canvas only
        classes = term.fanout_classes
        widths = [c.width for c in classes]
        cls_of = term.class_rows()[0]
        # what a window program of a route with classes is told besides
        fan_kw = {} if fan_d is None else {"fan": fan_d}
        # distance elements a sink row of the pick's tables holds
        # (planes_tbl[5] is uid_ucell [U + 1, C])
        sink_cells = int(planes_tbl[5].shape[1])

        # device-fused STA config (analyzer mode): the full timing sweep
        # runs between iterations inside the window program
        sta_kw = {}
        if analyzer is not None:
            sta_kw = dict(
                tdev=analyzer.dev, req_seed=analyzer._req_seed,
                sta_depth=analyzer.tg.depth, crit_exp=analyzer.crit_exp,
                max_crit=analyzer.max_crit,
                use_sdc=analyzer._req_seed is not None)

        pres = opts.initial_pres_fac
        crit_d = (jax.tree.map(jnp.asarray, crit)
                  if isinstance(crit, tuple) else _by_class(crit, classes))
        it_done = 0
        dirty = np.arange(R)
        colors = None
        wide = np.zeros(R, dtype=bool)
        bb_full = np.zeros(R, dtype=bool)
        best_over = 1 << 30
        stall_windows = 0
        n_over = -1
        sweep_boost = 1
        # two-phase mode switch (the reference's congestion phase two,
        # …cxx:6238-6267): when overuse stalls, the remaining dirty nets
        # drop from the doubling sink schedule to the exact VPR
        # incremental schedule (sink_group=1) — the doubling trees cost
        # a few % wirelength, which at tight capacity is the difference
        # between converging and livelocking (measured on W=6 fixtures)
        precise = opts.sink_group != 0
        full_reroute_done = False
        finish_done = False
        fin_save = None
        force_all_next = False
        widx = 0
        # the relaxation's graph as this route's windows see it: the
        # router's own until _scan_guard_due fires at a window's end
        # (the predecessor 2-cycle's mark), the scans' guard on from the
        # next window to the route's end
        pg_now = self.pg
        # cells of a net's canvas pair by crop tile (None: the whole
        # grid), reckoned once a route for the rows' cell_sweeps
        canvas_cells = {}
        # only the spatially sharded mesh path keeps full canvases
        # (crops are net-local)
        crop_forced = None
        if "x" in crop and self.mesh is None:
            cwf, chf = (int(v) for v in crop.split("x"))
            crop_forced = (min(cwf, rr.grid.nx - 1),
                           min(chf, rr.grid.ny - 1))
        elif "x" in crop:
            import warnings

            warnings.warn("crop='WxH' is ignored under a mesh (crops "
                          "are net-local; the spatially sharded path "
                          "keeps full canvases)")
        crop_full = (crop not in ("auto",) and crop_forced is None) \
            or self.mesh is not None

        if resume is not None:
            # elastic resume: the checkpointed negotiation continues
            # under THIS router's mesh layout (occ/acc etc. were already
            # re-uploaded by route()); restore the host scheduling state
            pres = resume.pres
            it_done = resume.it_done
            d = resume.driver
            widx = d["widx"]
            dirty = d["dirty"].copy()
            colors = (d["colors"].copy()
                      if d["colors"] is not None else None)
            wide = d["wide"].copy()
            bb_full = d["bb_full"].copy()
            best_over = d["best_over"]
            stall_windows = d["stall_windows"]
            sweep_boost = d["sweep_boost"]
            precise = d["precise"]
            full_reroute_done = d["full_reroute_done"]
            finish_done = d.get("finish_done", False)
            if d.get("scan_guard"):
                pg_now = pg_now.replace(scan_guard=True)
            force_all_next = d["force_all_next"]
            result.widened_nets = d["widened_nets"]
            crop_full = d.get("crop_full", crop_full)
            fs = getattr(resume, "fin_save", None)
            if fs is not None:
                # re-arm the pre-finish legal snapshot: if the resumed
                # finishing pass cannot re-legalize within budget, the
                # legal route is restored instead of reporting failure
                fin_save = tuple(jax.tree.map(jnp.asarray, v)
                                 for v in fs[:5]) + (int(fs[5]),)

        # current path-slot budget (one for every class's store)
        L = int(jax.tree.leaves(paths)[0].shape[2])
        L_cap = self.max_len
        next_ckpt = (it_done + opts.checkpoint_every
                     if opts.checkpoint_every else None)
        # cooperative yield target (slice_iterations): force a
        # checkpoint at the slice edge even when checkpoint_every is off
        yield_at = (it_done + opts.slice_iterations
                    if opts.slice_iterations else None)
        if yield_at is not None:
            next_ckpt = (yield_at if next_ckpt is None
                         else min(next_ckpt, yield_at))
        sliced_yield = False
        # static initial bbs (terminal extent + bb_factor): the crop
        # anchor — tiles must cover a net's terminals even after its
        # LIVE bb widens device-side (see _step_core crop notes)
        bb0_d = jnp.asarray(np.stack(
            [term.bb_xmin, term.bb_xmax, term.bb_ymin, term.bb_ymax],
            axis=1).astype(np.int32))
        # measured per-net live bb sizes (updated from each window's
        # summary; resume restores them from the checkpointed bbs)
        if resume is not None:
            live_w = (resume.bb[:, 1] - resume.bb[:, 0] + 1).astype(
                np.int64)
            live_h = (resume.bb[:, 3] - resume.bb[:, 2] + 1).astype(
                np.int64)
        else:
            live_w = (term.bb_xmax - term.bb_xmin + 1).astype(np.int64)
            live_h = (term.bb_ymax - term.bb_ymin + 1).astype(np.int64)
        # reduced-budget promotion state (sweep_budget_div > 1): nets
        # that missed a sink under a reduced budget run at full budget
        # from then on
        if resume is not None:
            budget_full = resume.driver.get(
                "budget_full", np.zeros(R, dtype=bool)).copy()
        else:
            budget_full = np.zeros(R, dtype=bool)
        # pipelined mode: generic host timing callbacks and per-
        # iteration stats rows serialize the loop anyway (K=1 + host
        # work between windows), so they keep the synchronous ordering;
        # the fused-STA analyzer path pipelines fine (crit never visits
        # the host)
        pipelined = bool(opts.pipeline) and not opts.stats_dir \
            and not (timing_cb is not None and analyzer is None)
        book = None           # deferred bookkeeping of the last window
        reg = get_metrics()
        tr = get_tracer()
        ctl = None      # the open route.pipeline.control span, if any
        # the window ledger: the kind of the window about to be planned
        # (set by the control step before it; a resumed route's first
        # comes from the checkpoint's flags), and per window the
        # tracer's event and the seconds of the control step after it
        kind = _window_kind(widx + 1, force_all_next, full_reroute_done,
                            finish_done)
        win_evs, ctl_s = [], []
        prologue_s = 0.0
        disp_total = reg.gauge("route.pipeline.dispatch_ms_total")
        # nets x windows handed to a cropped rung / to the full canvas
        crop_nets = reg.counter("route.crop.net_dispatches_cropped_total")
        full_nets = reg.counter("route.crop.net_dispatches_full_total")
        # of both, the nets with a terminal on a hard block (0 on a
        # device of identical clusters)
        hard_nets = reg.counter("route.hetero.net_dispatches_hard_total")
        # of both, the nets of a fanout class above the first; and over
        # every net dispatched its sinks and the sink slots its class's
        # tables give it (their ratio is what a batch's S axis holds)
        wide_nets = reg.counter("route.fanout.net_dispatches_wide_total")
        sinks_disp = reg.counter("route.fanout.sinks_dispatched_total")
        slots_disp = reg.counter(
            "route.fanout.sink_slots_dispatched_total")
        # window programs dispatched (one a rung) / conflict colourings
        # read (one a window: the last rung's summary, the one rung that
        # runs _mis_colors; _book_window counts each rung's form)
        mis_calls = reg.counter("route.mis_colors.calls_total")
        mis_reads = reg.counter("route.mis_colors.read_total")
        # the endgame's full rebuilds: finishing passes started, phase-2
        # restarts fired, routes that fell back to the pre-finish
        # snapshot (their windows past it are total_relax_steps_discarded)
        finish_passes = reg.counter("route.endgame.finish_passes_total")
        full_restarts = reg.counter("route.endgame.full_restarts_total")
        finish_restored = reg.counter(
            "route.endgame.finish_restored_total")
        # the plane dtype named by opts.plane_dtype is the dtype every
        # window of this route commits
        pd = str(opts.plane_dtype)
        if pd not in PLANE_DTYPES:
            raise ValueError(
                f"plane_dtype must be one of {PLANE_DTYPES} "
                f"(got {opts.plane_dtype!r})")
        if opts.dtype_guard != "off":
            raise ValueError(
                "dtype_guard must be 'off', its one remaining value "
                f"(got {opts.dtype_guard!r}): plane_dtype names the "
                "dtype that is committed")
        resil_rt = getattr(opts, "resil", None)
        lad = resil_rt.ladder if resil_rt is not None else None
        reg.gauge("route.kernel.plane_dtype").set(pd)
        # cumulative pipeline accounting (drives the
        # route.pipeline.overlap_frac gauge): host seconds spent on
        # plan/stage/bookkeeping work, and the subset performed while
        # device work was in flight
        pl_tot_host = pl_ov_host = 0.0
        pl_exec = pl_stall = pl_serial = 0.0
        t_prev_end = time.perf_counter()
        # donated-buffer graveyard: on XLA:CPU, DELETING an array whose
        # buffer was donated into a still-in-flight execution blocks
        # until that execution completes (the usage hold must resolve) —
        # rebinding `out`/`outs` would silently serialize the pipeline
        # right where it is supposed to overlap.  Old window tuples park
        # here and are released only after the stall, when the in-flight
        # work they were donated into has finished and deletion is free.
        retire = []
        outs = []
        while it_done < opts.max_router_iterations:
            K = self._WINDOWS[min(widx, len(self._WINDOWS) - 1)]
            if (timing_cb is not None and analyzer is None) \
                    or opts.stats_dir:
                # generic host timing callback / per-iteration stats rows
                # need a sync every iteration; the analyzer path instead
                # fuses the STA on device and keeps K>1
                K = 1
            K = min(K, opts.max_router_iterations - it_done)
            widx += 1

            # per-net spans of the window's work set: the larger of the
            # static bb and the MEASURED live bb from the last window's
            # summary (device-side widening feeds the next partition —
            # the measured-cost re-partition analogue, ...cxx:909-916);
            # nets the host widened take full-device spans
            # the work set's nets of the first fanout class, which the
            # crop ladder below bins; the classes above it come first,
            # widest first, on the full canvas (VPR's order: the nets of
            # most sinks are routed first)
            dirty_all = dirty
            wide_rungs = [(dirty[cls_of[dirty] == c], None, c)
                          for c in range(len(classes) - 1, 0, -1)
                          if (cls_of[dirty] == c).any()]
            if wide_rungs:
                dirty = dirty[cls_of[dirty] == 0]
            w_all = np.where(wide[dirty], rr.grid.nx + 2, np.maximum(
                term.bb_xmax[dirty] - term.bb_xmin[dirty] + 1,
                live_w[dirty])) if len(dirty) else np.array([8])
            h_all = np.where(wide[dirty], rr.grid.ny + 2, np.maximum(
                term.bb_ymax[dirty] - term.bb_ymin[dirty] + 1,
                live_h[dirty])) if len(dirty) else np.array([8])

            # size-class crop bucketing (static tiles per compile): bin
            # the window's work set by bb span into pow-2 crop classes
            # (ladder 8, 16, 32, ... clamped at the grid) and dispatch
            # ONE cropped window call per populated class — a 4x4-span
            # net no longer sweeps the worst net's canvas — plus one
            # full-canvas call for whatever fits no rung (device-
            # spanning resets, host-widened boxes): the planes analogue
            # of the ELL path's narrow/wide split, generalized to a
            # ladder.  The ladder is a fixed function of the grid, so
            # the compiled window-program variants stay O(log grid);
            # the unsharded program crops, only the spatial mesh path
            # keeps full canvases (crops are net-local).  dispatch = [(subset, tile or None), ...],
            # smallest tiles first, full canvas last.
            if crop_forced is not None and len(dirty):
                Lm = self.pg.max_span
                narrow = ((w_all + 2 * Lm <= crop_forced[0])
                          & (h_all + 2 * Lm <= crop_forced[1]))
                dispatch = []
                if narrow.any():
                    dispatch.append((dirty[narrow], crop_forced))
                if not narrow.all():
                    dispatch.append((dirty[~narrow], None))
            elif not crop_full and len(dirty):
                Lm = self.pg.max_span
                tiles, assign = _size_class_buckets(
                    w_all + 2 * Lm, h_all + 2 * Lm,
                    rr.grid.nx, rr.grid.ny,
                    min_count=max(1, B // 8))
                dispatch = [(dirty[assign == k], tile)
                            for k, tile in enumerate(tiles)]
                if (assign == len(tiles)).any():
                    dispatch.append((dirty[assign == len(tiles)],
                                     None))
            else:
                dispatch = [(dirty, None)]
            widen_d = (None if opts.sweep_budget_div <= 1
                       else self._staging.put("widen", budget_full))

            # active mesh for this window: the legacy (net, node) GSPMD
            # mesh if constructed with one, else the halo-exchange
            # RowMesh at the resil ladder's current "mesh" level
            # (re-resolved every window so a mid-route demotion takes
            # effect at the next window boundary)
            rm_now = self._active_row_mesh(lad)
            mesh_now = self.mesh if self.mesh is not None else rm_now
            mesh_vk = (False if mesh_now is None
                       else True if rm_now is None
                       else (rm_now.n_shards, rm_now.impl))
            if rm_now is not None:
                # sharded relaxation always runs the full canvas: the
                # crop ladder is single-device VMEM machinery — the
                # row mesh splits the canvas across chips instead
                dispatch = [(dirty, None)]
            # every rung names its fanout class; a first class left
            # with no net beside a wider one dispatches nothing
            dispatch = wide_rungs + [
                (sub, tile, 0) for sub, tile in dispatch
                if len(sub) or not wide_rungs]
            dirty = dirty_all
            for rung_nets, rung_tile, rung_cls in dispatch:
                (full_nets if rung_tile is None
                 else crop_nets).inc(len(rung_nets))
                if term.hard is not None:
                    hard_nets.inc(int(term.hard[rung_nets].sum()))
                if rung_cls:
                    wide_nets.inc(len(rung_nets))
                sinks_disp.inc(int(nsinks_np[rung_nets].sum()))
                slots_disp.inc(len(rung_nets) * widths[rung_cls])
            mis_calls.inc(len(dispatch))

            def plan_rung(sub, tile, ri, fcls):
                """Host planning for one rung of this window's dispatch
                ladder: batch plan, sweep budget, widen gate,
                kernel-layout plan, and the staged device uploads."""
                sel_p, valid_p = self._plan_groups(
                    sub, colors, nsinks_np, cx_np, cy_np, B, R)
                ws = np.where(wide[sub], rr.grid.nx + 2, np.maximum(
                    term.bb_xmax[sub] - term.bb_xmin[sub] + 1,
                    live_w[sub])) if len(sub) else np.array([8])
                hs = np.where(wide[sub], rr.grid.ny + 2, np.maximum(
                    term.bb_ymax[sub] - term.bb_ymin[sub] + 1,
                    live_h[sub])) if len(sub) else np.array([8])
                # lookahead-informed sweep budget (the planes analogue
                # of route_timing.c:753 get_expected_segs_to_target):
                # one min-plus scan pass covers a whole LINE, so the
                # budget counts line moves — segments, not tiles.  On a
                # min-length-L arch the bb needs ~span/L direction
                # changes (+2 end-hop slack); on L=1 archs this reduces
                # exactly to the tile half-perimeter of earlier rounds.
                # Under-budget windows self-heal: unreached sinks stay
                # dirty and sweep_boost doubles.
                wok = widen_d
                if len(sub):
                    lx, ly = self._lmin_seg
                    if lx == 1 and ly == 1:
                        spans_full = ws + hs
                    else:
                        spans_full = -(-ws // lx) + -(-hs // ly) + 2
                    spans = spans_full
                    if opts.sweep_budget_div > 1:
                        # reduced first-try budget; promoted/wide nets
                        # keep the full line-move bound
                        red = np.maximum(8, spans_full
                                         // opts.sweep_budget_div)
                        spans = np.where(budget_full[sub] | wide[sub],
                                         spans_full, red)
                    span = int(spans.max())
                else:
                    span = 8
                # sweep_boost doubles while overuse stalls: a congested
                # detour can need more turns than the bb-span heuristic
                # (the fixed-trip relax has no early exit to lean on).
                # nsw is quantized to the pow-2 ladder {8..128} so the
                # dispatch signature stays canonical (O(log) compiled
                # variants): the budget is a CEILING — the relaxation
                # while_loop exits at its fixpoint — and the widen gate
                # below compares against the same quantized value, so
                # the rounding is result-neutral
                nsw = min(128, _pow2_at_least(max(8, span * sweep_boost)))
                if wok is not None and len(sub):
                    # a net whose DISPATCHED budget covers its full
                    # line-move bound may widen on a miss regardless of
                    # its promotion state (mixed subsets lift everyone
                    # to the max net's budget — denying those widening
                    # would burn a pointless promotion round trip)
                    wok_np = budget_full.copy()
                    wok_np[sub[spans_full <= nsw]] = True
                    wok = self._staging.put(f"wok{ri}", wok_np)
                maxfan = int(nsinks_np[sub].max()) if len(sub) else 1
                # a class above the first keeps the doubling schedule
                # under ``precise`` too: the exact schedule is one
                # relaxation a sink, 204 a batch for a net of 204 sinks
                # (PERF.md PR 37 has what a 13-sinks-a-wave middle cost)
                doubling = opts.sink_group == 0 and (not precise or fcls > 0)
                # the class's width is what Smax was: the most sinks a
                # wave may pick, and the cap of the wave count
                S_c = widths[fcls]
                grp_w = max(1, min(S_c if opts.sink_group == 0
                                   else opts.sink_group, S_c))
                if not doubling and opts.sink_group == 0:
                    grp_w = 1
                # the wave cap is a ceiling too (the wave loop skips
                # once no sinks are pending), so the precise schedule's
                # count also quantizes to pow-2 for free
                waves = (max(1, math.ceil(math.log2(maxfan + 1))) + 1
                         if doubling
                         else min(S_c, _pow2_at_least(
                             math.ceil(maxfan / grp_w) + 1)))
                kplan = self._plan_block_nets(tile, len(sub), nsw,
                                              plane_dtype=pd)
                if rm_now is not None:
                    # per-chip cost truth for devprof + the halo
                    # ledger: bytes one sweep's exchange moves at this
                    # rung's plan width, in the plane storage dtype
                    # (bf16 halves wire traffic like it halves HBM)
                    from .planes_shard import (halo_bytes_per_sweep,
                                               modeled_overlap_frac)
                    bw = sel_p.shape[1] if len(sub) else 1
                    kplan = dict(
                        kplan, mesh_shards=rm_now.n_shards,
                        mesh_impl=rm_now.impl,
                        halo_bytes_per_sweep=halo_bytes_per_sweep(
                            self.pg, bw, rm_now.n_shards, pd),
                        mesh_overlap_frac=modeled_overlap_frac(
                            self.pg, bw, rm_now.n_shards, rm_now.impl,
                            pd))
                # staged, hash-skipped plan uploads: identical plans
                # (endgame windows redispatch the same few dirty nets)
                # reuse the staged device buffer outright, and fresh
                # ones go up with a non-blocking device_put while the
                # previous rung still executes
                sel_d = self._staging.put(f"sel{ri}", sel_p)
                valid_d = self._staging.put(f"valid{ri}", valid_p)
                # ledger: filled batch slots, plan width, and real
                # (non-pad) batch rows of this planned dispatch
                # the class rides in a dispatch key only where the
                # route has classes: one class keeps the parent's keys
                return dict(tile=tile, fcls=fcls,
                            fkey=(fcls,) if fan_d is not None else (),
                            nsw=nsw, waves=waves,
                            grp_w=grp_w, doubling=doubling, wok=wok,
                            sel_d=sel_d, valid_d=valid_d, kplan=kplan,
                            sel_shape=sel_p.shape,
                            ledger=(int((valid_p > 0).sum()),
                                    valid_p.shape[1],
                                    int(valid_p.any(axis=1).sum())))

            def window_call(p, ri):
                """One route_window_planes dispatch of planned rung
                ``p`` (rung ``ri`` of this window's dispatch ladder).
                The first rung alone escalates acc (acc_fac is 0 in the
                others); pres re-escalates identically in every rung,
                so iteration k sees the same pres."""
                # canonical dispatch signature: everything jit traces
                # as a static arg or shape.  New key = a fresh XLA
                # compile (or persistent-cache load); known key = a jit
                # cache hit
                # the guard rides in a key only once it is on: a route
                # that never meets the state keeps the parent's keys
                gkey = ("scan_guard",) if pg_now.scan_guard else ()
                vkey = (p["tile"], K, p["nsw"], L, p["waves"],
                        p["grp_w"], p["doubling"], p["sel_shape"][0],
                        p["sel_shape"][1], p["wok"] is None, mesh_vk,
                        bool(sta_kw), R, Smax, N, pd) + p["fkey"] + gkey
                wp_args = (
                    pg_now, dev, occ, acc, paths, sink_delay,
                    all_reached, bb, source_d, sinks_d, crit_d,
                    *planes_tbl,
                    p["sel_d"], p["valid_d"], full_bb,
                    jnp.float32(pres),
                    jnp.float32(opts.pres_fac_mult),
                    jnp.float32(opts.max_pres_fac),
                    jnp.float32(opts.acc_fac if ri == 0 else 0.0),
                    jnp.int32(it_done),
                    jnp.int32(it_done + 1 if force_all_next
                              else opts.incremental_after),
                    K, p["nsw"], L, p["waves"], p["grp_w"],
                    p["doubling"], min(4096, N), 5,
                    # re-read at every rung: a window whose mesh member
                    # died in an earlier rung must not dispatch the
                    # next onto the dead mesh
                    None if self._mesh_lost else mesh_now)
                wp_kwargs = dict(
                    crop_tile=p["tile"], bb0_all=bb0_d,
                    widen_ok=p["wok"], plane_dtype=pd,
                    # traced, like acc_fac by rung: the host reads the
                    # colours of the window's last rung alone
                    colours_read=jnp.bool_(ri == len(dispatch) - 1),
                    **fan_kw, **sta_kw,
                    **({"fclass": p["fcls"]} if fan_kw else {}))
                # device-truth profiling: avatarize the REAL call args
                # BEFORE the dispatch donates them, so capture_all()
                # can AOT-relower this exact variant later
                get_devprof().note_variant(
                    (p["tile"], K, p["nsw"], L, p["waves"],
                     p["grp_w"]) + p["fkey"] + gkey, p["kplan"],
                    route_window_planes, wp_args, wp_kwargs)
                with dispatching(window=widx, route=rid, rung=ri):
                    if resil_rt is not None \
                            and resil_rt.guard is not None:
                        # guarded dispatch: watchdog + retry/backoff
                        # over a chain of bit-identical rungs (AOT ->
                        # jit), each noting the variant it runs;
                        # injected faults fire before the call so
                        # donated buffers survive retries
                        return self._guarded_dispatch(
                            resil_rt, vkey, wp_args, wp_kwargs)
                    _note_dispatch_variant(vkey)
                    if self._library is not None:
                        # AOT library serve: known variants run from
                        # the deserialized exported executable (no
                        # trace/lower); misses note their avatarized
                        # args for export_program_library() and take
                        # the jit path
                        return self._library.dispatch(
                            vkey, route_window_planes, wp_args,
                            wp_kwargs)
                    return route_window_planes(*wp_args, **wp_kwargs)

            # the window's live span, closed after its stall; opened
            # and closed by hand, as the control span after it is (that
            # one runs into the next turn of the loop).  What
            # the row knows at open goes on the span at open: a
            # TraceAnnotation's args are fixed there, and they are what
            # matches a device trace of a slow window to its kind
            if ctl is not None:
                ctl.__exit__(None, None, None)
            win = span("route.window", cat="route", window=widx,
                       route=rid, first_iter=it_done + 1,
                       last_iter=it_done + K, K=K, kind=kind,
                       nets=len(dirty), precise=precise,
                       sweep_boost=sweep_boost)
            win.__enter__()
            tw0 = time.perf_counter()
            if ctl is not None:
                ctl_s.append(tw0 - t_prev_end)
            else:
                prologue_s = tw0 - t_enter
                if tr is not None:
                    tr.mark("route.prologue", t_enter, tw0, cat="route",
                            route=rid)
            disp0_ms = disp_total.value or 0.0
            # dispatch order: cropped size classes ascending (the first
            # carries the acc escalation), full-canvas remainder last.
            # (A further split by fanout class — per-call num_waves
            # adapts to the subset max — was measured at 600 LUTs and
            # REJECTED: reordering hi-fan nets behind the lo-fan
            # commits diverged the negotiation, 30 iters vs 16 and 2x
            # the relax steps for a 1% wl gain.)  Every call threads
            # the device state to the next; each rung's summary arrays
            # start streaming host-side the moment it is dispatched,
            # and rung i+1 is planned/staged while rung i executes —
            # the pipeline's intra-window overlap
            retire.append(outs)     # keep donated-in refs alive
            outs = []
            bucket_occ = []
            kplans = []
            rung_cells = []       # cells a sweep of the rung covers
            comp_num = comp_den = 0
            plan_s = 0.0          # host plan/stage/dispatch, this window
            plan0_s = 0.0         # rung 0's share (nothing in flight yet)
            t_disp0 = None        # first dispatch return: exec start
            sync_block_s = 0.0    # --sync per-rung drain time
            for ri, (sub0, tile, fcls) in enumerate(dispatch):
                tp0 = time.perf_counter()
                with span("route.pipeline.plan", cat="route",
                          stage="plan", window=widx, route=rid,
                          rung=ri, nets=len(sub0), tile=tile,
                          fanout_class=fcls):
                    p = plan_rung(sub0, tile, ri, fcls)
                o = window_call(p, ri)
                kplans.append(p["kplan"])
                # park the just-donated state refs before
                # rebinding: dropping the last reference to a
                # donated in-flight buffer blocks until its
                # execution completes
                retire.append((occ, acc, paths, sink_delay,
                               all_reached, bb, crit_d))
                occ, acc, paths, sink_delay, all_reached, bb = (
                    o.occ, o.acc, o.paths, o.sink_delay,
                    o.all_reached, o.bb)
                crit_d = o.crit_all
                # start the packed summary copies now: by stall
                # time they are already host-side (replaces the
                # 13-array blocking jax.device_get of the
                # pre-pipeline driver)
                small = (o.status, o.scal, o.dmax_hist) \
                    if analyzer is not None else (o.status, o.scal)
                for a in small:
                    if hasattr(a, "copy_to_host_async"):
                        a.copy_to_host_async()
                tp1 = time.perf_counter()
                plan_s += tp1 - tp0
                if ri == 0:
                    plan0_s = tp1 - tp0
                    t_disp0 = tp1
                if not pipelined:
                    # --sync escape hatch: drain the rung before
                    # ANY further host work, so plan spans can
                    # never overlap device execution
                    # (trace_report --check asserts exactly this)
                    with span("route.pipeline.stall", cat="route",
                              window=widx, route=rid, rung=ri,
                              sync=True):
                        # graftlint: ignore[pipeline-sync] — this
                        # IS the sanctioned --sync drain
                        jax.block_until_ready(o.status)
                    te1 = time.perf_counter()
                    sync_block_s += te1 - tp1
                    reg.counter(
                        "route.pipeline.blocking_syncs").inc()
                    if tr is not None:
                        tr.mark("route.pipeline.exec", tp1, te1,
                                cat="route", window=widx, rung=ri,
                                K=K, pipelined=False)
                outs.append((o, tile))
                nvalid, bg, grows = p["ledger"]
                if tile not in canvas_cells:
                    canvas_cells[tile] = self._canvas_cells(tile)
                rung_cells.append(bg * canvas_cells[tile])
                if grows:
                    bucket_occ.append(nvalid / (grows * bg))
                    comp_num += grows * bg
                    comp_den += grows * B
            rung_scals = [(o2.scal, tc is not None)
                          for o2, tc in outs]
            out = outs[-1][0]
            force_all_next = False
            # one dispatch per populated rung
            reg.gauge("route.kernel.dispatches_per_window").set(
                len(dispatch))

            # ---- overlapped host stage: consume the PREVIOUS window's
            # summary (its bookkeeping was deferred to here, where this
            # window's rungs are in flight on device) ----
            book_s = 0.0
            if book is not None:
                tb0 = time.perf_counter()
                with span("route.pipeline.plan", cat="route",
                          stage="summary", window=book["widx"],
                          route=rid):
                    self._book_window(book, result, mlog)
                book = None
                book_s = time.perf_counter() - tb0

            # ---- stall: block until THIS window's packed summary is
            # host-side (the one blocking point per pipelined window) ----
            t_st0 = time.perf_counter()
            with span("route.pipeline.stall", cat="route", window=widx,
                      route=rid):
                status_np = np.asarray(out.status)  # graftlint: ignore[pipeline-sync]
                scal_np = np.asarray(out.scal)    # graftlint: ignore[pipeline-sync]
                dmax_hist = (np.asarray(out.dmax_hist)  # graftlint: ignore[pipeline-sync]
                             if analyzer is not None
                             else None)
            t_st1 = time.perf_counter()
            win.__exit__(None, None, None)
            # the host's control step: from this window's summary to
            # the next window's first plan (or the route's end) nothing
            # is in flight, so a device gap here is the host's
            ctl = span("route.pipeline.control", cat="route",
                       window=widx, route=rid)
            ctl.__enter__()
            # everything donated into this window has now completed:
            # releasing the graveyard is a plain refcount drop
            del retire[:]
            stall_s = (t_st1 - t_st0) + sync_block_s
            if pipelined:
                exec_s = (t_st1 - t_disp0) if t_disp0 is not None \
                    else 0.0
                serial_s = ((t_disp0 if t_disp0 is not None else t_st1)
                            - t_prev_end)
                reg.counter("route.pipeline.blocking_syncs").inc()
                if tr is not None and t_disp0 is not None:
                    tr.mark("route.pipeline.exec", t_disp0, t_st1,
                            cat="route", window=widx, K=K,
                            rungs=len(outs), pipelined=True)
            else:
                # --sync: the device is busy only inside the per-rung
                # drains; every other moment of the window is host-
                # serialized (plans, bookkeeping, summary fetch)
                exec_s = sync_block_s
                serial_s = (t_st1 - t_prev_end) - sync_block_s
            t_prev_end = t_st1
            # per-window pipeline accounting.  overlap_frac is the
            # pipeline FILL factor — the fraction of the negotiation
            # timeline with device work in flight (1 - host-serialized
            # share); host_overlap_frac is the stricter host-work view:
            # of the host plan/stage/bookkeeping seconds, how many ran
            # while a window executed (rungs>=1 planning + deferred
            # bookkeeping; structurally zero in --sync).
            tot_host_w = plan_s + book_s
            ov_host_w = ((plan_s - plan0_s) + book_s) if pipelined \
                else 0.0
            pl_tot_host += tot_host_w
            pl_ov_host += ov_host_w
            pl_exec += exec_s
            pl_stall += stall_s
            pl_serial += serial_s
            disp_ms = (disp_total.value or 0.0) - disp0_ms
            reg.set_gauges({
                "route.pipeline.dispatch_ms": round(disp_ms, 3),
                "route.pipeline.stall_ms": round(stall_s * 1e3, 3),
                "route.pipeline.overlap_frac": round(
                    pl_exec / max(pl_exec + pl_serial, 1e-9), 4),
                "route.pipeline.host_overlap_frac": round(
                    pl_ov_host / max(pl_tot_host, 1e-9), 4),
                "route.pipeline.host_plan_ms_total": round(
                    pl_tot_host * 1e3, 3),
                "route.pipeline.device_exec_ms_total": round(
                    pl_exec * 1e3, 3),
                "route.pipeline.stall_ms_total": round(
                    pl_stall * 1e3, 3),
                "route.pipeline.host_serial_ms_total": round(
                    pl_serial * 1e3, 3),
            })

            # ---- control: everything below feeds the next dispatch,
            # so it stays at the sync point in BOTH modes (lag-0) ----
            (rrm, colors, dev_wide, unreached, live_w,
             live_h) = unpack_window_status(status_np)
            mis_reads.inc()
            n_over = int(scal_np[SCAL_N_OVER])
            over_total = int(scal_np[SCAL_OVER_TOTAL])
            max_span = int(scal_np[SCAL_MAX_SPAN])
            if opts.sweep_budget_div > 1:
                # reduced-budget promotion: a miss retries at full
                # budget (feature-off runs must not accumulate state —
                # a later resume with div>1 would be pre-promoted)
                budget_full |= unreached
            guarded = pg_now.scan_guard      # as this window ran
            if (not guarded and self.mesh is None
                    and self._row_meshes is None
                    and _scan_guard_due(n_over, bool(unreached.any()),
                                        fin_save is not None,
                                        pg_now.max_span)):
                # a static field: every program up to here is the one a
                # route without the state runs, and the windows from
                # here compile (or load) their guarded twins
                pg_now = pg_now.replace(scan_guard=True)
            crit_d = out.crit_all       # donated in; stays device-resident
            # fold device-side widening into the host classification:
            # those nets must take the full-canvas window from now on
            # (their crop tile covers only their static bb0)
            wide |= dev_wide
            bb_full |= dev_wide
            it_done += K
            cpd = float(dmax_hist[K - 1]) if analyzer is not None \
                else float("nan")
            # deferred bookkeeping record for THIS window (every field
            # a captured value; the per-rung scal vectors are device
            # refs whose async copies completed with the window)
            book = dict(
                widx=widx, it_done=it_done, K=K, n_over=n_over,
                over_total=over_total, ndirty=len(dirty), pres=pres,
                cpd=cpd, tw0=tw0, tw1=t_st1, win_ev=win.event,
                kind=kind, stall_s=stall_s, plan_s=plan_s,
                dispatch_ms=disp_ms, rung_scals=rung_scals,
                rung_cells=rung_cells, scan_guard=guarded,
                bucket_occ=bucket_occ,
                compaction=comp_num / max(1, comp_den), kplans=kplans,
                colors_max=int(np.max(colors) + 1
                               if colors is not None and len(colors)
                               else 0),
                dirty_next=int(rrm.sum()), precise=precise,
                sweep_boost=sweep_boost, widened=result.widened_nets,
                dmax_hist=dmax_hist,
                rung_classes=[c for _, _, c in dispatch],
                sink_cells=sink_cells,
                # occ snapshot for the congestion top-k: inline in
                # --sync (booked before the next dispatch donates the
                # array), a non-donated async-readback copy when
                # pipelined — congestion telemetry no longer requires
                # the synchronous driver
                occ_ref=self._occ_snapshot(occ, pipelined, mlog),
                # mesh ledger state, resolved AFTER the dispatch so a
                # mid-window demotion books as single-chip: (active
                # shards, impl) — (1, "single_chip") when the window
                # ran on one device but sharding was requested, None
                # when mesh_shards was never on
                mesh=(None if self._row_meshes is None
                      else (1, "single_chip")
                      if (rm_now is None or self._mesh_lost)
                      else (rm_now.n_shards, rm_now.impl)))
            win_evs.append(win.event)
            if analyzer is not None and cpd == cpd:
                analyzer.crit_path_delay = cpd
            if not pipelined:
                # synchronous mode keeps the old program order:
                # bookkeeping inline, before the control decisions
                self._book_window(book, result, mlog)
                book = None
            pres = min(opts.max_pres_fac,
                       pres * opts.pres_fac_mult ** K)
            if opts.stats_dir and opts.dump_routes:
                # stats/debug mode only; the sync is the point of it
                self._dump_routes(opts.stats_dir, it_done,
                                  _dense(paths, classes, N), N)  # graftlint: ignore[pipeline-sync]

            if n_over == 0 and not rrm.any():
                # the pass rebuilds the doubling trees one sink a wave:
                # the multi-sink nets of the first fanout class (a wider
                # class keeps the doubling schedule, so the pass has
                # nothing to give it)
                finish_set = (nsinks_np > 1) & (cls_of == 0)
                if (opts.finish_precise and opts.sink_group == 0
                        and not finish_done and not full_reroute_done
                        and finish_set.any()
                        and it_done + 4 < opts.max_router_iterations
                        and sum(int(p.size) for p in
                                jax.tree.leaves(paths)) * 4 <= (1 << 30)):
                    # wirelength finishing pass (see RouterOpts): one
                    # precise reroute of the MULTI-SINK nets (a
                    # single-sink traceback is already an exact path —
                    # only doubling trees carry waste), then back to
                    # legality by the nets that fight alone.  The two
                    # full rebuilds exclude each other: a phase-2
                    # restart already rebuilt every tree precisely, so
                    # it subsumes this, and no restart follows this
                    # (_phase2_restart_due).
                    # Best-effort by construction: the converged state
                    # is snapshotted ON DEVICE (cheap copies; skipped
                    # with the finish at >1 GB path stores) and restored
                    # if re-legalization does not land within budget — a
                    # legal route must never become a reported failure.
                    finish_done = True
                    finish_passes.inc()
                    precise = True
                    force_all_next = True
                    rrm = finish_set
                    fin_save = (occ + 0,
                                jax.tree.map(lambda p: p + 0, paths),
                                jax.tree.map(lambda d: d + 0, sink_delay),
                                all_reached | False, bb + 0, it_done)
                    # fresh plateau state: the cleanup's transient
                    # overuse must not trip the stall valve
                    best_over = 1 << 30
                    stall_windows = 0
                    sweep_boost = 1
                else:
                    result.success = True
                    result.iterations = it_done
                    break

            # path-budget regrowth: device-side widening (unreached
            # sinks get full-device boxes inside _step_core) can outgrow
            # the bb-adaptive L; pad the store and recompile (rare).  A
            # net on a full-device box gets the FULL budget — a
            # congested detour can wind well past 2x the half-perimeter
            if int(max_span) >= rr.grid.nx + rr.grid.ny:
                L_need = L_cap
            else:
                L_need = path_budget(int(max_span), L_cap)
            if L_need > L:
                paths = _grow_paths(paths, L_need, N)
                L = L_need

            # plateau valve at window granularity (…cxx:6238-6267)
            if n_over < best_over:
                best_over = n_over
                stall_windows = 0
                sweep_boost = 1
            else:
                stall_windows += K
                sweep_boost = min(4, sweep_boost * 2)
                precise = True
            if stall_windows >= opts.plateau_iters and n_over > 0:
                stuck = rrm & ~bb_full
                if stuck.any():
                    wide |= stuck
                    bb_full |= stuck
                    result.widened_nets += int(stuck.sum())
                    bb = jnp.where(jnp.asarray(stuck)[:, None],
                                   full_bb[None, :], bb)
                    if L < L_cap:    # full-device boxes need full budget
                        paths = _grow_paths(paths, L_cap, N)
                        L = L_cap
                stall_windows = 0

            dirty = np.where(rrm)[0]
            # endgame: few overused nodes left -> exact sink schedule
            if 0 < n_over <= 8:
                precise = True
            # phase-2 restart (once): a stalled endgame usually means the
            # fast-schedule trees of the CLEAN nets are what the last
            # fighters can't fit around — rip up and re-route EVERYTHING
            # precisely against the accumulated history costs (the
            # reference's congested-mode rebuild, …cxx:6238-6267)
            if _phase2_restart_due(precise, full_reroute_done,
                                   finish_done, n_over, widx):
                dirty = np.arange(R)
                force_all_next = True
                full_reroute_done = True
                full_restarts.inc()
            kind = _window_kind(widx + 1, force_all_next,
                                full_reroute_done, finish_done)
            if it_done < opts.max_router_iterations:
                ctl.set(next_kind=kind)
            if timing_cb is not None and analyzer is None:
                # host timing callback forces K=1 per-iteration sync
                # by design (documented in RouteOpts)
                result.sink_delay = _dense(sink_delay, classes, np.inf)  # graftlint: ignore[pipeline-sync]
                new_crit = np.minimum(np.asarray(
                    timing_cb(result), dtype=np.float32), 0.99)
                if np.array_equal(new_crit, crit):
                    # no slack change: crit_d (the window program
                    # threads crit through unchanged when no device
                    # STA is fused) already holds these values — skip
                    # the [R, Smax] re-upload
                    reg.counter("route.pipeline.crit_upload_skips").inc()
                else:
                    crit = new_crit
                    crit_d = _by_class(crit, classes)

            if next_ckpt is not None and it_done >= next_ckpt:
                # window-boundary snapshot: everything the resume needs
                # to continue this negotiation under any mesh
                # graftlint: ignore[pipeline-sync] — durable snapshot at
                # a window boundary is a sanctioned sync (resil contract)
                a = jax.tree.map(np.asarray, list(jax.device_get(
                    (occ, acc, paths, sink_delay, all_reached, bb,
                     crit_d))))
                fin_ck = None
                if fin_save is not None:
                    # the finishing pass is live: the checkpoint must
                    # carry the pre-finish legal snapshot, or a resumed
                    # run that fails to re-legalize would report
                    # success=False after a legal route existed
                    fin_ck = tuple(jax.tree.map(
                        np.asarray,
                        jax.device_get(fin_save[:5])  # graftlint: ignore[pipeline-sync]
                    )) + (int(fin_save[5]),)
                result.checkpoint = RouteCheckpoint(
                    occ=a[0], acc=a[1], paths=a[2], sink_delay=a[3],
                    all_reached=a[4], bb=a[5], crit=a[6],
                    it_done=it_done, pres=pres,
                    driver=dict(
                        widx=widx, dirty=dirty.copy(),
                        colors=(None if colors is None
                                else np.asarray(colors).copy()),
                        wide=wide.copy(), bb_full=bb_full.copy(),
                        best_over=best_over,
                        stall_windows=stall_windows,
                        sweep_boost=sweep_boost, precise=precise,
                        full_reroute_done=full_reroute_done,
                        force_all_next=force_all_next,
                        finish_done=finish_done,
                        scan_guard=pg_now.scan_guard,
                        budget_full=budget_full.copy(),
                        widened_nets=result.widened_nets,
                        crop_full=crop_full),
                    fin_save=fin_ck)
                next_ckpt = it_done + opts.checkpoint_every
                mlog.log("elastic", event="checkpoint",
                         it_done=it_done, pres=round(pres, 4))
                if yield_at is not None and it_done >= yield_at:
                    # preemption yield: the checkpoint above is the
                    # resume point; the unfinished result reports the
                    # iterations actually spent this slice
                    sliced_yield = True
                    result.iterations = it_done
                    break
        else:
            result.iterations = opts.max_router_iterations
        # the route's epilogue: from the last control step's end (the
        # prologue's, if no window ran) to the return
        t_loop_end = time.perf_counter()
        if ctl is not None:
            ctl.__exit__(None, None, None)
            ctl_s.append(t_loop_end - t_prev_end)
        else:               # no window ran: the prologue is all there is
            prologue_s = t_loop_end - t_enter

        if book is not None:
            # drain the in-flight bookkeeping (loop exited via break or
            # iteration cap with a window's record still pending); runs
            # after the device is idle, so it counts as unoverlapped
            tb0 = time.perf_counter()
            self._book_window(book, result, mlog)
            book = None
            pl_tot_host += time.perf_counter() - tb0
            reg.gauge("route.pipeline.host_overlap_frac").set(round(
                pl_ov_host / max(pl_tot_host, 1e-9), 4))
            reg.gauge("route.pipeline.host_plan_ms_total").set(round(
                pl_tot_host * 1e3, 3))

        fin_it = None
        if not result.success and fin_save is not None \
                and not sliced_yield:
            # the finishing pass could not re-legalize within budget:
            # restore the pre-finish converged (legal) state (a
            # preemption yield instead keeps the in-finish state — the
            # checkpoint carries fin_save and the resume finishes it)
            occ, paths, sink_delay, all_reached, bb, fin_it = fin_save
            result.success = True
            result.iterations = fin_it
            finish_restored.inc()
        # close the ledger: the control step after each window, and
        # whether its result is in the route returned (the rows past a
        # restored snapshot are not: ONE rule for sweeps and seconds)
        for row, ev, c in zip(result.stats, win_evs, ctl_s):
            row.control_s = c
            row.kept = fin_it is None or row.iteration <= fin_it
            if ev is not None:
                ev["args"].update(control_s=c, kept=row.kept)
        gone = [row for row in result.stats if not row.kept]
        result.total_relax_steps_discarded = sum(
            row.relax_steps for row in gone)
        reg.counter("route.window.discarded_seconds_total").inc(
            sum(row.route_time_s for row in gone))
        result.route_id = rid
        result.wall = dict(
            prologue_s=prologue_s,
            windows_s=sum(row.route_time_s for row in result.stats),
            control_s=sum(ctl_s))
        result.wirelength = int(wirelength_on_device(
            dev, jnp.concatenate([p.ravel() for p in paths])
            if isinstance(paths, tuple) else paths))
        # the host's result keeps its shape: [R, Smax] sink delays, and
        # paths that answer paths[r][s] whatever the device's store is
        result.paths = (ClassedPaths(paths, classes, N)
                        if isinstance(paths, tuple) else np.asarray(paths))
        result.sink_delay = _dense(sink_delay, classes, np.inf)
        result.occ = np.asarray(occ)
        self._obs_final(result)
        if opts.stats_dir:
            write_stats_files(opts.stats_dir, result)
            from .report import write_route_report
            import os
            write_route_report(
                os.path.join(opts.stats_dir, "route_report.txt"),
                rr, result.occ, R)
            dp = get_devprof()
            if dp.enabled:
                # device-truth ledger: AOT lower+compile each noted
                # variant (outside every timed window) and dump next
                # to metrics.json / the mdclog files
                dp.capture_all()
                dp.dump(os.path.join(opts.stats_dir, "devprof.json"))
        t_end = time.perf_counter()
        result.wall["epilogue_s"] = t_end - t_loop_end
        if tr is not None:
            tr.mark("route.epilogue", t_loop_end, t_end, cat="route",
                    route=rid)
        return result

    def _planes_terminals(self, term):
        """Device entry tables for ``term`` (planes.PlanesTerminals),
        cached on id(term) across route() calls on the same terminals
        — uploaded once, they stay device-resident.  Returns (sinks,
        the twelve tables, fan): what is dense in the sink axis (the
        sinks, ``sink_uid``, the three ``direct_*``) one table a fanout
        class where ``term`` has several, and ``fan`` = (each net's row
        in its class's tables, each class's nets), None where it has
        one."""
        if getattr(self, "_pt_key", None) != id(term):
            from .planes import build_planes_terminals
            classes = term.fanout_classes
            pt = build_planes_terminals(
                self.rr, term.source, term.sinks,
                np.asarray(self.pg.cell_of_node), self.pg.ncells)
            self._pt = (
                _by_class(term.sinks.astype(np.int32), classes),
                tuple(jnp.asarray(a) for a in (
                    pt.opin_node, pt.entry_cell, pt.entry_oidx,
                    pt.entry_delay))
                + (_by_class(pt.sink_uid, classes),)
                + tuple(jnp.asarray(a) for a in (
                    pt.uid_ucell, pt.uid_upin, pt.uid_pcdel,
                    pt.uid_pcrank))
                + tuple(_by_class(a, classes) for a in (
                    pt.direct_oidx, pt.direct_ipin, pt.direct_delay)),
                None if len(classes) == 1 else (
                    jnp.asarray(term.class_rows()[1].astype(np.int32)),
                    tuple(jnp.asarray(c.nets.astype(np.int32))
                          for c in classes)))
            self._pt_key = id(term)
            self._pt_ref = term          # keep id(term) alive
        return self._pt

    def route(self, term: NetTerminals,
              crit: Optional[np.ndarray] = None,
              timing_cb: Optional[Callable[["RouteResult"], np.ndarray]]
              = None, analyzer=None,
              resume: Optional[RouteCheckpoint] = None) -> RouteResult:
        """Route all nets.  crit [R, Smax] per-sink criticalities (0 =>
        pure congestion-driven).  timing_cb, if given, is called after each
        iteration with the current result and must return updated per-sink
        criticalities (the analyze_timing / update_sink_criticalities hook,
        parallel_route/router.cxx:28,42).

        ``analyzer`` (timing.sta.TimingAnalyzer) is the preferred
        timing-driven hookup: the planes window program fuses the full
        STA on device between iterations (no host sync per iteration,
        K>1 windows); for the ELL program it degrades to the per-
        iteration host callback."""
        if analyzer is not None and self.pg is None and timing_cb is None:
            timing_cb = analyzer.timing_cb
        if resume is not None and self.pg is None:
            raise ValueError("resume is supported by the planes program")
        opts = self.opts
        # the route's wall opens here: its prologue is this set-up and
        # the window loop's, up to the first window
        t_enter = time.perf_counter()
        # multi-route safety (the serve loop calls route() many times
        # on one process): zero the per-route pipeline gauges so a job
        # that never reaches a given gauge doesn't inherit the previous
        # job's value.  The dispatch-variant seen-set is process state
        # on purpose and is NOT reset: warm variants stay warm.
        get_metrics().set_gauges(dict.fromkeys(_PIPELINE_GAUGES, 0.0))
        # normalized into a LOCAL — never mutate the caller's
        # RouterOpts (the same opts object may drive several routers,
        # and the caller may compare it against what it passed in)
        crop = normalize_crop(opts.crop)
        rr, dev = self.rr, self.dev
        R, Smax = term.sinks.shape
        N = rr.num_nodes
        B = min(opts.batch_size, max(1, R))
        if self.mesh is not None and B % self._net_axis:
            # batch must tile the net axis evenly
            B = ((B + self._net_axis - 1) // self._net_axis) * self._net_axis

        if crit is None:
            crit = np.zeros((R, Smax), dtype=np.float32)
        else:
            # max_criticality clamp (VPR --max_criticality 0.99): crit of
            # exactly 1 zeroes the congestion term and kills negotiation
            crit = np.minimum(np.asarray(crit, dtype=np.float32), 0.99)

        # host<->device transfers are the scarce resource, so every
        # whole-circuit array lives on device for the entire call; the
        # host loop moves net indices in and scalars out (search.py
        # "device-resident stepping")
        occ = self._put_node(jnp.zeros(N, dtype=jnp.int32))
        acc = self._put_node(jnp.ones(N, dtype=jnp.float32))
        # bb-adaptive path-slot budget: a bb-confined path needs ~2x the
        # box half-perimeter, not the device half-perimeter — the dense
        # [R, Smax, L] store's L term shrinks to the circuit's largest
        # box (the Titan-scale memory fix, BENCHMARKS.md memory model).
        # Bucketed to 64 to bound compile variants; regrown on demand
        # when negotiation widens boxes past the budget (rare event,
        # host-side pad + recompile).
        if R:
            span0 = int(((term.bb_xmax - term.bb_xmin)
                         + (term.bb_ymax - term.bb_ymin)).max())
        else:
            span0 = 8
        L = path_budget(span0, self.max_len)
        if resume is None:
            all_reached = jnp.zeros(R, dtype=bool)
            bb = jnp.asarray(np.stack(
                [term.bb_xmin, term.bb_xmax, term.bb_ymin, term.bb_ymax],
                axis=1).astype(np.int32))
        full_bb = jnp.asarray(np.array(
            [0, rr.grid.nx + 1, 0, rr.grid.ny + 1], dtype=np.int32))
        source_d = jnp.asarray(term.source.astype(np.int32))
        nsinks_np = term.num_sinks.astype(np.int64)
        cx_np = ((term.bb_xmin + term.bb_xmax) // 2).astype(np.int64)
        cy_np = ((term.bb_ymin + term.bb_ymax) // 2).astype(np.int64)
        result = RouteResult(False, 0, None, None, None, 0)

        if self.pg is not None:
            # the planes program: the window loop (_route_planes_windows)
            rid = next(_ROUTE_IDS)      # shared by every span of this route
            # the path store and the sink delays: one table a fanout
            # class ([R_c, S_c, L]; the bare array where there is one)
            classes = term.fanout_classes
            if resume is None:
                def store(tail, fill, dtype):
                    t = tuple(jnp.full((len(c.nets), c.width) + tail, fill,
                                       dtype=dtype) for c in classes)
                    return t[0] if len(t) == 1 else t
                paths = store((L,), N, jnp.int32)
                sink_delay = store((), jnp.inf, jnp.float32)
            else:
                # re-upload the checkpointed negotiation under THIS mesh
                # (elastic shrink/grow: the sharding comes from this
                # Router's layout, not the checkpoint's origin); no fresh
                # allocation — the checkpoint IS the path store
                occ = self._put_node(jnp.asarray(resume.occ))
                acc = self._put_node(jnp.asarray(resume.acc))
                paths = jax.tree.map(jnp.asarray, resume.paths)
                crit = resume.crit
                sink_delay = jax.tree.map(jnp.asarray, resume.sink_delay)
                all_reached = jnp.asarray(resume.all_reached)
                bb = jnp.asarray(resume.bb)
            sinks_d, planes_tbl, fan_d = self._planes_terminals(term)
            # structured per-(window, category) logging (zlog/MDC
            # equivalent): no-op unless a stats_dir sink is configured
            from ..mdclog import MdcLogger
            tr = get_tracer()
            if opts.stats_dir:
                # a stats_dir run is the diagnostics mode: the device-
                # truth profiler rides along and dumps devprof.json
                get_devprof().enabled = True
            with MdcLogger(opts.stats_dir,
                           t0=tr.t0 if tr is not None else None) as mlog:
                return self._route_planes_windows(
                    term, crit, timing_cb, analyzer, occ, acc, paths,
                    sink_delay, all_reached, bb, full_bb, source_d,
                    sinks_d, planes_tbl, nsinks_np, cx_np, cy_np,
                    result, B, mlog, crop=crop, resume=resume, rid=rid,
                    t_enter=t_enter, fan_d=fan_d)

        # the ELL program (never resumed): dense stores
        paths = jnp.full((R, Smax, L), N, dtype=jnp.int32)
        sink_delay = jnp.full((R, Smax), jnp.inf, dtype=jnp.float32)
        sinks_d = jnp.asarray(term.sinks.astype(np.int32))

        # --- bb-windowed search setup (VPR's per-net boxes as gathered
        # fixed-size windows; search.py "Bounding-box-windowed search") ---
        win = None
        lb_scale = None
        wide = np.zeros(R, dtype=bool)   # nets routed in global space
        bb_full = np.zeros(R, dtype=bool)  # nets already on full-device bb
        win_row = None                   # net id -> compacted table row
        if opts.windowed:
            # chunk over nets: window_sizes/build_windows hold an
            # [chunk, N] membership intermediate — unchunked that is
            # R x N and OOMs Titan-class graphs during setup
            chunk = max(1, int(2e8) // max(1, N))
            sizes = np.concatenate(
                [np.asarray(window_sizes(dev, bb[lo:lo + chunk]))
                 for lo in range(0, R, chunk)])
            # a handful of device-spanning nets (resets, very high
            # fanout) must not disable windowing for everyone: they are
            # born wide and take the global program; the tables are built
            # ONLY for the windowable nets (compacted rows), so dead
            # device-spanning rows neither allocate nor count against
            # the byte budget
            small = sizes < opts.window_max_frac * N
            small_idx = np.where(small)[0]
            nbox = int(_pow2_at_least(
                max(1, int(sizes[small].max())))) if small.any() else N
            tbl_bytes = len(small_idx) * nbox * dev.max_in_degree * 9
            if small.any() and tbl_bytes <= opts.window_max_bytes:

                wide = ~small
                bb_small = bb[jnp.asarray(small_idx)]
                parts = [build_windows(dev, bb_small[lo:lo + chunk], nbox)
                         for lo in range(0, len(small_idx), chunk)]
                win = (parts[0] if len(parts) == 1 else jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *parts))
                win_row = np.full(R, 0, dtype=np.int32)
                win_row[small_idx] = np.arange(len(small_idx),
                                               dtype=np.int32)
                lb_scale = jnp.asarray(self._lb_scale(),
                                       dtype=jnp.float32)

        pres_fac = opts.initial_pres_fac
        if win is not None:
            result.windowed_nets = int((~wide).sum())
        n_over = -1                      # previous iteration's overuse
        crit_d = None                    # uploaded once; refreshed on cb
        L_e = int(paths.shape[2])        # bb-adaptive path budget
        L_cap = self.max_len
        stall = 0                        # phase-two plateau counter
        best_over = 1 << 30              # best overuse seen so far
        rrm = np.ones(R, dtype=bool)     # reroute mask from last summary
        steps_dev = jnp.int32(0)         # lazy device-side step counter
        prev_steps = 0

        for it in range(1, opts.max_router_iterations + 1):
            tw0 = time.perf_counter()
            if it <= opts.incremental_after:
                idx = np.arange(R)
            else:
                idx = np.where(rrm)[0]

            if it > 1 and len(idx) > 1 and n_over > 0:
                I = _pow2_at_least(len(idx))
                # cap at N: lax.top_k rejects k > dimension size
                K = min(_pow2_at_least(min(max(n_over, 1), 4096)), N)
                idx_pad = _pad_to(idx.astype(np.int32), I, -1)
                conflict = np.asarray(conflict_subset(
                    dev, occ, paths, jnp.asarray(idx_pad), K))
                groups = _color_schedule(idx, conflict[:len(idx), :len(idx)])
            else:
                groups = [idx]
            # batch formation: fanout classes keep the wave loop tight
            # (peers finish their sinks together), spatial round-robin
            # inside a class spreads each batch's nets across the device
            # so concurrent commits rarely contend; the class streams are
            # concatenated descending-fanout and chunked ONCE, so class
            # boundaries never multiply dispatches.  Nets whose bb was
            # widened to the full device can't use the windows and go
            # through the global-space program in separate batches.
            batches = []
            for g in groups:
                parts = ((g[~wide[g]], g[wide[g]]) if win is not None
                         else (g,))
                for gp in parts:
                    batches.extend(_order_and_chunk(
                        gp, nsinks_np, cx_np, cy_np, B))

            # one static wave cap for every batch: the wave loop is a
            # device while_loop that exits early once all sinks are done,
            # so the full Smax cap costs nothing, every batch shares one
            # program, and a group-picked-but-failed sink always has
            # enough waves left to retry (sink_group > 1 with a
            # ceil(Smax/group) cap could exhaust waves with sinks
            # unreached and permanently widen the net)
            waves = max(1, Smax)
            grp = Smax if opts.sink_group == 0 else opts.sink_group
            grp = max(1, min(grp, Smax))
            if crit_d is None:
                crit_d = jnp.asarray(crit)
            for sel in batches:
                if len(sel) == 0:
                    continue
                nsel = len(sel)
                b_valid = np.zeros(B, dtype=bool)
                b_valid[:nsel] = True
                sel_d = self._put_batch(_pad_to(sel.astype(np.int32), B, 0))
                valid_d = self._put_batch(b_valid)
                # fused rip-up + route + commit + scatter-back, one device
                # dispatch; each net is costed against the occupancy of
                # *everyone else* (serial rip-up-one-net-at-a-time view,
                # route_timing.c:399)
                if win is not None and not wide[sel[0]]:
                    selw_d = self._put_batch(_pad_to(
                        win_row[sel].astype(np.int32), B, 0))
                    # audited (search.py donate wrappers): rebinding the
                    # donated tuple here drops the old buffers into the
                    # just-dispatched execution — a bounded retire stall.
                    # This legacy batched path is synchronous by design
                    # (iteration_summary is device_get'd every
                    # iteration), so there is no pipeline to protect and
                    # a retire list would only delay the same wait
                    # (grandfathered in analysis/baseline.json).
                    (paths, sink_delay, all_reached, occ,
                     steps) = route_batch_resident_win(
                        dev, win, occ, acc, jnp.float32(pres_fac),
                        paths, sink_delay, all_reached,
                        source_d, sinks_d, crit_d, sel_d, selw_d,
                        valid_d, lb_scale,
                        self.max_len, L_e, waves, grp, self.mesh)
                else:
                    # same bounded retire stall as the windowed branch
                    # above; the serial dependency chain (occ feeds the
                    # next dispatch) retires each execution anyway
                    # (grandfathered in analysis/baseline.json).
                    (paths, sink_delay, all_reached, bb, occ,
                     steps) = route_batch_resident(
                        dev, occ, acc, jnp.float32(pres_fac),
                        paths, sink_delay, all_reached, bb,
                        source_d, sinks_d, crit_d, sel_d, valid_d, full_bb,
                        self.max_len, L_e, waves, grp, self.mesh)
                steps_dev = steps_dev + steps
                result.total_net_routes += nsel

            # ONE device->host fetch per iteration: reroute mask for the
            # next iteration, reached flags, overuse summary, lazy step
            # counter (per-read round trips dominate small-circuit
            # iteration time otherwise)
            rrm, ar, n_over, over_total, st_tot = (
                np.asarray(v) for v in jax.device_get(iteration_summary(
                    dev, occ, paths, all_reached, steps_dev)))
            n_over, over_total = int(n_over), int(over_total)
            it_steps = int(st_tot) - prev_steps
            prev_steps = int(st_tot)

            # a net that failed a sink gets the full device next time
            # (place_and_route.c bb relaxation); it leaves the windowed
            # program for good — its window no longer matches its bb
            # ANY unreached sink (including born-wide nets, whose wide
            # flag predates this iteration) means a full-device search
            # comes next: give the path store the full budget
            if (~ar).any() and L_e < L_cap:
                paths = _grow_paths(paths, L_cap, N)
                L_e = L_cap
            newly_wide = ~ar & ~wide
            if newly_wide.any():
                wide |= newly_wide
                bb_full |= newly_wide
                result.widened_nets += int(newly_wide.sum())
                bb = jnp.where(jnp.asarray(newly_wide)[:, None],
                               full_bb[None, :], bb)

            # phase-two safety valve (…cxx:6238-6267): only a genuine
            # stagnation trips it — ANY new best overuse resets the
            # counter, so steadily converging runs never see the
            # widening cliff; plateau_iters iterations without a new
            # best is stagnation
            if n_over < best_over:
                stall = 0
                best_over = n_over
            elif n_over > 0:
                stall += 1
            if stall >= opts.plateau_iters and n_over > 0:
                # widen every congested net not already on a full-device
                # bb — including born-wide nets, whose ORIGINAL box may
                # be what is blocking the detour
                stuck = rrm & ~bb_full
                if stuck.any():
                    wide |= stuck
                    bb_full |= stuck
                    result.widened_nets += int(stuck.sum())
                    bb = jnp.where(jnp.asarray(stuck)[:, None],
                                   full_bb[None, :], bb)
                    if L_e < L_cap:
                        paths = _grow_paths(paths, L_cap, N)
                        L_e = L_cap
                stall = 0
            result.total_relax_steps += it_steps
            # the ELL program has no per-sweep convergence measurement:
            # its steps count as useful so the ledger invariant
            # (useful + wasted == total) holds across both programs
            result.total_relax_steps_useful += it_steps
            result.stats.append(RouteStats(
                it, n_over, over_total, len(idx),
                time.perf_counter() - tw0,
                relax_steps=it_steps, batches=len(batches),
                overuse_pct=100.0 * n_over / max(1, N)))
            self._obs_window(tw0, it, 1, n_over, over_total, len(idx),
                             it_steps, pres_fac, float("nan"),
                             len(batches))

            if opts.stats_dir and opts.dump_routes:
                self._dump_routes(opts.stats_dir, it, np.asarray(paths), N)

            if n_over == 0 and bool(ar.all()):
                result.success = True
                result.iterations = it
                break

            # pathfinder history/present update (congestion.h:177-193),
            # computed on device so sharded acc never leaves the mesh
            acc = acc + opts.acc_fac * jnp.maximum(
                occ - dev.capacity, 0).astype(jnp.float32)
            pres_fac = min(opts.max_pres_fac, pres_fac * opts.pres_fac_mult)

            if timing_cb is not None:
                result.sink_delay = np.asarray(sink_delay)
                new_crit = np.minimum(
                    np.asarray(timing_cb(result), dtype=np.float32), 0.99)
                if np.array_equal(new_crit, crit):
                    # no slack change: keep the device-resident copy
                    # instead of re-uploading [R, Smax] every iteration
                    get_metrics().counter(
                        "route.pipeline.crit_upload_skips").inc()
                else:
                    crit = new_crit
                    crit_d = None        # re-upload next iteration
        else:
            result.iterations = opts.max_router_iterations

        result.wirelength = int(wirelength_on_device(dev, paths))
        result.paths = np.asarray(paths)
        result.sink_delay = np.asarray(sink_delay)
        result.occ = np.asarray(occ)
        self._obs_final(result)
        if opts.stats_dir:
            write_stats_files(opts.stats_dir, result)
            from .report import write_route_report
            import os
            write_route_report(
                os.path.join(opts.stats_dir, "route_report.txt"),
                rr, result.occ, R)
        return result
