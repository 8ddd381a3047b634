"""Row-sharded planes relaxation: halo exchange over a 1-D device mesh.

The multi-chip translation of the reference's distributed-memory
spatial router (rr_graph_partitioner.h:840 + the mpi_spatial_route*
workers exchanging boundary state): the [B, W, X, Y] relaxation
canvases are split along the canvas row (x) axis into one contiguous
column block per device, and the ONLY cross-device traffic per sweep is
the halo columns each block shares with its neighbors — the planes
analogue of the reference's boundary-node messages (route.h:330-365).

Block layout (kx owned columns per shard, PX = n_shards * kx >= NX+2):

    chanx block:  [B, W, kx+2, NY+1]   local col 0 / kx+1 = halo
    chany block:  [B, W, kx+3, NY]     local col 0 = left halo,
                                       kx+1..kx+2 = right halo slab

The chany right halo is a 2-column slab because the turn fold into a
chanx column u reads chany columns {u, u+1}: the last owned chanx
column needs one chany column past the boundary, and the halo chany
column itself is rebuilt from the NEXT shard's turn fold, which read
one more.  Everything outside the real canvas (global pad columns,
and the one-column borders) is INERT: break masks True, endpoint masks
False, congestion INF — a pad cell's scan-entry cost and every turn
candidate into it are INF, so pad distances stay INF by induction and
nothing leaks back into the real canvas.

Per sweep, each shard ships ONLY the dist halo columns (4 ppermutes:
dx left/right 1 column, dy left 1 / right 2).  The pred and wenter
payloads need no exchange: scan preds are computed from the improved
cell's OWN global id +- stride, turn preds come from the (static)
global-id canvases, and wenter comes from the delay canvases — none
ever read a neighbor's payload value.  Convergence is decided by a
global reduce: each shard's "some owned distance improved" flag is
psum'd, so the bounded ``lax.while_loop`` exits on the SAME trip on
every device and the early exit stays exact (owned cells are monotone
non-increasing; if no owned cell changed globally, next sweep's halos
are identical and every further sweep is an identity).

Two transport implementations ride the resil ladder's "mesh" rungs:

* ``impl="ppermute"`` — the XLA rung: halos exchanged at the top of
  each sweep via ``jax.lax.ppermute`` (non-wrapping; edge shards mask
  the zero-filled unreceived halos back to INF).  Sweep t consumes
  halos from the end of sweep t-1 — the exchange is on the critical
  path.
* ``impl="pallas_halo"`` — the overlapped rung: halos are used with
  LAG 2 (sweep t consumes boundary columns produced at the end of
  sweep t-2), so the transfer issued right after sweep t-1's columns
  exist has ALL of sweep t's compute to hide behind.  On TPU the
  transport is remote_slab_permute below (double-buffered
  ``pltpu.make_async_remote_copy`` neighbor sends); elsewhere the same
  lag-2 schedule runs over ppermute so the rung's numerics are
  CI-testable.  Lag-2 staleness means one globally-stable sweep no
  longer proves the fixpoint — the loop exits after TWO consecutive
  stable sweeps: stable at t-1 and t means owned(t)=owned(t-1)=
  owned(t-2), so sweep t+1 sees exactly sweep t's inputs and is an
  identity, and so on forever.

Both rungs relax to the same fixpoint as the single-device program in
exact arithmetic (same monotone operator, halos are always previously
committed distances).  Truncating the min-plus associative scans at
block boundaries regroups the float reductions, so distances can
differ from the single-device program by ulps (measured ~2e-16 max).
The parity surface is therefore tiered:

* kernel level — dist/wenter BIT-IDENTICAL whenever the cost sums are
  float-exact (tests use power-of-two congestion), for every impl,
  shard count, and plane dtype;
* route level, bench config — BIT-IDENTICAL paths/occ/wirelength
  (CI mesh-smoke + tests/test_planes_shard.py): the router's
  deterministic per-(net,node) jitter separates equal-cost ties by
  far more than scan-regrouping noise, and on bench-scale negotiation
  no near-tie falls inside the ulp band;
* route level, large circuits — a 22-iteration 200-LUT negotiation
  was measured to amplify one ulp-flipped path choice into ~1.4%
  wirelength drift (legal, converged, same iteration count class).
  ``scale_bench.py --mesh`` measures and reports ``bit_identical``
  per run rather than assuming it; runs that must be bit-exact at any
  scale should shard a dimension that does not split the scan axis
  (the batch axis), or quantize costs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .planes import (INF, PlanesGeom, PlanesGraph, _dequantize_plane_state,
                     _sweep_costs, _sweep_once, plane_itemsize,
                     quantize_plane_state)

ROW_AXIS = "row"

# ceiling on the inflated sweep budget: information crosses one shard
# boundary per sweep, so a path spanning m blocks needs up to m extra
# sweeps — nsweeps * n_shards, capped (the fixpoint early-exit keeps
# the real trip count near the single-device one)
MAX_SHARD_SWEEPS = 512

MESH_IMPLS = ("ppermute", "pallas_halo")


@dataclasses.dataclass(frozen=True)
class RowMesh:
    """Hashable handle for the row-sharded relaxation: rides the
    existing ``mesh`` static argname through route_window_planes ->
    _step_core -> the relax dispatch, so the whole window program
    re-jits per (mesh, impl) variant."""
    mesh: Mesh
    n_shards: int
    impl: str = "ppermute"

    def __post_init__(self):
        if self.impl not in MESH_IMPLS:
            raise ValueError(f"RowMesh impl must be one of {MESH_IMPLS}, "
                             f"got {self.impl!r}")
        if self.n_shards < 2:
            raise ValueError(f"RowMesh needs >= 2 shards, got "
                             f"{self.n_shards} (use mesh=None for "
                             f"single-device)")

    def with_impl(self, impl: str) -> "RowMesh":
        return dataclasses.replace(self, impl=impl)


def make_row_mesh(n_shards: int, impl: str = "ppermute",
                  devices=None) -> RowMesh:
    """1-D ("row",) mesh over the first ``n_shards`` devices."""
    devs = list(jax.devices()) if devices is None else list(devices)
    if n_shards < 2:
        raise ValueError(f"n_shards must be >= 2, got {n_shards}")
    if len(devs) < n_shards:
        raise ValueError(
            f"mesh_shards={n_shards} but only {len(devs)} device(s) "
            f"are visible; on CPU hosts set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before jax initializes")
    return RowMesh(Mesh(np.array(devs[:n_shards]), (ROW_AXIS,)),
                   n_shards, impl)


def row_block_cols(pg: PlanesGraph, n_shards: int) -> int:
    """Owned canvas columns per shard (kx).  The padded extent
    PX = n_shards * kx covers the real chanx extent NX plus the chany
    extent NX+1 plus one border, and kx >= 2 so the 2-column chany
    halo slab always lands on owned columns of one neighbor."""
    W, NX, NYp1 = pg.shape_x
    return max(2, -(-(NX + 2) // n_shards))


def halo_bytes_per_sweep(pg: PlanesGraph, batch: int, n_shards: int,
                         plane_dtype: str = "f32") -> int:
    """Modeled interconnect bytes ONE sweep's halo exchange moves:
    per internal boundary, 2 dx columns ([B, W, NY+1]) + 3 dy columns
    ([B, W, NY]), in the plane storage dtype — only dist is exchanged
    (pred/wenter halos are never read), so bf16 planes halve the wire
    traffic exactly as they halve HBM traffic."""
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    cells = batch * W * (2 * NYp1 + 3 * NY)
    return (n_shards - 1) * cells * plane_itemsize(plane_dtype)


def modeled_overlap_frac(pg: PlanesGraph, batch: int, n_shards: int,
                         impl: str, plane_dtype: str = "f32") -> float:
    """Modeled fraction of the halo-exchange time hidden behind sweep
    compute.  The ppermute rung exchanges on the critical path (0.0).
    The lag-2 rung's transfer has one full sweep of compute to land
    behind; it is fully hidden when the per-boundary DMA time fits in
    a sweep, estimated by byte volume: a sweep touches every canvas
    cell a handful of times while a boundary ships 5 columns, so the
    hide saturates long before real grids get interesting."""
    if impl != "pallas_halo" or n_shards < 2:
        return 0.0
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    # per-shard per-sweep touched bytes vs per-boundary shipped bytes,
    # scaled by the ICI:HBM bandwidth ratio (~1:10 on current parts)
    sweep_bytes = batch * W * (NX * NYp1 + NXp1 * NY) \
        * plane_itemsize(plane_dtype) / n_shards
    halo_bytes = halo_bytes_per_sweep(pg, batch, n_shards, plane_dtype) \
        / max(1, n_shards - 1)
    ici_hbm_ratio = 10.0
    return round(min(1.0, sweep_bytes / max(1.0, halo_bytes
                                            * ici_hbm_ratio)), 6)


def _pad_cols(a, left: int, total: int, fill):
    """Pad the canvas x axis (axis -2) with ``left`` fill columns
    before and out to ``total`` columns."""
    pads = [(0, 0)] * a.ndim
    pads[-2] = (left, total - left - a.shape[-2])
    return jnp.pad(a, pads, constant_values=fill)


def _stack_blocks(a, s: int, kx: int, ext: int):
    """[..., PXpad, Y] -> [s, ..., ext, Y]: block i spans padded
    columns i*kx .. i*kx+ext (owned = local 1..kx)."""
    return jnp.stack([a[..., i * kx:i * kx + ext, :] for i in range(s)])


def _geom_blocks(pg: PlanesGraph, s: int, kx: int) -> PlanesGeom:
    """Per-shard sweep geometry, stacked on a leading [s] axis: the
    global masks/delays padded with inert columns (breaks True,
    endpoints False) and sliced into overlapping blocks, plus global
    flat-id and parity canvases computed from the padded positions so
    preds and rotated-turn parity stay exact under sharding."""
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    PX = s * kx
    ncx = W * NX * NYp1
    ext_x = kx + 2
    ext_y = kx + 3

    def pad_x(a, fill):
        return _pad_cols(a, 1, PX + 2, fill)

    def pad_y(a, fill):
        return _pad_cols(a, 1, PX + 3, fill)

    def bx(a, fill):            # chanx-extent field -> [s, 1, W, ext_x, .]
        return _stack_blocks(pad_x(a, fill), s, kx, ext_x)[:, None]

    def by(a, fill):
        return _stack_blocks(pad_y(a, fill), s, kx, ext_y)[:, None]

    # global flat ids at padded positions (real col = position - 1;
    # pad positions clamp into range — their cells stay at INF so the
    # ids never surface in an owned pred)
    gx = jnp.clip(jnp.arange(PX + 2) - 1, 0, NX - 1)
    idxx_pad = ((jnp.arange(W)[:, None] * NX + gx[None, :]) * NYp1
                )[:, :, None] + jnp.arange(NYp1)[None, None, :]
    gy = jnp.clip(jnp.arange(PX + 3) - 1, 0, NXp1 - 1)
    idxy_pad = ncx + ((jnp.arange(W)[:, None] * NXp1 + gy[None, :]) * NY
                      )[:, :, None] + jnp.arange(NY)[None, None, :]
    # global corner parity (x + y) % 2 at padded-y positions
    par_pad = ((jnp.arange(PX + 3) - 1)[:, None]
               + jnp.arange(NYp1)[None, :]) % 2

    return PlanesGeom(
        brk_before_x=bx(pg.brk_before_x, True),
        brk_after_x=bx(pg.brk_after_x, True),
        brk_before_y=by(pg.brk_before_y, True),
        brk_after_y=by(pg.brk_after_y, True),
        first_x=bx(pg.first_x, False), last_x=bx(pg.last_x, False),
        first_y=by(pg.first_y, False), last_y=by(pg.last_y, False),
        delay_x=bx(pg.delay_x, 0.0), delay_y=by(pg.delay_y, 0.0),
        delay_y_rot0=by(pg.delay_y_rot0, 0.0),
        delay_y_rot1=by(pg.delay_y_rot1, 0.0),
        idxx=_stack_blocks(idxx_pad.astype(jnp.int32), s, kx,
                           ext_x)[:, None],
        idxy=_stack_blocks(idxy_pad.astype(jnp.int32), s, kx,
                           ext_y)[:, None],
        base_par=_stack_blocks(par_pad, s, kx, ext_y)[:, None],
        stride_x=NYp1, directional=pg.directional,
        inc_track=(jnp.broadcast_to(pg.inc_track,
                                    (s,) + pg.inc_track.shape)
                   if pg.inc_track is not None else None),
        group_tracks=pg.group_tracks)


def remote_slab_permute(slab, axis_name, n_shards, fwd=True):
    """Halo-slab neighbor exchange over the TPU interconnect (RDMA).

    Transport for the mesh ladder's top rung ("pallas_halo"): inside
    planes_relax_sharded's shard_map body each device
    pushes its boundary dist slab ([B, W, 1-or-2, Y]) directly into the
    neighbor's output buffer with ``pltpu.make_async_remote_copy`` —
    a one-hop ICI DMA instead of the collective-scheduled
    ``lax.ppermute`` the middle rung uses.  The overlap itself lives in
    the lag-2 schedule below: the halo installed before sweep k
    was extracted before sweep k-1 ran, so two exchange generations are
    in flight at once and this DMA hides behind the interior sub-sweep
    (route.mesh.overlap_frac models the hide).

    Semantics match the non-wrapping ``lax.ppermute`` shift exactly:
    ``fwd=True`` sends shard i -> i+1 (the last shard sends nothing),
    ``fwd=False`` sends i -> i-1 (the first sends nothing), and an edge
    shard with no inbound neighbor returns zeros — planes_shard masks
    those halos to +inf by row index, so the two transports stay
    bit-identical and rung demotion cannot move QoR.

    TPU-only (callers gate on ``jax.default_backend() == "tpu"``): the
    remote-DMA primitives have no interpret-mode lowering, so on CPU
    hosts the ppermute rung is the top of the mesh ladder.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis_name)
        if fwd:
            neighbor, sender = me + 1, me - 1
            sends, recvs = me < n_shards - 1, me > 0
        else:
            neighbor, sender = me - 1, me + 1
            sends, recvs = me > 0, me < n_shards - 1
        logical = pltpu.DeviceIdType.LOGICAL
        # handshake: a receiver tells its sender it has entered the
        # kernel (its output buffer is live) before the sender pushes
        # into it; each sender consumes exactly the one signal it is
        # sent, so the barrier semaphore is back at zero on exit
        barrier = pltpu.get_barrier_semaphore()

        @pl.when(recvs)
        def _ready():
            pltpu.semaphore_signal(barrier, 1, device_id=sender,
                                   device_id_type=logical)

        @pl.when(sends)
        def _await_ready():
            pltpu.semaphore_wait(barrier, 1)

        copy = pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=o_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=neighbor, device_id_type=logical)

        @pl.when(jnp.logical_not(recvs))
        def _zero_edge():
            o_ref[...] = jnp.zeros_like(o_ref[...])

        @pl.when(sends)
        def _start():
            copy.start()

        @pl.when(sends)
        def _wait_send():
            copy.wait_send()

        @pl.when(recvs)
        def _wait_recv():
            copy.wait_recv()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(slab.shape, slab.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            # fwd/bwd exchanges of one sweep overlap; distinct barrier
            # semaphores keep their handshakes separate
            collective_id=0 if fwd else 1,
        ),
    )(slab)


def planes_relax_sharded(pg: PlanesGraph, d0_flat, cc_flat, crit_c,
                         wenter0, nsweeps: int, rmesh: RowMesh,
                         plane_dtype: str = "f32"):
    """planes_relax, spatially sharded over ``rmesh``: same signature
    contract — (dist_flat, pred_flat, wenter_flat, stats) — with every
    device relaxing its own column block and exchanging halo columns
    per sweep (see module docstring for layout and exactness)."""
    B = d0_flat.shape[0]
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1
    s = rmesh.n_shards
    kx = row_block_cols(pg, s)
    PX = s * kx
    nsw_cap = int(min(MAX_SHARD_SWEEPS, max(nsweeps, nsweeps * s)))
    lag2 = rmesh.impl == "pallas_halo"

    dx0 = d0_flat[:, :ncx].reshape(B, W, NX, NYp1)
    dy0 = d0_flat[:, ncx:].reshape(B, W, NXp1, NY)
    cc_x = cc_flat[:, :ncx].reshape(B, W, NX, NYp1)
    cc_y = cc_flat[:, ncx:].reshape(B, W, NXp1, NY)
    wx0 = wenter0[:, :ncx].reshape(B, W, NX, NYp1)
    wy0 = wenter0[:, ncx:].reshape(B, W, NXp1, NY)
    if plane_dtype != "f32":
        # match planes_relax: the congestion input is quantized ONCE
        # through the plane dtype so every rung sees identical costs
        from .planes import plane_jnp_dtype
        dt = plane_jnp_dtype(plane_dtype)
        cc_x = cc_x.astype(dt).astype(jnp.float32)
        cc_y = cc_y.astype(dt).astype(jnp.float32)

    def blocks_x(a, fill):
        return _stack_blocks(_pad_cols(a, 1, PX + 2, fill), s, kx, kx + 2)

    def blocks_y(a, fill):
        return _stack_blocks(_pad_cols(a, 1, PX + 3, fill), s, kx, kx + 3)

    gm_blocks = _geom_blocks(pg, s, kx)
    dxb = blocks_x(dx0, INF)
    dyb = blocks_y(dy0, INF)
    ccxb = blocks_x(cc_x, INF)
    ccyb = blocks_y(cc_y, INF)
    wxb = blocks_x(wx0, 0.0)
    wyb = blocks_y(wy0, 0.0)

    fwd = [(i, i + 1) for i in range(s - 1)]     # -> right neighbor
    bwd = [(i, i - 1) for i in range(1, s)]      # -> left neighbor
    if rmesh.impl == "pallas_halo" \
            and jax.default_backend() == "tpu":
        def _send(slab, to_right: bool):
            return remote_slab_permute(slab, ROW_AXIS, s,
                                       fwd=to_right)
    else:
        def _send(slab, to_right: bool):
            return lax.ppermute(slab, ROW_AXIS, fwd if to_right else bwd)

    def body(gm_blk, dxk, dyk, ccxk, ccyk, wxk, wyk, crit):
        gm = jax.tree_util.tree_map(lambda a: a[0], gm_blk)
        dx, dy = dxk[0], dyk[0]
        ccx, ccy = ccxk[0], ccyk[0]
        wx, wy = wxk[0], wyk[0]
        predx = jnp.broadcast_to(gm.idxx, dx.shape)
        predy = jnp.broadcast_to(gm.idxy, dy.shape)
        costs = _sweep_costs(gm, crit, ccx, ccy)
        ridx = lax.axis_index(ROW_AXIS)

        def extract(st):
            # dist halo slabs in the storage dtype, transfers issued
            # here (for lag-2, one full sweep before they are needed)
            return (_send(st[0][:, :, kx:kx + 1], True),
                    _send(st[0][:, :, 1:2], False),
                    _send(st[1][:, :, kx:kx + 1], True),
                    _send(st[1][:, :, 1:3], False))

        def install(st, h):
            # edge shards mask ppermute's zero-filled unreceived halos
            # back to INF (a zero would be a spurious source seed)
            lx, rx, ly, ry = h
            dx = st[0].at[:, :, 0:1].set(
                jnp.where(ridx == 0, INF, lx))
            dx = dx.at[:, :, kx + 1:kx + 2].set(
                jnp.where(ridx == s - 1, INF, rx))
            dy = st[1].at[:, :, 0:1].set(
                jnp.where(ridx == 0, INF, ly))
            dy = dy.at[:, :, kx + 1:kx + 3].set(
                jnp.where(ridx == s - 1, INF, ry))
            return (dx, dy) + st[2:]

        def owned_changed(s2, s1):
            own = (slice(None), slice(None), slice(1, kx + 1))
            return (jnp.any(s2[0][own] < s1[0][own])
                    | jnp.any(s2[1][own] < s1[1][own]))

        if plane_dtype != "f32":
            def sweep(st):
                return quantize_plane_state(
                    _sweep_once(gm, _dequantize_plane_state(st), crit,
                                ccx, ccy, costs), plane_dtype)
        else:
            def sweep(st):
                return _sweep_once(gm, st, crit, ccx, ccy, costs)

        state0 = (dx, dy, predx, predy, wx, wy)
        if plane_dtype != "f32":
            state0 = quantize_plane_state(state0, plane_dtype)

        if not lag2:
            def cond(c):
                i, go, _ = c
                return go & (i < nsw_cap)

            def loop(c):
                i, _, st = c
                st_in = install(st, extract(st))
                st2 = sweep(st_in)
                ch = owned_changed(st2, st_in)
                go = lax.psum(ch.astype(jnp.int32), ROW_AXIS) > 0
                return i + 1, go, st2

            i, go, state = lax.while_loop(
                cond, loop, (jnp.int32(0), jnp.bool_(True), state0))
            useful = jnp.maximum(jnp.int32(0),
                                 i - jnp.where(go, 0, 1))
        else:
            # lag-2 overlapped schedule: sweep t installs halos
            # extracted at the end of sweep t-2 — the carry's slabs
            # were issued one whole sweep ago.  Exit needs TWO
            # consecutive globally-stable sweeps (see module doc).
            def cond(c):
                i, streak, _, _ = c
                return (streak < 2) & (i < nsw_cap)

            def loop(c):
                i, streak, st, h = c
                st_in = install(st, h)
                st2 = sweep(st_in)
                h2 = extract(st)        # from PRE-sweep state: no data
                #                         dependency on st2 -> the
                #                         transfer overlaps the sweep
                ch = owned_changed(st2, st_in)
                anych = lax.psum(ch.astype(jnp.int32), ROW_AXIS) > 0
                streak = jnp.where(anych, jnp.int32(0), streak + 1)
                return i + 1, streak, st2, h2

            i, streak, state, _ = lax.while_loop(
                cond, loop,
                (jnp.int32(0), jnp.int32(0), state0, extract(state0)))
            useful = jnp.maximum(jnp.int32(0), i - streak)

        own = (slice(None), slice(None), slice(1, kx + 1))
        outs = tuple(a[own][None] for a in state)
        stats = jnp.stack([i, useful]).astype(jnp.int32)[None]
        return outs + (stats,)

    shmap = shard_map(
        body, mesh=rmesh.mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                  P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P()),
        out_specs=(P(ROW_AXIS),) * 7,
        check_vma=False)
    dxs, dys, pxs, pys, wxs, wys, stats = shmap(
        gm_blocks, dxb, dyb, ccxb, ccyb, wxb, wyb, crit_c)

    def reassemble(out, real_x):
        a = jnp.moveaxis(out, 0, 2)          # [B, W, s, kx, Y]
        a = a.reshape(B, W, PX, out.shape[-1])
        return a[:, :, :real_x]

    dx = reassemble(dxs, NX)
    dy = reassemble(dys, NXp1)
    predx = reassemble(pxs, NX)
    predy = reassemble(pys, NXp1)
    wx = reassemble(wxs, NX)
    wy = reassemble(wys, NXp1)
    if plane_dtype != "f32":
        dx, dy, wx, wy = (a.astype(jnp.float32)
                          for a in (dx, dy, wx, wy))

    def flat(a, b):
        return jnp.concatenate([a.reshape(B, -1), b.reshape(B, -1)],
                               axis=1)

    return flat(dx, dy), flat(predx, predy), flat(wx, wy), stats[0]
