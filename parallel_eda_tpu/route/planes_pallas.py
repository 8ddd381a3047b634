"""Pallas TPU kernels for the planes relaxation: the whole multi-sweep
loop VMEM-resident, a BLOCK of G nets per grid step, canvases packed
along the sublane/lane dimensions.

Two perf levers compose here:

* VMEM residency (rounds 3/4): the XLA lowering of planes_relax
  materialises every scan/turn intermediate through HBM — per sweep
  that is ~15 canvas-sized reads+writes, so the sweep is
  HBM-bandwidth-bound.  The kernel runs the ENTIRE nsweeps loop on
  VMEM-resident canvases: HBM traffic drops from O(nsweeps * canvases)
  to O(canvases).

* Lane packing (this round): one bench-sized net fills a sliver of the
  (8, 128) f32 vector registers — a 12x12 / W=12 canvas laid out
  [1, W, NX, NY+1] puts NY+1 = 13 of 128 lanes to work.  Each net's
  canvases are therefore stored as ONE folded row (planes.fold_canvas:
  W and the spatial dims collapse into the minor axis, trailing Y
  padded to a lane multiple) and a grid step loads a [G, row] block —
  G nets across the sublanes, full-width lanes.  G is planned from the
  VMEM budget (auto_block_nets, sized per crop-ladder rung); when one
  rung's padded block would overflow, G degrades toward 1 and the grid
  pipeline's double-buffered HBM->VMEM copies stream the blocks.

The pad columns are storage-only.  Inside the kernel every canvas is
sliced back to its unpadded (W, X, Y) shape before the shared sweep
body runs (_sweep_once / _sweep_costs from planes.py — the same code as
the XLA program, the two lowerings cannot drift), so the packed kernels
are BIT-IDENTICAL to the one-net-per-step path (block_nets=1,
lane_mult=1) and to each other for any G: padding an associative_scan
axis instead would change the min-plus fold's combine tree and break
that equivalence.  Batch remainders are padded with inert nets
(d0 = +inf everywhere — no scan or turn can improve an all-inf canvas —
congestion 0, crit 0) whose outputs are sliced off.  The [executed,
useful] convergence counters thread through unchanged: a block's
while_loop stops at the max of its member nets' trip counts, so the
batch-level max over blocks equals the max over nets — exactly the
reduction the equivalent batched while_loop applies.

Correctness is enforced by tests/test_planes_pallas.py and the packed
parity suite in tests/test_kernel_pack.py in interpret mode (the kernel
auto-selects the interpreter off-TPU; it stays opt-in via
RouterOpts(program="planes_pallas") until device-measured).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .planes import (INF, PlanesGeom, PlanesGraph, _run_relax,
                     _sweep_costs, _sweep_once, crop_state, fold_canvas,
                     geom_cropped, geom_full, plane_jnp_dtype,
                     scatter_state, unfold_canvas)

# f32 vector-register geometry (TPU: 8 sublanes x 128 lanes; bf16 rows
# stay legal because the packed [G, row] layout keeps the minor axis
# lane-aligned — the bf16 min tile only grows the SUBLANE direction,
# which the G axis covers)
SUBLANE = 8
LANE = 128
DEF_LANE_MULT = 8           # trailing-Y pad granularity for packed rows
# VMEM plan budget: ~16 MB/core minus headroom for the grid pipeline's
# scratch and compiler spills
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# canvas-pair-equivalents of VMEM one net occupies during the in-kernel
# sweep loop, split by what scales with the plane storage dtype: the 6
# state inputs + 6 outputs double-buffered by the grid pipeline (24)
# carry the storage dtype, while the ~16 live scan/turn intermediates
# in the sweep body are f32 regardless (the bf16 mode upcasts per
# sweep), so a bf16 block shrinks its buffers but not its temporaries
BUFFER_EQUIV = 24
SWEEP_TMP_EQUIV = 16
CANVAS_EQUIV = BUFFER_EQUIV + SWEEP_TMP_EQUIV


def packed_bytes_per_cell(itemsize: int = 4) -> int:
    """Modeled HBM bytes one PADDED cell moves across a packed-kernel
    dispatch: two traversals of each of the five storage-dtype canvas
    sets (dist + wenter in and out, congestion in) plus two of the
    int32 pred output.  itemsize=4 reproduces the round-5 f32 model
    (2 * 6 * 4 = 48 B/cell) exactly; bf16 (itemsize=2) models 28 —
    the dtype-aware bytes/sweep ledger and the route.kernel gauges both
    derive from this one function."""
    return 2 * (5 * int(itemsize) + 4)


def xla_bytes_per_cell(itemsize: int = 4) -> int:
    """Modeled HBM bytes one USEFUL cell moves per XLA sweep: ~15
    canvas traversals, of which the three loop-carried storage sets
    (dist, wenter, congestion) take the plane dtype while the scan and
    turn intermediates XLA materialises stay f32 — the XLA lowering
    barely benefits from bf16 (60 -> 54 B/cell); the packed kernel is
    where the dtype lever pays."""
    return 3 * int(itemsize) + 12 * 4


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // int(m)) * int(m)


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Storage layout of one net's canvas pair after lane folding: the
    x-plane set (W, X, Y+1) and y-plane set (W, X+1, Y) each flatten to
    one row of row_x / row_y elements, trailing Y padded up to
    lane_mult.  All occupancy / footprint modeling (kernel planning,
    route.kernel.* gauges, tools/kernel_bench.py) derives from this one
    object so the numbers agree everywhere."""
    shape_x: tuple
    shape_y: tuple
    lane_mult: int = DEF_LANE_MULT

    @property
    def pad_yx(self) -> int:
        return _ceil_to(self.shape_x[-1], self.lane_mult) \
            - self.shape_x[-1]

    @property
    def pad_yy(self) -> int:
        return _ceil_to(self.shape_y[-1], self.lane_mult) \
            - self.shape_y[-1]

    @property
    def row_x(self) -> int:
        W, X, Y = self.shape_x
        return W * X * (Y + self.pad_yx)

    @property
    def row_y(self) -> int:
        W, X, Y = self.shape_y
        return W * X * (Y + self.pad_yy)

    @property
    def cells(self) -> int:
        """Useful (unpadded) cells across both plane sets."""
        (W, X, Y), (_, X2, Y2) = self.shape_x, self.shape_y
        return W * X * Y + W * X2 * Y2

    @property
    def padded_cells(self) -> int:
        return self.row_x + self.row_y

    def block_bytes(self, G: int, itemsize: int = 4) -> int:
        """Modeled VMEM bytes of a G-net block while the sweep loop
        runs.  The buffered state scales with the plane storage dtype
        (``itemsize``); the live sweep-body intermediates are f32 in
        every mode (itemsize=4 collapses to the round-5 model,
        CANVAS_EQUIV * 4 bytes per padded cell)."""
        per_cell = BUFFER_EQUIV * int(itemsize) + SWEEP_TMP_EQUIV * 4
        return int(G) * per_cell * self.padded_cells

    def lane_occupancy(self, G: int) -> float:
        """Useful-cell fraction of the vreg footprint of a [G, row]
        block: G rows over ceil-to-8 sublanes, rows over ceil-to-128
        lanes."""
        sub = _ceil_to(max(int(G), 1), SUBLANE)
        lanes = _ceil_to(self.row_x, LANE) + _ceil_to(self.row_y, LANE)
        return (int(G) * self.cells) / float(sub * lanes)


def packed_layout(shape_x, shape_y,
                  lane_mult: int = DEF_LANE_MULT) -> PackedLayout:
    return PackedLayout(tuple(shape_x), tuple(shape_y), int(lane_mult))


def auto_block_nets(shape_x, shape_y, nnets: int,
                    lane_mult: int = DEF_LANE_MULT,
                    vmem_bytes: int = VMEM_BUDGET_BYTES,
                    itemsize: int = 4) -> int:
    """Largest power-of-two block of nets whose packed state fits the
    VMEM plan budget, clamped to the batch.  Never below 1: a single
    net that overflows the budget still runs — the grid pipeline
    streams its block with double-buffered HBM->VMEM copies.  A
    narrower plane dtype (``itemsize``) shrinks the per-net footprint,
    so the same budget packs more nets per block — the lane-width
    doubling of the bf16 mode."""
    lay = packed_layout(shape_x, shape_y, lane_mult)
    per_net = max(1, lay.block_bytes(1, itemsize))
    g = max(1, vmem_bytes // per_net)
    return _pow2_floor(min(g, max(1, int(nnets))))


def unpacked_lane_occupancy(shape_x, shape_y) -> float:
    """Vreg occupancy model of the legacy one-net-per-step layout:
    [1, W, X, Y] blocks tile (X, Y) onto (8, 128), so the whole Y
    extent of a small canvas sits in one vreg's first lanes."""
    (W, X, Y), (_, X2, Y2) = tuple(shape_x), tuple(shape_y)
    tiled = (W * _ceil_to(X, SUBLANE) * _ceil_to(Y, LANE)
             + W * _ceil_to(X2, SUBLANE) * _ceil_to(Y2, LANE))
    return (W * X * Y + W * X2 * Y2) / float(tiled)


def _load_packed(ref, G: int, shape, pad_y: int):
    """[G, row] ref -> unpadded [G, *shape] value (pad columns are
    storage-only and never reach compute)."""
    padded = (G,) + tuple(shape[:-1]) + (shape[-1] + pad_y,)
    v = ref[:].reshape(padded)
    return v[..., :shape[-1]] if pad_y else v


def _store_packed(ref, a, pad_y: int):
    """Unpadded [G, *shape] value -> [G, row] ref (pad columns
    zero-filled so the stored block is fully defined)."""
    if pad_y:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad_y)])
    ref[:] = a.reshape(ref.shape)


def _sweep_kernel(pg_template: PlanesGraph, nsweeps: int, G: int,
                  pad_yx: int, pad_yy: int, plane_dtype: str,
                  # refs: per-net state, folded [G, row]
                  dx_ref, dy_ref, ccx_ref, ccy_ref, crit_ref, wx_ref,
                  wy_ref,
                  # refs: static planes metadata (same block for all b)
                  bbx_ref, bax_ref, bby_ref, bay_ref,
                  fx_ref, lx_ref, fy_ref, ly_ref,
                  delx_ref, dely_ref, delr0_ref, delr1_ref, inc_ref,
                  # outputs
                  odx_ref, ody_ref, opx_ref, opy_ref, owx_ref, owy_ref,
                  ost_ref):
    """One grid step = one BLOCK of G nets, each net's canvases stored
    as one folded row: unpack to unpadded canvases, rebuild a shared
    PlanesGeom over the (unpadded) static masks, run the shared sweep
    body to the block's fixpoint, re-fold and store."""
    shx = pg_template.shape_x
    shy = pg_template.shape_y
    W, NX, NYp1 = shx
    _, NXp1, NY = shy
    ncx = W * NX * NYp1

    idxx = jnp.arange(ncx, dtype=jnp.int32).reshape(1, W, NX, NYp1)
    idxy = (ncx + jnp.arange(W * NXp1 * NY, dtype=jnp.int32)
            ).reshape(1, W, NXp1, NY)
    base_par = ((jnp.arange(NX + 1)[:, None]
                 + jnp.arange(NY + 1)[None, :]) % 2)[None]
    gm = PlanesGeom(
        brk_before_x=(bbx_ref[:] != 0)[None],
        brk_after_x=(bax_ref[:] != 0)[None],
        brk_before_y=(bby_ref[:] != 0)[None],
        brk_after_y=(bay_ref[:] != 0)[None],
        first_x=(fx_ref[:] != 0)[None], last_x=(lx_ref[:] != 0)[None],
        first_y=(fy_ref[:] != 0)[None], last_y=(ly_ref[:] != 0)[None],
        delay_x=delx_ref[:][None], delay_y=dely_ref[:][None],
        delay_y_rot0=delr0_ref[:][None], delay_y_rot1=delr1_ref[:][None],
        idxx=idxx, idxy=idxy, base_par=base_par, stride_x=NYp1,
        directional=pg_template.directional,
        inc_track=(inc_ref[:] != 0 if pg_template.directional else None),
        group_tracks=pg_template.group_tracks,
    )

    dx = _load_packed(dx_ref, G, shx, pad_yx)
    dy = _load_packed(dy_ref, G, shy, pad_yy)
    # the congestion refs carry the plane storage dtype (real HBM/VMEM
    # savings in bf16 mode); the sweep body always computes in f32 —
    # the wrapper quantized cc through the same dtype the XLA program
    # uses, so the upcast sees identical values in both lowerings
    cc_x = _load_packed(ccx_ref, G, shx, pad_yx).astype(jnp.float32)
    cc_y = _load_packed(ccy_ref, G, shy, pad_yy).astype(jnp.float32)
    crit_c = crit_ref[:].reshape(G, 1, 1, 1)
    wx = _load_packed(wx_ref, G, shx, pad_yx)
    wy = _load_packed(wy_ref, G, shy, pad_yy)

    predx = jnp.broadcast_to(gm.idxx, dx.shape)
    predy = jnp.broadcast_to(gm.idxy, dy.shape)

    costs = _sweep_costs(gm, crit_c, cc_x, cc_y)

    def body(s):
        return _sweep_once(gm, s, crit_c, cc_x, cc_y, costs)

    # per-block bounded while_loop: the block stops at its members'
    # common fixpoint — the max of the member nets' own trip counts,
    # the same reduction the batched XLA while_loop applies batch-wide.
    # In bf16 mode the refs already carry the storage dtype, so
    # _run_relax's entry quantization is a no-op cast and the per-sweep
    # up/down cycle matches the XLA program bit for bit
    (dx, dy, predx, predy, wx, wy), stats = _run_relax(
        body, (dx, dy, predx, predy, wx, wy), nsweeps, plane_dtype)

    _store_packed(odx_ref, dx, pad_yx)
    _store_packed(ody_ref, dy, pad_yy)
    _store_packed(opx_ref, predx, pad_yx)
    _store_packed(opy_ref, predy, pad_yy)
    _store_packed(owx_ref, wx, pad_yx)
    _store_packed(owy_ref, wy, pad_yy)
    ost_ref[:] = stats.reshape(1, 2)


def _bpad(a, n: int, fill=0):
    """Pad the batch axis with n inert rows."""
    if n <= 0:
        return a
    return jnp.pad(a, [(0, n)] + [(0, 0)] * (a.ndim - 1),
                   constant_values=fill)


def pallas_interpret(interpret=None) -> bool:
    """Resolve the kernels' ``interpret=None`` auto-select: the Pallas
    interpreter off-TPU (tests/CPU), the chip's compiler on it.  Benches
    call this too — no printed result from an interpreted kernel may
    lack ``interpret: true``."""
    return jax.default_backend() != "tpu" if interpret is None \
        else bool(interpret)


@functools.partial(jax.jit, static_argnames=("nsweeps", "interpret",
                                             "block_nets", "lane_mult",
                                             "plane_dtype"))
def planes_relax_pallas(pg: PlanesGraph, d0_flat, cc_flat, crit_c,
                        wenter0, nsweeps: int, interpret=None,
                        block_nets=None, lane_mult: int = DEF_LANE_MULT,
                        plane_dtype: str = "f32"):
    """Drop-in for planes.planes_relax with identical signature and
    bit-identical results, lowered as a Pallas kernel gridded over
    BLOCKS of nets.  interpret=None auto-selects the interpreter
    off-TPU (tests/CPU); block_nets=None auto-plans the block size from
    the VMEM budget; block_nets=1 + lane_mult=1 is the legacy
    one-net-per-step layout.  plane_dtype="bf16" stores the dist/
    wenter/congestion refs (and their out_shapes) in bfloat16 — the
    per-sweep state really moves half the bytes — and stays
    bit-identical to planes_relax run with the same plane_dtype."""
    interpret = pallas_interpret(interpret)
    B = d0_flat.shape[0]
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1
    shx = (W, NX, NYp1)
    shy = (W, NXp1, NY)

    sdt = plane_jnp_dtype(plane_dtype)
    isz = jnp.dtype(sdt).itemsize
    lay = packed_layout(shx, shy, lane_mult)
    G = (auto_block_nets(shx, shy, B, lane_mult, itemsize=isz)
         if block_nets is None else int(block_nets))
    G = max(1, min(G, B))
    NB = -(-B // G)
    Bp = NB * G
    pyx, pyy = lay.pad_yx, lay.pad_yy

    def prep(part, shape, pad_y, fill):
        # quantize BEFORE padding so the ref carries the storage dtype
        # (the pad fills are exactly representable in either dtype)
        return _bpad(fold_canvas(part.reshape((B,) + shape).astype(sdt),
                                 pad_y), Bp - B, fill)

    # inert batch-pad nets: d0 = +inf everywhere (no scan or turn can
    # improve an all-inf canvas), congestion/wenter/crit 0
    dx0 = prep(d0_flat[:, :ncx], shx, pyx, INF)
    dy0 = prep(d0_flat[:, ncx:], shy, pyy, INF)
    ccx = prep(cc_flat[:, :ncx], shx, pyx, 0)
    ccy = prep(cc_flat[:, ncx:], shy, pyy, 0)
    wx0 = prep(wenter0[:, :ncx], shx, pyx, 0)
    wy0 = prep(wenter0[:, ncx:], shy, pyy, 0)
    critb = _bpad(crit_c.reshape(B, 1), Bp - B, 0)

    def rowspec(row):
        return pl.BlockSpec((G, row), lambda b: (b, 0))

    def sspec(shape):
        # static metadata: every grid step reads block 0
        return pl.BlockSpec(shape, lambda b: (0,) * len(shape))

    i8 = jnp.int8
    inc = (pg.inc_track.astype(i8) if pg.directional
           else jnp.zeros((W,), i8))
    statics = (pg.brk_before_x.astype(i8), pg.brk_after_x.astype(i8),
               pg.brk_before_y.astype(i8), pg.brk_after_y.astype(i8),
               pg.first_x.astype(i8), pg.last_x.astype(i8),
               pg.first_y.astype(i8), pg.last_y.astype(i8),
               pg.delay_x, pg.delay_y, pg.delay_y_rot0, pg.delay_y_rot1,
               inc)
    static_specs = [sspec(a.shape) for a in statics]

    f32 = jnp.float32
    rx, ry = lay.row_x, lay.row_y
    out_shapes = [jax.ShapeDtypeStruct((Bp, rx), sdt),
                  jax.ShapeDtypeStruct((Bp, ry), sdt),
                  jax.ShapeDtypeStruct((Bp, rx), jnp.int32),
                  jax.ShapeDtypeStruct((Bp, ry), jnp.int32),
                  jax.ShapeDtypeStruct((Bp, rx), sdt),
                  jax.ShapeDtypeStruct((Bp, ry), sdt),
                  jax.ShapeDtypeStruct((NB, 2), jnp.int32)]
    out_specs = [rowspec(rx), rowspec(ry), rowspec(rx), rowspec(ry),
                 rowspec(rx), rowspec(ry),
                 pl.BlockSpec((1, 2), lambda b: (b, 0))]

    kern = functools.partial(_sweep_kernel, pg, nsweeps, G, pyx, pyy,
                             plane_dtype)
    dx, dy, px, py, wx, wy, stats = pl.pallas_call(
        kern,
        grid=(NB,),
        in_specs=[rowspec(rx), rowspec(ry), rowspec(rx), rowspec(ry),
                  pl.BlockSpec((G, 1), lambda b: (b, 0)),
                  rowspec(rx), rowspec(ry)] + static_specs,
        out_shape=out_shapes,
        out_specs=out_specs,
        interpret=interpret,
    )(dx0, dy0, ccx, ccy, critb, wx0, wy0, *statics)

    if sdt != f32:
        # f32 flats regardless of storage dtype (planes_relax contract)
        dx, dy, wx, wy = (a.astype(f32) for a in (dx, dy, wx, wy))

    def flat(ax, ay):
        ax = unfold_canvas(ax, shx, pyx)[:B]
        ay = unfold_canvas(ay, shy, pyy)[:B]
        return jnp.concatenate([ax.reshape(B, -1), ay.reshape(B, -1)],
                               axis=1)

    # batch-level stats: the slowest block's trip count == the slowest
    # net's (all-pad blocks cannot exist: the last block holds >= 1
    # real net, and pad nets converge after the discovery sweep)
    bstats = jnp.stack([stats[:, 0].max(), stats[:, 1].max()])
    return flat(dx, dy), flat(px, py), flat(wx, wy), bstats


def _crop_sweep_kernel(group_tracks: int, stride_x: int, nsweeps: int,
                       G: int, shx, shy, pad_yx: int, pad_yy: int,
                       geo_meta, plane_dtype, *refs):
    """One grid step = a BLOCK of G nets' bb TILES, whole nsweeps loop
    in VMEM.  Geometry arrives pre-cropped per net (geom_cropped runs
    in XLA) and folded to [G, row] like the state; geo_meta carries
    each geometry array's unpadded tile shape + trailing pad."""
    (dx_ref, dy_ref, ccx_ref, ccy_ref, crit_ref,
     wx_ref, wy_ref) = refs[:7]
    geo_refs = refs[7:7 + len(geo_meta)]
    inc_ref = refs[7 + len(geo_meta)]
    (odx_ref, ody_ref, opx_ref, opy_ref, owx_ref, owy_ref,
     ost_ref) = refs[-7:]

    (bbx, bax, bby, bay, fx, lxm, fy, lym, delx, dely, delr0, delr1,
     idxx, idxy, par) = [_load_packed(r, G, shape, pad)
                         for r, (shape, pad) in zip(geo_refs, geo_meta)]
    gm = PlanesGeom(
        brk_before_x=bbx != 0, brk_after_x=bax != 0,
        brk_before_y=bby != 0, brk_after_y=bay != 0,
        first_x=fx != 0, last_x=lxm != 0,
        first_y=fy != 0, last_y=lym != 0,
        delay_x=delx, delay_y=dely,
        delay_y_rot0=delr0, delay_y_rot1=delr1,
        idxx=idxx, idxy=idxy, base_par=par, stride_x=stride_x,
        directional=group_tracks > 0,
        inc_track=(inc_ref[:] != 0 if group_tracks else None),
        group_tracks=group_tracks,
    )
    dx = _load_packed(dx_ref, G, shx, pad_yx)
    dy = _load_packed(dy_ref, G, shy, pad_yy)
    # congestion refs share the plane storage dtype; the sweep body
    # computes in f32, so upcast once at load
    cc_x = _load_packed(ccx_ref, G, shx, pad_yx).astype(jnp.float32)
    cc_y = _load_packed(ccy_ref, G, shy, pad_yy).astype(jnp.float32)
    crit_c = crit_ref[:].reshape(G, 1, 1, 1)
    wx = _load_packed(wx_ref, G, shx, pad_yx)
    wy = _load_packed(wy_ref, G, shy, pad_yy)
    predx = jnp.broadcast_to(gm.idxx, dx.shape)
    predy = jnp.broadcast_to(gm.idxy, dy.shape)

    costs = _sweep_costs(gm, crit_c, cc_x, cc_y)

    def body(s):
        return _sweep_once(gm, s, crit_c, cc_x, cc_y, costs)

    (dx, dy, predx, predy, wx, wy), stats = _run_relax(
        body, (dx, dy, predx, predy, wx, wy), nsweeps, plane_dtype)
    _store_packed(odx_ref, dx, pad_yx)
    _store_packed(ody_ref, dy, pad_yy)
    _store_packed(opx_ref, predx, pad_yx)
    _store_packed(opy_ref, predy, pad_yy)
    _store_packed(owx_ref, wx, pad_yx)
    _store_packed(owy_ref, wy, pad_yy)
    ost_ref[:] = stats.reshape(1, 2)


@functools.partial(jax.jit,
                   static_argnames=("nsweeps", "cnx", "cny", "interpret",
                                    "block_nets", "lane_mult",
                                    "plane_dtype"))
def planes_relax_cropped_pallas(pg: PlanesGraph, d0_flat, cc_flat,
                                crit_c, wenter0, nsweeps: int, ox, oy,
                                cnx: int, cny: int, interpret=None,
                                block_nets=None,
                                lane_mult: int = DEF_LANE_MULT,
                                plane_dtype: str = "f32"):
    """Drop-in for planes.planes_relax_cropped, with the multi-sweep
    relaxation of a BLOCK of net TILES resident in VMEM — the
    composition of all three work/hardware-efficiency levers: per-net
    work scales with the bb (crop), the sweep loop never touches HBM
    (Pallas), and the block's tiles pack the vector lanes (fold).
    Block size is planned per crop-ladder rung (smaller tiles -> more
    nets per block).

    Crop and scatter-back run in XLA exactly as in the XLA cropped
    program; inside the kernel the folded tiles are sliced back to
    their unpadded shapes, so results are bit-identical to the
    one-net-per-step path for any block size."""
    interpret = pallas_interpret(interpret)
    sdt = plane_jnp_dtype(plane_dtype)
    isz = jnp.dtype(sdt).itemsize
    B = d0_flat.shape[0]
    W, NX, NYp1 = pg.shape_x
    shx = (W, cnx, cny + 1)
    shy = (W, cnx + 1, cny)

    lay = packed_layout(shx, shy, lane_mult)
    G = (auto_block_nets(shx, shy, B, lane_mult, itemsize=isz)
         if block_nets is None else int(block_nets))
    G = max(1, min(G, B))
    NB = -(-B // G)
    Bp = NB * G
    pyx, pyy = lay.pad_yx, lay.pad_yy

    gm_full = geom_full(pg)
    gm = geom_cropped(pg, ox, oy, cnx, cny, full=gm_full)
    fulls, (dx0, dy0, ccx, ccy, wx0, wy0) = crop_state(
        pg, d0_flat, cc_flat, wenter0, ox, oy, cnx, cny)

    def prep(a4, pad_y, fill):
        # downcast to the storage dtype before folding: HBM traffic and
        # VMEM residency both pay the narrow width
        return _bpad(fold_canvas(a4.astype(sdt), pad_y), Bp - B, fill)

    dx0 = prep(dx0, pyx, INF)
    dy0 = prep(dy0, pyy, INF)
    ccx = prep(ccx, pyx, 0)
    ccy = prep(ccy, pyy, 0)
    wx0 = prep(wx0, pyx, 0)
    wy0 = prep(wy0, pyy, 0)
    critb = _bpad(crit_c.reshape(B, 1), Bp - B, 0)

    i8 = jnp.int8
    inc = (pg.inc_track.astype(i8) if pg.directional
           else jnp.zeros((W,), i8))
    geo4 = (gm.brk_before_x.astype(i8), gm.brk_after_x.astype(i8),
            gm.brk_before_y.astype(i8), gm.brk_after_y.astype(i8),
            gm.first_x.astype(i8), gm.last_x.astype(i8),
            gm.first_y.astype(i8), gm.last_y.astype(i8),
            gm.delay_x, gm.delay_y, gm.delay_y_rot0, gm.delay_y_rot1,
            gm.idxx, gm.idxy, gm.base_par.astype(jnp.int32))
    lm = int(lane_mult)
    geo_meta = tuple(
        (tuple(a.shape[1:]),
         _ceil_to(a.shape[-1], lm) - a.shape[-1]) for a in geo4)
    # inert batch-pad geometry: all-zero masks/delays/ids — with the
    # pad nets' all-inf d0 no cell can ever improve
    geo_in = [_bpad(fold_canvas(a, p), Bp - B, 0)
              for a, (_, p) in zip(geo4, geo_meta)]

    def rowspec(row):
        return pl.BlockSpec((G, row), lambda b: (b, 0))

    geo_specs = [rowspec(a.shape[1]) for a in geo_in]
    # inc is shared across nets: every grid step reads block 0
    inc_spec = pl.BlockSpec((W,), lambda b: (0,))

    f32 = jnp.float32
    rx, ry = lay.row_x, lay.row_y
    out_shapes = [jax.ShapeDtypeStruct((Bp, rx), sdt),
                  jax.ShapeDtypeStruct((Bp, ry), sdt),
                  jax.ShapeDtypeStruct((Bp, rx), jnp.int32),
                  jax.ShapeDtypeStruct((Bp, ry), jnp.int32),
                  jax.ShapeDtypeStruct((Bp, rx), sdt),
                  jax.ShapeDtypeStruct((Bp, ry), sdt),
                  jax.ShapeDtypeStruct((NB, 2), jnp.int32)]
    out_specs = [rowspec(rx), rowspec(ry), rowspec(rx), rowspec(ry),
                 rowspec(rx), rowspec(ry),
                 pl.BlockSpec((1, 2), lambda b: (b, 0))]

    kern = functools.partial(_crop_sweep_kernel, pg.group_tracks, NYp1,
                             nsweeps, G, shx, shy, pyx, pyy, geo_meta,
                             plane_dtype)
    dx, dy, px, py, wx, wy, stats = pl.pallas_call(
        kern,
        grid=(NB,),
        in_specs=[rowspec(rx), rowspec(ry), rowspec(rx), rowspec(ry),
                  pl.BlockSpec((G, 1), lambda b: (b, 0)),
                  rowspec(rx), rowspec(ry)] + geo_specs + [inc_spec],
        out_shape=out_shapes,
        out_specs=out_specs,
        interpret=interpret,
    )(dx0, dy0, ccx, ccy, critb, wx0, wy0, *geo_in, inc)

    if sdt != f32:
        # scatter back into the f32 full canvases (planes_relax_cropped
        # contract: f32 out regardless of storage dtype)
        dx, dy, wx, wy = (a.astype(f32) for a in (dx, dy, wx, wy))

    def unfold6(a2, shape, pad_y):
        return unfold_canvas(a2, shape, pad_y)[:B]

    tiles = (unfold6(dx, shx, pyx), unfold6(dy, shy, pyy),
             unfold6(px, shx, pyx), unfold6(py, shy, pyy),
             unfold6(wx, shx, pyx), unfold6(wy, shy, pyy))
    bstats = jnp.stack([stats[:, 0].max(), stats[:, 1].max()])
    return scatter_state(gm_full, fulls, tiles, ox, oy) + (bstats,)


def remote_slab_permute(slab, axis_name, n_shards, fwd=True):
    """Halo-slab neighbor exchange over the TPU interconnect (RDMA).

    Transport for the mesh ladder's top rung ("pallas_halo",
    route/planes_shard.py): inside the shard_map body each device
    pushes its boundary dist slab ([B, W, 1-or-2, Y]) directly into the
    neighbor's output buffer with ``pltpu.make_async_remote_copy`` —
    a one-hop ICI DMA instead of the collective-scheduled
    ``lax.ppermute`` the middle rung uses.  The overlap itself lives in
    planes_shard's lag-2 schedule: the halo installed before sweep k
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
