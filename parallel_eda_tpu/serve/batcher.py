"""Cross-job net bin-packing, as a MODEL: it plans no dispatch.

The service routes one job's slice at a time (serve/queue.py); nothing
merges two jobs' nets into one device program.  What this module
computes is how the admitted set WOULD fold onto one size-class crop
ladder (the ``_size_class_buckets`` pow-2 ladder the Router bins one
job's nets on): the UNION of all jobs' nets binned, one
``PackedLayout`` + ``auto_block_nets`` G per populated rung, packed
slots demultiplexed back to (job, net).  Its one consumer,
``RouteService.admit``, keeps of that the four ``route.serve.pack.*``
gauges.  (The planes relaxation is per-net -- a batch is bit-identical
to its nets relaxed one at a time,
tests/test_kernel_pack.py::test_relax_net_independent* -- so a
scheduler that did merge jobs would compute, net for net, what each
job's solo batch computes: PERF.md section 7's ``serve_burst`` row
says when one is worth building.)  ROADMAP.md Queue 3 item 8 names
this module's arithmetic as the next deletion.

Inputs are plain numpy spans; no jax, no Router import at module load.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.metrics import get_metrics


# ---------------------------------------------------------------------
# Lane-packed block layout: host-side arithmetic only.  It sizes the
# [G, row] blocks of a lane-packed relaxation kernel that the tree no
# longer has (the v5e compiler refused it; the relaxation is the XLA
# planes_relax); what remains is the model pack_jobs plans its shared
# rungs with and Router._plan_block_nets reports occupancy from.
# ROADMAP.md Queue 3 carries it as a debt decided with a served cell.
# ---------------------------------------------------------------------

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


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // int(m)) * int(m)


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


@dataclass(frozen=True)
class PackedLayout:
    """Storage layout of one net's canvas pair after lane folding: the
    x-plane set (W, X, Y+1) and y-plane set (W, X+1, Y) each flatten to
    one row of row_x / row_y elements, trailing Y padded up to
    lane_mult.  All occupancy / footprint modeling (pack_jobs' block
    plan, the route.kernel.* gauges) derives from this one object so
    the numbers agree everywhere."""
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
        every mode (itemsize=4: 40 canvas-pair equivalents of 4 bytes
        per padded cell)."""
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


@dataclass
class RungPlan:
    """One shared packed dispatch class: a crop tile (None = full
    canvas), its folded layout, the VMEM-planned block size, and the
    (job, net) slot assignment in dispatch order."""
    tile: Optional[Tuple[int, int]]
    shape_x: Tuple[int, int, int]
    shape_y: Tuple[int, int, int]
    block_nets: int
    lane_occupancy: float
    slots: List[Tuple[str, int]] = field(default_factory=list)

    @property
    def nets(self) -> int:
        return len(self.slots)

    @property
    def blocks(self) -> int:
        g = max(1, self.block_nets)
        return (len(self.slots) + g - 1) // g

    def demux(self) -> Dict[str, List[Tuple[int, int]]]:
        """job_id -> [(packed_slot, job_net_idx)] — strict: every
        occupied slot maps to exactly one job; pad slots (beyond
        ``nets`` up to blocks*G) map to none."""
        out: Dict[str, List[Tuple[int, int]]] = {}
        for s, (job, idx) in enumerate(self.slots):
            out.setdefault(job, []).append((s, idx))
        return out


@dataclass
class CrossJobPlan:
    rungs: List[RungPlan]
    jobs: List[str]

    @property
    def total_nets(self) -> int:
        return sum(r.nets for r in self.rungs)

    @property
    def lane_occupancy(self) -> float:
        """Net-weighted lane occupancy across the shared rungs — the
        number ``route.serve.pack.lane_occupancy`` publishes."""
        if not self.rungs:
            return 0.0
        return round(sum(r.lane_occupancy * r.nets for r in self.rungs)
                     / max(1, self.total_nets), 4)

    def job_slots(self, job_id: str) -> List[Tuple[int, int, int]]:
        """[(rung, packed_slot, job_net_idx)] for one job."""
        out = []
        for ri, r in enumerate(self.rungs):
            for s, idx in r.demux().get(job_id, []):
                out.append((ri, s, idx))
        return out


def pack_jobs(job_nets: Dict[str, Tuple[np.ndarray, np.ndarray]],
              shape_x: Tuple[int, int, int],
              shape_y: Tuple[int, int, int],
              min_count: int = 1, base: int = 8,
              lane_mult: Optional[int] = None,
              publish_gauges: bool = True) -> CrossJobPlan:
    """Plan shared packed dispatches for several jobs' nets.

    ``job_nets`` maps job_id -> (need_w, need_h) per-net canvas spans
    (grid cells, crop margin included — the same arrays the Router
    feeds ``_size_class_buckets``).  ``shape_x``/``shape_y`` are the
    full-canvas plane shapes (``pg.shape_x``/``pg.shape_y``); all jobs
    must target the same device grid, which is what makes their
    variant keys shareable in the first place.
    """
    from ..route.router import _size_class_buckets

    lm = DEF_LANE_MULT if lane_mult is None else lane_mult
    W, NX, NYp1 = shape_x
    _, NXp1, NY = shape_y
    nx, ny = NX, NY

    jobs = sorted(job_nets)
    # union spans, with provenance back to (job, net)
    owners: List[Tuple[str, int]] = []
    need_w_all, need_h_all = [], []
    for job in jobs:
        nw, nh = job_nets[job]
        nw = np.asarray(nw)
        nh = np.asarray(nh)
        if nw.shape != nh.shape:
            raise ValueError(f"{job}: span arrays disagree "
                             f"{nw.shape} vs {nh.shape}")
        for i in range(len(nw)):
            owners.append((job, i))
        need_w_all.append(nw)
        need_h_all.append(nh)
    if not owners:
        return CrossJobPlan(rungs=[], jobs=jobs)
    need_w = np.concatenate(need_w_all)
    need_h = np.concatenate(need_h_all)

    classes, assign = _size_class_buckets(
        need_w, need_h, nx, ny, min_count=min_count, base=base)

    rungs: List[RungPlan] = []
    for k, tile in enumerate(list(classes) + [None]):
        idx = np.nonzero(assign == k)[0]
        if len(idx) == 0:
            continue
        if tile is not None:
            cnx, cny = tile
            shx, shy = (W, cnx, cny + 1), (W, cnx + 1, cny)
        else:
            shx, shy = (W, NX, NYp1), (W, NXp1, NY)
        lay = packed_layout(shx, shy, lane_mult=lm)
        g = auto_block_nets(shx, shy, len(idx), lane_mult=lm)
        rungs.append(RungPlan(
            tile=tile, shape_x=shx, shape_y=shy, block_nets=g,
            lane_occupancy=round(lay.lane_occupancy(g), 4),
            slots=[owners[i] for i in idx]))

    plan = CrossJobPlan(rungs=rungs, jobs=jobs)
    if publish_gauges and rungs:
        get_metrics().set_gauges({
            "route.serve.pack.jobs": len(jobs),
            "route.serve.pack.shared_rungs": len(rungs),
            "route.serve.pack.nets": plan.total_nets,
            "route.serve.pack.lane_occupancy": plan.lane_occupancy,
        })
    return plan
