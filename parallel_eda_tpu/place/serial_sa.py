"""ctypes binding for the native serial SA placer (native/serial_sa.cc).

The C++ annealer is the CPU measurement baseline for BASELINE.md's "SA
moves/sec/chip" metric (semantics of vpr/SRC/place/place.c try_place):
an honest serial-CPU speed class to hold the batched TPU placer against
— a pure-Python loop would overstate the device win by an order of
magnitude.  Built on first use with g++ -O3 (toolchain is in the image) on the
machine that loads it (nativelib.build_native).
"""

from __future__ import annotations

import ctypes
import time
from dataclasses import dataclass

import numpy as np

from ..nativelib import build_native
from ..netlist.packed import PackedNetlist
from ..rr.grid import DeviceGrid

_FLAGS = ("-O3", "-march=native")


def _build_lib() -> str:
    return build_native("serial_sa.cc", _FLAGS)


_lib = None


def _get_lib():
    global _lib
    if _lib is None:
        _lib = ctypes.CDLL(_build_lib())
        _lib.serial_sa_place.restype = ctypes.c_int64
    return _lib


@dataclass
class SerialPlaceResult:
    pos: np.ndarray
    proposed: int
    accepted: int
    final_cost: float
    temps: int
    wall_s: float

    @property
    def moves_per_sec(self) -> float:
        return self.proposed / max(self.wall_s, 1e-12)


def _tables(pnl: PackedNetlist, grid: DeviceGrid):
    """Flat net/block tables — independently derived from the packed
    netlist (not shared with place.sa's builder: baseline independence)."""
    NB = pnl.num_blocks
    costed = [i for i, n in enumerate(pnl.nets)
              if not n.is_global and n.sinks]
    rows = []
    for ni in costed:
        n = pnl.nets[ni]
        blks = [n.driver.block] + [p.block for p in n.sinks]
        uniq = list(dict.fromkeys(blks))
        rows.append(uniq)
    NN = max(1, len(rows))
    P = max(1, max((len(r) for r in rows), default=1))
    net_blk = np.full((NN, P), -1, dtype=np.int32)
    for i, r in enumerate(rows):
        net_blk[i, :len(r)] = r
    from .sa import crossing_factor

    npins = np.array([len(r) for r in rows] + [1] * (NN - len(rows)),
                     dtype=np.int32)[:NN]
    net_q = np.asarray(crossing_factor(npins), dtype=np.float32)

    blk_rows = [[] for _ in range(NB)]
    for i, r in enumerate(rows):
        for b in r:
            blk_rows[b].append(i)
    F = max(1, max((len(x) for x in blk_rows), default=1))
    blk_net = np.full((NB, F), -1, dtype=np.int32)
    for b, nets in enumerate(blk_rows):
        blk_net[b, :len(nets)] = nets

    is_io = np.array([pnl.block_type(i).is_io for i in range(NB)],
                     dtype=np.uint8)
    ring = np.array(grid.io_sites(), dtype=np.int32)
    return net_blk, net_q, blk_net, is_io, ring


def _type_tables(pnl: PackedNetlist, grid: DeviceGrid, is_io):
    """Sites by block type: per block its interior type (-1 for I/O),
    per type the nearest own column of every x and the rows a block
    occupies (its anchors are the rows 1 + k * height)."""
    names = sorted({b.type_name for b, io in zip(pnl.blocks, is_io)
                    if not io})
    tid = {t: i for i, t in enumerate(names)}
    type_id = np.array([-1 if io else tid[b.type_name]
                        for b, io in zip(pnl.blocks, is_io)],
                       dtype=np.int32)
    col_snap = np.ones((max(1, len(names)), grid.nx + 2), dtype=np.int32)
    type_h = np.ones(max(1, len(names)), dtype=np.int32)
    for t, i in tid.items():
        cols = np.array([x for x in range(1, grid.nx + 1)
                         if grid.interior_type_name(x) == t])
        if not len(cols):
            raise ValueError(f"block type '{t}' has no columns")
        xs = np.arange(grid.nx + 2)
        col_snap[i] = cols[np.abs(cols[None, :] - xs[:, None]).argmin(1)]
        type_h[i] = grid.height_of(t)
    return type_id, col_snap, type_h


def serial_sa_place(pnl: PackedNetlist, grid: DeviceGrid,
                    pos0: np.ndarray, inner_num: float = 1.0,
                    exit_t_frac: float = 0.005, max_temps: int = 500,
                    seed: int = 0) -> SerialPlaceResult:
    lib = _get_lib()
    net_blk, net_q, blk_net, is_io, ring_xy = _tables(pnl, grid)
    type_id, col_snap, type_h = _type_tables(pnl, grid, is_io)
    NB = pnl.num_blocks
    NN, P = net_blk.shape
    F = blk_net.shape[1]
    NRING = ring_xy.shape[0]

    ring_of = {tuple(xy): i for i, xy in enumerate(grid.io_sites())}
    pos = np.ascontiguousarray(pos0.astype(np.int32)).copy()
    ring = np.full(NB, -1, dtype=np.int32)
    NS = grid.nx * grid.ny + NRING * grid.io_capacity
    occ = np.full(NS, -1, dtype=np.int32)
    for i in range(NB):
        if is_io[i]:
            ring[i] = ring_of[(int(pos[i, 0]), int(pos[i, 1]))]
            s = grid.nx * grid.ny + ring[i] * grid.io_capacity \
                + int(pos[i, 2])
        else:
            s = (int(pos[i, 1]) - 1) * grid.nx + (int(pos[i, 0]) - 1)
        if occ[s] != -1:
            raise ValueError("initial placement has site collisions")
        occ[s] = i

    stats = np.zeros(3, dtype=np.float64)
    c = ctypes
    t0 = time.time()
    proposed = lib.serial_sa_place(
        net_blk.ctypes.data_as(c.c_void_p),
        net_q.ctypes.data_as(c.c_void_p),
        blk_net.ctypes.data_as(c.c_void_p),
        is_io.ctypes.data_as(c.c_void_p),
        ring_xy.ctypes.data_as(c.c_void_p),
        type_id.ctypes.data_as(c.c_void_p),
        col_snap.ctypes.data_as(c.c_void_p),
        type_h.ctypes.data_as(c.c_void_p),
        c.c_int32(NN), c.c_int32(P), c.c_int32(NB), c.c_int32(F),
        c.c_int32(NRING), c.c_int32(grid.nx), c.c_int32(grid.ny),
        c.c_int32(grid.io_capacity),
        pos.ctypes.data_as(c.c_void_p),
        ring.ctypes.data_as(c.c_void_p),
        occ.ctypes.data_as(c.c_void_p),
        c.c_double(inner_num), c.c_double(exit_t_frac),
        c.c_int32(max_temps), c.c_uint64(seed),
        stats.ctypes.data_as(c.c_void_p))
    wall = time.time() - t0
    return SerialPlaceResult(
        pos=pos, proposed=int(proposed), accepted=int(stats[0]),
        final_cost=float(stats[1]), temps=int(stats[2]), wall_s=wall)
