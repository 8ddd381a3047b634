"""Net → rr-node terminal mapping.

Equivalent of the reference's ``net_rr_terminals`` setup
(vpr/SRC/route/route_common.c alloc_and_load_rr_node_route_structs /
init.cxx:392 init_nets): for each routable net, the SOURCE rr-node of its
driver pin's class and the SINK rr-node of each sink pin's class, plus the
bb_factor-expanded bounding box the router restricts its search to
(route.h:70-165 net_t semantics).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from ..netlist.packed import PackedNetlist
from ..obs import get_metrics
from .graph import RRGraph


@dataclass
class NetTerminals:
    """Flat arrays over routable nets (padded to max fanout: the host's
    tables stay dense, the device's are built a fanout class each,
    ``fanout_classes``)."""
    net_ids: np.ndarray        # [R] packed-netlist net index per routable net
    source: np.ndarray         # [R] SOURCE rr-node
    sinks: np.ndarray          # [R, Smax] SINK rr-nodes, -1 padded
    num_sinks: np.ndarray      # [R]
    bb_xmin: np.ndarray        # [R] bounding box (bb_factor expanded)
    bb_xmax: np.ndarray
    bb_ymin: np.ndarray
    bb_ymax: np.ndarray
    # [R] bool: the net has a terminal on a block of a typed column (a
    # RAM, a multiplier); None where the terminals were not built from
    # a placed netlist
    hard: Optional[np.ndarray] = None

    @property
    def num_nets(self) -> int:
        return len(self.net_ids)

    @property
    def max_sinks(self) -> int:
        return self.sinks.shape[1]

    @property
    def fanout_classes(self) -> List["FanoutClass"]:
        """The nets by fanout class (``fanout_ladder`` of the sink
        counts at the table's own width), built once."""
        if self._classes is None:
            self._classes = fanout_ladder(self.num_sinks, self.max_sinks)
        return self._classes

    _classes: Optional[List["FanoutClass"]] = field(
        default=None, init=False, repr=False, compare=False)

    def class_rows(self):
        """([R] each net's fanout class, [R] its row in that class's
        tables)."""
        return class_rows(self.fanout_classes)

    def sink_slots(self) -> np.ndarray:
        """[R, Smax] the slot of every (net, sink) in the flat vector
        of routed delays the STA reads: the classes' [R_c, S_c] tables
        laid end to end (r * Smax + s where there is one class), -1
        where the net's class has no such slot."""
        cls, row = self.class_rows()
        width = np.array([c.width for c in self.fanout_classes])
        size = np.array([c.width * len(c.nets)
                         for c in self.fanout_classes])
        base = np.cumsum(size) - size
        s = np.arange(self.max_sinks)[None, :]
        slots = (base[cls] + row * width[cls])[:, None] + s
        return np.where(s < width[cls][:, None], slots, -1)


@dataclass
class FanoutClass:
    """The nets of one fanout class: what a net of it costs follows
    ``width`` and not the widest net of the circuit."""
    width: int                 # S_c: sink slots a net of the class has
    nets: np.ndarray           # [R_c] routable-net indices, ascending


def class_rows(classes: List[FanoutClass]):
    """([R] each net's class, [R] its row in that class's tables)."""
    R = sum(len(c.nets) for c in classes)
    cls = np.zeros(R, dtype=np.int64)
    row = np.zeros(R, dtype=np.int64)
    for k, c in enumerate(classes):
        cls[c.nets] = k
        row[c.nets] = np.arange(len(c.nets))
    return cls, row


# a net of at most FANOUT_BASE sinks is of the first class, one of at
# most FANOUT_BASE * FANOUT_STEP of the second, and so on; a class of
# fewer than FANOUT_MIN_NETS nets joins the next one up
FANOUT_BASE, FANOUT_STEP, FANOUT_MIN_NETS = 16, 4, 8


def fanout_ladder(num_sinks: np.ndarray, width: int) -> List[FanoutClass]:
    """The fanout classes of a problem, read from its own sink counts.

    Rungs at FANOUT_BASE x FANOUT_STEP^k sinks: a net is of the first
    rung that holds its sink count.  A populated rung of fewer than
    FANOUT_MIN_NETS nets joins the next populated rung up (a class
    costs a dispatch a window and a set of compiled programs; the
    widest class takes what is left and joins nothing below it: that
    would make every net of the class below pay its width).  A class
    is as wide as its widest net, and the top class as wide as the
    table it was read from (``width``; a subset of a circuit keeps the
    circuit's).  A fixed function of the sink counts: every net of at
    most FANOUT_BASE sinks is ONE class of the table's width."""
    ns = np.asarray(num_sinks, dtype=np.int64)
    rung = np.zeros(len(ns), dtype=np.int64)
    cap = FANOUT_BASE
    while len(ns) and ns.max() > cap:
        rung += ns > cap
        cap *= FANOUT_STEP
    used = sorted(set(rung.tolist()))
    for k, nxt in zip(used, used[1:]):
        if (rung == k).sum() < FANOUT_MIN_NETS:
            rung[rung == k] = nxt
    out = []
    for k in sorted(set(rung.tolist())) or [0]:
        nets = np.flatnonzero(rung == k)
        out.append(FanoutClass(int(ns[nets].max()) if len(nets) else 1,
                               nets))
    out[-1].width = int(width)
    return out


def net_terminals(pnl: PackedNetlist, rr: RRGraph, pos: np.ndarray,
                  bb_factor: int = 3) -> NetTerminals:
    """``pos`` is [num_blocks, 3] (x, y, subtile).  bb_factor default mirrors
    SetupVPR.c:337.  Sets the gauge ``route.hetero.nets_hard`` (the
    routed nets with a terminal on a block of a typed column) and the
    three ``route.fanout.*`` gauges of the fanout classes."""
    routable = pnl.routed_nets
    R = len(routable)
    Smax = max((pnl.nets[i].num_sinks for i in routable), default=1)
    nx, ny = rr.grid.nx, rr.grid.ny

    source = np.zeros(R, dtype=np.int32)
    sinks = np.full((R, Smax), -1, dtype=np.int32)
    num_sinks = np.zeros(R, dtype=np.int32)
    bbx0 = np.zeros(R, dtype=np.int32); bbx1 = np.zeros(R, dtype=np.int32)
    bby0 = np.zeros(R, dtype=np.int32); bby1 = np.zeros(R, dtype=np.int32)
    col_types = set(rr.grid.col_types.values())
    hard = np.zeros(R, dtype=bool)

    for r, ni in enumerate(routable):
        net = pnl.nets[ni]
        bt = pnl.block_type(net.driver.block)
        x, y, z = (int(v) for v in pos[net.driver.block])
        k = bt.pin_class_of[net.driver.pin]
        source[r] = rr.src_of[(x, y, z, k)]
        # a block's position is its anchor; the box holds every row it
        # occupies (its pins are spread over them)
        xs, ys = [x], [y, y + bt.height - 1]
        for s, pin in enumerate(net.sinks):
            bt_s = pnl.block_type(pin.block)
            sx, sy, sz = (int(v) for v in pos[pin.block])
            ks = bt_s.pin_class_of[pin.pin]
            sinks[r, s] = rr.sink_of[(sx, sy, sz, ks)]
            xs.append(sx); ys += [sy, sy + bt_s.height - 1]
        num_sinks[r] = net.num_sinks
        hard[r] = any(pnl.blocks[p.block].type_name in col_types
                      for p in [net.driver] + net.sinks)
        bbx0[r] = max(0, min(xs) - bb_factor)
        bbx1[r] = min(nx + 1, max(xs) + bb_factor)
        bby0[r] = max(0, min(ys) - bb_factor)
        bby1[r] = min(ny + 1, max(ys) + bb_factor)

    term = NetTerminals(
        net_ids=np.array(routable, dtype=np.int32),
        source=source, sinks=sinks, num_sinks=num_sinks,
        bb_xmin=bbx0, bb_xmax=bbx1, bb_ymin=bby0, bb_ymax=bby1,
        hard=hard,
    )
    classes = term.fanout_classes
    get_metrics().set_gauges({
        "route.hetero.nets_hard": int(hard.sum()),
        "route.fanout.max_sinks": int(Smax),
        "route.fanout.classes": len(classes),
        # real sinks over the sink slots of all class tables
        "route.fanout.sink_slot_fill": int(num_sinks.sum()) / max(
            1, sum(c.width * len(c.nets) for c in classes))})
    return term


def subset_terminals(term: NetTerminals, frac: float,
                     seed: int = 1) -> NetTerminals:
    """Seeded random subset of the routable nets, SAME device grid.

    The multi-tenant serving layer needs "tiny job on a big device"
    workloads (a daemon serves one graph, so a small job cannot shrink
    the grid — it routes fewer nets on it).  The subset is drawn from
    ``seed`` alone, so a submission spec carrying (circuit seed,
    net_frac, net_seed) is a complete, replayable description of the
    job — delivery retries can never change what gets routed.  Max
    fanout padding is left untouched: the sliced job shares the solo
    circuit's Smax, keeping its dispatch shapes on the same ladder."""
    R = term.num_nets
    k = max(1, min(R, int(round(R * float(frac)))))
    if k >= R:
        return term
    idx = np.sort(np.random.RandomState(int(seed)).choice(
        R, size=k, replace=False))
    return NetTerminals(
        net_ids=term.net_ids[idx], source=term.source[idx],
        sinks=term.sinks[idx], num_sinks=term.num_sinks[idx],
        bb_xmin=term.bb_xmin[idx], bb_xmax=term.bb_xmax[idx],
        bb_ymin=term.bb_ymin[idx], bb_ymax=term.bb_ymax[idx],
        hard=None if term.hard is None else term.hard[idx])
