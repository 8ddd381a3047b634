"""Timing graph construction (host side).

Equivalent of the reference's timing-graph build
(vpr/SRC/timing/path_delay.c:284 alloc_and_load_timing_graph_new): a DAG of
tnodes over the *logical* primitives with per-connection delays.  Where the
reference allocates pin-level tnodes inside every pb_graph, our cluster
model (arch.model.BlockType T_comb/T_setup/T_clk_to_q stand-ins) needs only
primitive-level nodes:

  inpad        -> one OUT tnode, startpoint (arrival 0)
  lut          -> one OUT tnode; in-edges carry net delay + T_comb
  ff           -> an IN tnode (endpoint; in-edge carries net delay + T_setup)
                  and an OUT tnode (startpoint seeded with T_clk_to_q)
  outpad       -> one IN tnode, endpoint
  hard block   -> by its block type's timing kind (BlockType.combinational)
    registered (a RAM): FF semantics a PIN, an IN tnode (endpoint,
                  T_setup) a used input pin and an OUT tnode (startpoint,
                  T_clk_to_q) a used output pin
    combinational (the published mult_36): an IN tnode a used input pin
                  (in-edge: the routed connection), ONE junction tnode a
                  block (zero-delay in-edges from its IN tnodes: the max
                  over the used inputs) and an OUT tnode a used output
                  pin (in-edge from the junction at the pin-to-pin delay
                  of the primitive's mode): every output depends on every
                  used input at one delay, as the published
                  <delay_constant> says, through pins + 1 edges where
                  the complete bipartite form needs inputs x outputs;
                  no endpoint, no startpoint, and the loop check below
                  sees through the block

Each timing edge's delay is  const + routed_delay[ridx]  where ridx indexes
the router's flat per-(net, sink) delay array (the t_net_timing coupling of
vpr_types.h:1134 / path_delay.c:457 load_timing_graph_net_delays_new):
intra-cluster connections get a constant local-interconnect delay and
ridx = -1; inter-cluster connections get ridx >= 0 so every STA call sees
the latest routed delays without rebuilding the graph.

The DAG is levelized on the host once (depth bounds the number of device
relaxation sweeps); clock nets are ideal (no data edges through them,
path_delay.c skips clock nets the same way).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..netlist.netlist import (LogicalNetlist, PRIM_FF, PRIM_HARD,
                               PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD)
from ..obs import get_metrics, span
from ..netlist.packed import PackedNetlist
from ..rr.terminals import NetTerminals

# intra-cluster feedback-path delay (local output->input mux inside a CLB);
# stands in for VPR7's intra-pb interconnect delays
T_LOCAL = 150e-12

# the backward sweep reads the out-edge ELL [T, D] once a level, and D
# is the widest tnode's: a primary input that feeds 260 LUT pins makes
# every tnode pay for 285 out-edge slots.  A tnode's out-edges past
# this many go to a flat overflow list the sweep folds in by a
# scatter-min (timing/sta.py); no tnode of the circuits whose widest
# net has a dozen sinks comes near it (their D is 6 to 19)
OUT_ELL_CAP = 32

# the forward sweep reads the in-edge ELL [T, D] once a level.  Its D
# follows the LUT: the widest in-degree of the tnodes that are NOT the
# junction of a combinational hard block (a LUT's K, whatever else the
# circuit holds).  A junction's in-edges past D go to a flat overflow
# list the sweep folds in by a scatter-max (timing/sta.py, the mirror
# of the out-edge list above): a 36-operand-bit multiplier costs 30
# list entries a block, not 30 more columns a tnode


def _rank_within(ends: np.ndarray, deg: np.ndarray) -> np.ndarray:
    """Each edge's rank among the edges of its own ``ends`` node, in
    edge order (``_ell``'s slot); ``deg`` the nodes' edge counts."""
    order = np.argsort(ends, kind="stable")
    starts = np.zeros(len(deg) + 1, dtype=np.int64)
    np.cumsum(deg, out=starts[1:])
    rank = np.zeros(len(ends), dtype=np.int64)
    rank[order] = np.arange(len(ends)) - starts[ends[order]]
    return rank


def _widest_other(deg: np.ndarray, junction: np.ndarray) -> int:
    """The widest degree among the tnodes that are no junction."""
    rest = deg[~junction]
    return max(1, int(rest.max())) if rest.size else 1


def _ell(num_nodes: int, ends: np.ndarray, other: np.ndarray,
         const: np.ndarray, ridx: np.ndarray):
    """Edge list grouped by ``ends`` -> ELL arrays padded to max degree."""
    order = np.argsort(ends, kind="stable")
    ends, other = ends[order], other[order]
    const, ridx = const[order], ridx[order]
    deg = np.bincount(ends, minlength=num_nodes)
    D = max(1, int(deg.max()) if num_nodes else 1)
    starts = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=starts[1:])
    slot = np.arange(len(ends)) - starts[ends]
    e_other = np.zeros((num_nodes, D), dtype=np.int32)
    e_const = np.zeros((num_nodes, D), dtype=np.float32)
    e_ridx = np.full((num_nodes, D), -1, dtype=np.int32)
    e_valid = np.zeros((num_nodes, D), dtype=bool)
    e_other[ends, slot] = other
    e_const[ends, slot] = const
    e_ridx[ends, slot] = ridx
    e_valid[ends, slot] = True
    return e_other, e_const, e_ridx, e_valid


@dataclass
class TimingGraph:
    """Host arrays describing the timing DAG (device copies made by sta)."""
    num_tnodes: int
    depth: int                 # DAG level count (bounds relaxation sweeps)
    # in-edge ELL (forward/arrival sweep): edge (in_src[v,d] -> v)
    in_src: np.ndarray         # int32 [T, D]
    in_const: np.ndarray      # f32   [T, D] constant delay part
    in_ridx: np.ndarray       # int32 [T, D] flat (net, sink) index or -1
    in_valid: np.ndarray      # bool  [T, D]
    # out-edge ELL (backward/required sweep): edge (v -> out_dst[v,d])
    out_dst: np.ndarray
    out_const: np.ndarray
    out_ridx: np.ndarray
    out_valid: np.ndarray
    arrival0: np.ndarray       # f32 [T] startpoint seeds (-inf elsewhere)
    is_endpoint: np.ndarray    # bool [T]
    num_route_slots: int       # size of the routed-delay vector: R * Smax,
    #                            or the fanout classes' R_c * S_c summed
    # diagnostics: tnode -> primitive index
    tnode_prim: np.ndarray
    # diagnostics: tnode -> (role, net): "out" a pad's, LUT's or
    # flip-flop's output, "in" a flip-flop's D or an output pad, "hin" /
    # "hout" a hard block's pin on ``net``, "junction" a combinational
    # hard block's max node
    tnode_pin: list = None
    # multi-clock (SDC): endpoint -> clock-domain index into ``domains``
    # (-1 = unclocked endpoint, e.g. outpads: constrained by the default)
    endpoint_domain: np.ndarray = None   # int32 [T]
    domains: list = None                 # domain index -> clock net name
    # SDC I/O constraints (set_input_delay / set_output_delay): pad
    # port/net name -> tnode (inpads keyed by the net they drive,
    # outpads by both the pad name and the net they read)
    inpad_tnode: dict = None
    outpad_tnode: dict = None
    # [R, Smax] the (net, sink) -> routed-delay slot map where the
    # terminals have several fanout classes (NetTerminals.sink_slots);
    # None where slot = r * Smax + s
    route_slots: np.ndarray = None
    # the out-edges past OUT_ELL_CAP a tnode, flat: (src [E], dst [E],
    # const [E], ridx [E]); None where no tnode has that many
    out_overflow: tuple = None
    # the in-edges of combinational hard blocks' junctions past the
    # in-edge ELL's width, flat: (dst [E], src [E], const [E],
    # ridx [E]); None where no junction is wider than the table (every
    # graph without a combinational hard block)
    in_overflow: tuple = None
    # the junction tnode of each combinational hard block (int32 [Nj]);
    # None where the circuit has none.  An edge OUT of a junction is a
    # hard block's pin-to-pin arc (critical_path_hard_arcs)
    comb_junction: np.ndarray = None
    # every in-edge of a tnode wider than the in-edge ELL, those the
    # ELL holds among them
    in_edges_wide: int = 0

    @property
    def num_in_edges(self) -> int:
        return int(self.in_valid.sum()) + (
            0 if self.in_overflow is None else len(self.in_overflow[0]))


def build_timing_graph(nl: LogicalNetlist, pnl: PackedNetlist,
                       term: NetTerminals,
                       t_local: float = T_LOCAL) -> TimingGraph:
    """Build the DAG.  ``term`` supplies the routed-net numbering the delay
    vector uses; pnl supplies prim->block placement of the packing."""
    with span("timing.graph.build", cat="timing") as sp:
        tg = _build(nl, pnl, term, t_local)
        gauges = {
            "route.timing.tnodes": tg.num_tnodes,
            "route.timing.depth": tg.depth,
            "route.timing.in_edges": tg.num_in_edges,
            "route.timing.in_edges_wide": tg.in_edges_wide,
            "route.timing.comb_hard_blocks": (
                0 if tg.comb_junction is None else len(tg.comb_junction)),
        }
        get_metrics().set_gauges(gauges)
        sp.set(in_width=int(tg.in_src.shape[1]),
               **{k.rsplit(".", 1)[1]: v for k, v in gauges.items()})
    return tg


def _build(nl: LogicalNetlist, pnl: PackedNetlist, term: NetTerminals,
           t_local: float) -> TimingGraph:
    slots = term.sink_slots()

    block_of_prim = {}
    for bi, b in enumerate(pnl.blocks):
        for p in b.prims:
            block_of_prim[p] = bi

    # (packed net index, sink block) -> flat routed-delay index
    r_of_net = {int(ni): r for r, ni in enumerate(term.net_ids)}
    conn_ridx = {}
    for ni, r in r_of_net.items():
        for s, pin in enumerate(pnl.nets[ni].sinks):
            conn_ridx[(ni, pin.block)] = int(slots[r, s])

    clocks = set(nl.clocks)

    # ---- tnode numbering ----
    n_prims = len(nl.primitives)
    out_tnode = np.full(n_prims, -1, dtype=np.int32)
    in_tnode = np.full(n_prims, -1, dtype=np.int32)   # ff.IN / outpad.IN
    tnode_prim = []
    hard_in: dict = {}      # hard prim -> {input net: tnode}
    hard_out: dict = {}     # hard prim -> {output net: tnode}
    comb_j: dict = {}       # combinational hard prim -> junction tnode

    tnode_pin = []          # per tnode (role, net): see TimingGraph

    def new_tnode(p, role="out", net=None):
        tnode_prim.append(p)
        tnode_pin.append((role, net))
        return len(tnode_prim) - 1

    for i, p in enumerate(nl.primitives):
        if p.kind == PRIM_INPAD:
            out_tnode[i] = new_tnode(i)
        elif p.kind == PRIM_LUT:
            out_tnode[i] = new_tnode(i)
        elif p.kind == PRIM_FF:
            in_tnode[i] = new_tnode(i, "in")
            out_tnode[i] = new_tnode(i)
        elif p.kind == PRIM_HARD:
            # a node a connected input PIN and a node a connected output
            # pin, whatever the block's timing kind.  Registered (a
            # RAM): FF semantics at the block's timing, the input nodes
            # setup endpoints and the output nodes clk-to-q launch
            # points.  Combinational (the published multiplier): one
            # junction node between them (the module docstring)
            hard_in[i] = {n: new_tnode(i, "hin", n)
                          for n in dict.fromkeys(p.inputs)
                          if n is not None and n not in clocks}
            if pnl.block_type(block_of_prim[i]).combinational:
                comb_j[i] = new_tnode(i, "junction")
            hard_out[i] = {n: new_tnode(i, "hout", n) for n in p.outputs
                           if n is not None}
        elif p.kind == PRIM_OUTPAD:
            in_tnode[i] = new_tnode(i, "in")
    T = len(tnode_prim)

    arrival0 = np.full(T, -np.inf, dtype=np.float32)
    is_endpoint = np.zeros(T, dtype=bool)
    # clock domains (SDC multi-clock): one per distinct clock net
    domains = sorted(clocks)
    dom_of = {c: k for k, c in enumerate(domains)}
    endpoint_domain = np.full(T, -1, dtype=np.int32)
    inpad_tnode: dict = {}
    outpad_tnode: dict = {}
    _outpad_dup: set = set()
    for i, p in enumerate(nl.primitives):
        bt = pnl.block_type(block_of_prim[i])
        if p.kind == PRIM_INPAD:
            arrival0[out_tnode[i]] = 0.0
            inpad_tnode[p.name] = int(out_tnode[i])
            if p.output is not None:
                inpad_tnode[p.output] = int(out_tnode[i])
        elif p.kind in (PRIM_FF, PRIM_HARD) and i not in comb_j:
            ins, outs = (([in_tnode[i]], [out_tnode[i]])
                         if p.kind == PRIM_FF else
                         (list(hard_in[i].values()),
                          list(hard_out[i].values())))
            arrival0[outs] = bt.T_clk_to_q
            is_endpoint[ins] = True
            if p.clock is not None:
                endpoint_domain[ins] = dom_of[p.clock]
        elif p.kind == PRIM_OUTPAD:
            is_endpoint[in_tnode[i]] = True
            outpad_tnode[p.name] = int(in_tnode[i])
            if p.inputs and p.inputs[0] is not None:
                # net-name key only while unambiguous: two pads reading
                # the same net must not alias (the pad NAME always works)
                n = p.inputs[0]
                if n in outpad_tnode and outpad_tnode[n] != int(
                        in_tnode[i]):
                    _outpad_dup.add(n)
                else:
                    outpad_tnode[n] = int(in_tnode[i])

    # ---- edges ----
    e_src, e_dst, e_const, e_ridx = [], [], [], []
    for i, p in enumerate(nl.primitives):
        if p.kind in (PRIM_INPAD,):
            continue
        bt = pnl.block_type(block_of_prim[i])
        if p.kind == PRIM_LUT:
            dst, extra = out_tnode[i], bt.T_comb
        elif i in comb_j:
            # the connection ends on the pin's own node; the block's
            # delay lies on the junction's out-edges
            dst, extra = -1, 0.0
            for t in hard_in[i].values():
                e_src.append(t); e_dst.append(comb_j[i])
                e_const.append(0.0); e_ridx.append(-1)
            for t in hard_out[i].values():
                e_src.append(comb_j[i]); e_dst.append(t)
                e_const.append(bt.comb_delay(p.mode)); e_ridx.append(-1)
        elif p.kind in (PRIM_FF, PRIM_HARD):
            dst, extra = in_tnode[i], bt.T_setup
        else:                                       # outpad
            dst, extra = in_tnode[i], 0.0
        # a hard block: its connected input nets, each to its own node
        for n in hard_in[i] if p.kind == PRIM_HARD else p.inputs:
            if n is None or n in clocks:
                continue          # unconnected port / ideal clock network
            dp = nl.net_driver[n]
            src = (hard_out[dp][n] if dp in hard_out else out_tnode[dp])
            if p.kind == PRIM_HARD:
                dst = hard_in[i][n]
            const, ridx = extra, -1
            if block_of_prim[dp] == block_of_prim[i]:
                const += t_local
            else:
                ni = pnl.net_index.get(n, -1)
                key = (ni, block_of_prim[i])
                if key in conn_ridx:
                    ridx = conn_ridx[key]
                # else: global/unrouted inter-cluster net -> const only
            e_src.append(src); e_dst.append(dst)
            e_const.append(const); e_ridx.append(ridx)

    e_src = np.array(e_src, dtype=np.int32)
    e_dst = np.array(e_dst, dtype=np.int32)
    e_const = np.array(e_const, dtype=np.float32)
    e_ridx = np.array(e_ridx, dtype=np.int32)

    # ---- levelize (Kahn) for the sweep-depth bound ----
    indeg = np.bincount(e_dst, minlength=T) if len(e_dst) else np.zeros(T, int)
    level = np.zeros(T, dtype=np.int32)
    from collections import deque
    adj_starts = None
    order_e = np.argsort(e_src, kind="stable") if len(e_src) else e_src
    dsts_sorted = e_dst[order_e]
    deg_out = np.bincount(e_src, minlength=T) if len(e_src) else np.zeros(T, int)
    starts = np.zeros(T + 1, dtype=np.int64)
    np.cumsum(deg_out, out=starts[1:])
    q = deque(int(v) for v in np.where(indeg == 0)[0])
    seen = 0
    indeg_w = indeg.copy()
    while q:
        v = q.popleft()
        seen += 1
        for e in range(starts[v], starts[v + 1]):
            w = int(dsts_sorted[e])
            if level[w] < level[v] + 1:
                level[w] = level[v] + 1
            indeg_w[w] -= 1
            if indeg_w[w] == 0:
                q.append(w)
    if seen != T:
        raise ValueError("combinational loop in timing graph")
    depth = int(level.max()) + 1 if T else 1

    # the in-edge ELL at the width of the widest tnode that is not a
    # combinational block's junction; a junction's in-edges past it
    # flat (all False, and the arrays the parent's, where there is none)
    indeg_all = np.asarray(indeg, dtype=np.int64)
    junction = np.zeros(T, dtype=bool)
    junction[list(comb_j.values())] = True
    D_in = _widest_other(indeg_all, junction)
    over_in = _rank_within(e_dst, indeg_all) >= D_in
    in_overflow = ((e_dst[over_in], e_src[over_in], e_const[over_in],
                    e_ridx[over_in]) if over_in.any() else None)
    in_src, in_const, in_ridx, in_valid = _ell(
        T, e_dst[~over_in], e_src[~over_in], e_const[~over_in],
        e_ridx[~over_in])
    # a tnode's first OUT_ELL_CAP out-edges in the ELL, the rest flat; a
    # junction's (one out-edge a used output pin) at most as many as the
    # widest other tnode has, so that the table's width follows the
    # nets' fanout and not the multiplier's 36 product bits
    over = _rank_within(e_src, deg_out) >= min(
        OUT_ELL_CAP, _widest_other(np.asarray(deg_out), junction))
    out_overflow = ((e_src[over], e_dst[over], e_const[over], e_ridx[over])
                    if over.any() else None)
    out_dst, out_const, out_ridx, out_valid = _ell(
        T, e_src[~over], e_dst[~over], e_const[~over], e_ridx[~over])
    return TimingGraph(
        num_tnodes=T, depth=depth,
        in_src=in_src, in_const=in_const, in_ridx=in_ridx, in_valid=in_valid,
        out_dst=out_dst, out_const=out_const, out_ridx=out_ridx,
        out_valid=out_valid,
        arrival0=arrival0, is_endpoint=is_endpoint,
        num_route_slots=int(slots.max()) + 1 if slots.size else 0,
        route_slots=(slots if len(term.fanout_classes) > 1 else None),
        out_overflow=out_overflow,
        in_overflow=in_overflow,
        comb_junction=(np.array(sorted(comb_j.values()), dtype=np.int32)
                       if comb_j else None),
        in_edges_wide=int(indeg_all[indeg_all > D_in].sum()),
        tnode_prim=np.array(tnode_prim, dtype=np.int32),
        tnode_pin=tnode_pin,
        endpoint_domain=endpoint_domain, domains=domains,
        inpad_tnode=inpad_tnode,
        outpad_tnode={k: v for k, v in outpad_tnode.items()
                      if k not in _outpad_dup},
    )
