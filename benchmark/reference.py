"""The plain reference: what a routing has to satisfy, worked out from
the rr graph's arrays and the routed trees alone.

numpy and ``heapq`` only.  Nothing of the program is imported and
nothing the program computed is trusted: the graph arrives as plain
arrays (it is the problem, as a benchmark BLIF and its architecture
file are), the routing as the ``paths`` / ``sink_delay`` arrays the
timed path returned.

Three questions, one function each (``judge`` asks the first two of
one routing):

* ``check_legality`` -- does every net's routing form a tree over real
  rr edges from its SOURCE to each of its SINKs, and is no rr node used
  by more nets than its capacity?  (VPR ``check_route.c`` semantics.)
* ``tree_sink_delays`` -- the delay to every sink along its own routed
  tree, summed in float64 from the graph's per-edge delays.  The
  program reports the same number from its float32 relaxation planes;
  planes kept in bfloat16 miss it by about a part in a thousand.
* ``dijkstra_wire_dist`` -- textbook Dijkstra in float64 over the wire
  nodes for one net's cost field: the optimum a relaxation fixpoint of
  the same field has to reach.
"""

from __future__ import annotations

import functools
import heapq
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

# rr node types: the numbering is part of
# the graph's format, repeated here so that nothing is imported
SOURCE, SINK, OPIN, IPIN, CHANX, CHANY = 0, 1, 2, 3, 4, 5


@dataclass
class GraphArrays:
    """The rr graph as the reference reads it: node types and
    capacities, and the in-edge CSR with one delay per edge."""
    node_type: np.ndarray       # [N]
    capacity: np.ndarray        # [N]
    in_row_ptr: np.ndarray      # [N + 1]
    in_src: np.ndarray          # [E] source node of each in-edge
    in_delay: np.ndarray        # [E] seconds

    @property
    def num_nodes(self) -> int:
        return len(self.node_type)

    @classmethod
    def of(cls, rr) -> "GraphArrays":
        """From any object that carries arrays of these names."""
        return cls(*(np.asarray(getattr(rr, k)) for k in (
            "node_type", "capacity", "in_row_ptr", "in_src",
            "in_delay")))

    @functools.cached_property
    def edge_delays(self) -> Dict[int, float]:
        """{src * N + dst: delay} over every edge, float64."""
        N = self.num_nodes
        dst = np.repeat(np.arange(N, dtype=np.int64),
                        np.diff(self.in_row_ptr))
        keys = self.in_src.astype(np.int64) * N + dst
        return dict(zip(keys.tolist(),
                        self.in_delay.astype(np.float64).tolist()))


def _net_parents(paths_r: np.ndarray, sinks_r: np.ndarray, N: int,
                 problems: List[str], r: int) -> Dict[int, int]:
    """{child: parent} of one net from its sink -> tree segments
    (each stored sink first, join node last)."""
    parent: Dict[int, int] = {}
    for s, sink in enumerate(sinks_r.tolist()):
        seg = paths_r[s]
        seg = seg[seg < N].tolist()
        if not seg:
            problems.append(f"net {r} sink {s}: no path")
            continue
        if seg[0] != sink:
            problems.append(f"net {r} sink {s}: segment starts at node "
                            f"{seg[0]}, not at its sink {sink}")
        for child, par in zip(seg, seg[1:]):
            if parent.setdefault(child, par) != par:
                problems.append(f"net {r}: node {child} has two parents")
    return parent


def check_legality(g: GraphArrays, source, sinks, num_sinks,
                   paths) -> dict:
    """Every violation found, as text, plus what was counted on the way.

    Returns ``{"problems": [...], "wirelength": wire nodes used,
    "occ": [N] nets on each node, "parents": per-net {child: parent}}``.
    A routing is legal when ``problems`` is empty."""
    N = g.num_nodes
    edges = g.edge_delays
    occ = np.zeros(N, dtype=np.int64)
    problems: List[str] = []
    parents: List[Dict[int, int]] = []
    wirelength = 0
    for r in range(len(source)):
        src = int(source[r])
        ns = int(num_sinks[r])
        parent = _net_parents(paths[r], sinks[r, :ns], N, problems, r)
        parents.append(parent)
        for child, par in parent.items():
            if par * N + child not in edges:
                problems.append(f"net {r}: no rr edge {par} -> {child}")
        # every sink must walk up to the source without a cycle
        for s in range(ns):
            v, steps = int(sinks[r, s]), 0
            while v != src and v in parent and steps <= len(parent):
                v, steps = parent[v], steps + 1
            if v != src:
                problems.append(f"net {r} sink {s}: not connected to "
                                f"its source (walk ends at node {v})")
        own_sinks = set(sinks[r, :ns].tolist())
        used = set(parent) | set(parent.values()) | {src}
        for v in used:
            t = g.node_type[v]
            if t == SINK and v not in own_sinks:
                problems.append(f"net {r}: routes through sink {v} of "
                                f"another net")
            if t == SOURCE and v != src:
                problems.append(f"net {r}: routes through source {v} "
                                f"of another net")
            if t in (CHANX, CHANY):
                wirelength += 1
            occ[v] += 1
    over = np.flatnonzero(occ > g.capacity.astype(np.int64))
    for v in over[:8].tolist():
        problems.append(f"node {v}: used by {int(occ[v])} nets, "
                        f"capacity {int(g.capacity[v])}")
    if len(over) > 8:
        problems.append(f"... {len(over)} over-used nodes in all")
    return {"problems": problems, "wirelength": wirelength, "occ": occ,
            "parents": parents}


def tree_sink_delays(g: GraphArrays, source, sinks, num_sinks,
                     parents) -> np.ndarray:
    """[R, Smax] float64: delay from the net's SOURCE to each sink along
    the net's own tree (NaN where there is no sink, or no path)."""
    N = g.num_nodes
    edges = g.edge_delays
    out = np.full(np.asarray(sinks).shape, np.nan)
    for r, parent in enumerate(parents):
        src = int(source[r])
        for s in range(int(num_sinks[r])):
            v, total, steps = int(sinks[r, s]), 0.0, 0
            while v != src and v in parent and steps <= len(parent):
                u = parent[v]
                total += edges.get(u * N + v, np.nan)
                v, steps = u, steps + 1
            if v == src:
                out[r, s] = total
    return out


def sink_delay_gap(ref: np.ndarray, got: np.ndarray) -> float:
    """Widest relative gap between the program's sink delays and the
    reference's, over every sink the reference could follow."""
    have = np.isfinite(ref) & (ref > 0)
    if not have.any():
        return float("inf")
    got = np.asarray(got, dtype=np.float64)[have]
    return float(np.max(np.abs(got - ref[have]) / ref[have]))


def judge(g: GraphArrays, source, sinks, num_sinks, paths,
          sink_delay) -> dict:
    """One routing against the reference: the problems found, the
    wirelength and occupancy recounted from the trees, and the widest
    sink-delay gap."""
    legal = check_legality(g, source, sinks, num_sinks, paths)
    ref_delay = tree_sink_delays(g, source, sinks, num_sinks,
                                 legal["parents"])
    return {"problems": legal["problems"],
            "wirelength": legal["wirelength"], "occ": legal["occ"],
            "delay_gap": sink_delay_gap(ref_delay, sink_delay)}


def dijkstra_wire_dist(g: GraphArrays, seeds, cong: np.ndarray,
                       crit: float) -> np.ndarray:
    """[N] float64 cost-to-reach of every wire node for one net.

    Entering node v over edge e costs ``crit * in_delay[e] + cong[v]``;
    ``seeds`` start at 0; only CHANX / CHANY nodes are searched (pins
    are endpoints: an OPIN is reached only from its SOURCE and an IPIN
    leads only to its SINK); ``cong`` is inf outside the net's box."""
    N = g.num_nodes
    wire = (g.node_type == CHANX) | (g.node_type == CHANY)
    # out-adjacency from the in-edge CSR, wires only
    dst = np.repeat(np.arange(N, dtype=np.int64), np.diff(g.in_row_ptr))
    keep = wire[dst] & wire[g.in_src]
    order = np.argsort(g.in_src[keep], kind="stable")
    o_src = g.in_src[keep][order]
    o_dst = dst[keep][order]
    o_del = g.in_delay[keep][order].astype(np.float64)
    ptr = np.searchsorted(o_src, np.arange(N + 1))
    cong = np.asarray(cong, dtype=np.float64)
    dist = np.full(N, np.inf)
    heap = []
    for v in seeds:
        dist[int(v)] = 0.0
        heap.append((0.0, int(v)))
    heapq.heapify(heap)
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(ptr[u], ptr[u + 1]):
            v = int(o_dst[e])
            nd = d + crit * o_del[e] + cong[v]
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    dist[~wire] = np.inf
    return dist


def relax_gap(ref: np.ndarray, got: np.ndarray) -> float:
    """Widest relative gap between a relaxation's distances and
    Dijkstra's over the nodes either reaches; inf where one reaches a
    node and the other does not."""
    got = np.asarray(got, dtype=np.float64)
    fin_r, fin_g = np.isfinite(ref), np.isfinite(got)
    if (fin_r != fin_g).any():
        return float("inf")
    m = fin_r & (ref > 0)
    if not m.any():
        return 0.0
    return float(np.max(np.abs(got[m] - ref[m]) / ref[m]))
