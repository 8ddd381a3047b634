"""The plain reference of the DEVICE: what a typed, tall-block device
and a placement on it have to satisfy, recounted from the
architecture's published numbers, the grid's size and plain arrays.

numpy only.  Nothing of the program is imported: the architecture
arrives as the ``published`` block of the configuration file, the rr
graph as arrays (node types, coordinates, ptc, capacities, the in-edge
CSR), the placement as block type names and (x, y, subtile) rows, the
nets as (block, pin) terminals beside the SOURCE / SINK nodes the
program chose for them.  ``reference.py`` judges a ROUTING on whatever
graph it is handed; this file judges the graph and the placement the
routing starts from, which no reference covered while every device was
an I/O ring around identical clusters.

The rules (VPR's ``SetupGrid.c`` and ``rr_graph.c``, as the issue that
brought this file states them):

* a block type with ``columns`` {start, repeat} owns the interior
  columns start, start + repeat, ...; every other interior column holds
  ``clb``; the perimeter, corners apart, holds ``io``;
* a block of ``height`` h is anchored at a row 1 + k * h of a column of
  its type, lies inside the grid, and no two footprints share a tile;
* pins are numbered inputs first, then outputs, then the block's
  ``clocks`` (``io``: pin 0 the pad's input, pin 1 its output, no
  clock), then its ``assumed_clocks``: clock pins the program gives a
  block whose published entry has none, which the configuration has to
  own up to or the count below refuses the graph; pin p of a block
  lies on row p % h of its footprint, so a tile row holds the pins the
  rule gives it and no other;
* a net's SOURCE / SINK node lies inside its block's footprint and is
  the node its pin's OPIN / IPIN is tied to; it has the capacity of the
  pin's class: ``inputs_equivalent`` makes a type's inputs ONE class,
  every other pin is a class of its own (so a hard block's data bit 3
  can never be reached through the pin of bit 7);
* every pin reaches ``max(1, round(Fc x W))`` distinct wires (Python's
  ``round``, the builder's own) in EACH channel beside its row: four
  for an interior tile, the channels run through a hard column, one for
  a pad tile.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

SOURCE, SINK, OPIN, IPIN, CHANX, CHANY = 0, 1, 2, 3, 4, 5


class BlockRule:
    """One block type, from its ``published`` entry."""

    def __init__(self, name: str, spec: dict):
        self.name = name
        self.height = int(spec.get("height", 1))
        self.capacity = int(spec.get("capacity", 1))
        self.columns = spec.get("columns")
        self.n_in = sum(spec["inputs"].values())
        self.n_out = sum(spec["outputs"].values())
        self.is_io = name == "io"
        # the source's own clock pins, and beside them those the
        # program adds to the published block (stated, never implied)
        self.num_pins = (self.n_in + self.n_out
                         + int(spec.get("clocks", 0))
                         + int(spec.get("assumed_clocks", 0)))
        self.inputs_equivalent = bool(spec.get("inputs_equivalent", False))

    def is_output(self, p: int) -> bool:
        return self.n_in <= p < self.n_in + self.n_out

    def class_capacity(self, p: int) -> int:
        return self.n_in if (self.inputs_equivalent
                             and p < self.n_in) else 1


def column_type(rules: Dict[str, BlockRule], x: int) -> str:
    """The type interior column x holds."""
    for r in rules.values():
        c = r.columns
        if c and x >= c["start"] and (x - c["start"]) % c["repeat"] == 0:
            return r.name
    return "clb"


def device_problems(published: dict, nx: int, ny: int, W: int, rr,
                    block_types: List[str], pos: np.ndarray,
                    nets: dict, limit: int = 20) -> List[str]:
    """Everything wrong with the device and the placement, as text (at
    most ``limit`` lines a rule); empty when all rules hold.

    ``rr``: any object with the arrays node_type, xlow, ylow, xhigh,
    yhigh, ptc, capacity, in_row_ptr, in_src.  ``nets``: source [R],
    sinks [R, S] (-1 pad), src_block / src_pin [R], sink_block /
    sink_pin [R, S]."""
    rules = {n: BlockRule(n, s) for n, s in published["blocks"].items()}
    fc = {True: float(published["Fc_out"]), False: float(published["Fc_in"])}
    out: List[str] = []

    def say(rule: str, lines: List[str]):
        out.extend(f"{rule}: {t}" for t in lines[:limit])

    pos = np.asarray(pos)
    ntype = np.asarray(rr.node_type)
    xlow, ylow = np.asarray(rr.xlow, np.int64), np.asarray(rr.ylow, np.int64)
    yhigh = np.asarray(rr.yhigh, np.int64)
    ptc = np.asarray(rr.ptc, np.int64)
    cap = np.asarray(rr.capacity, np.int64)
    in_ptr = np.asarray(rr.in_row_ptr, np.int64)
    in_src = np.asarray(rr.in_src, np.int64)
    N = len(ntype)
    in_dst = np.repeat(np.arange(N, dtype=np.int64), np.diff(in_ptr))

    # ---- 1. placement: column, anchor, bounds, overlap
    bad: List[str] = []
    owner = np.full((nx + 2, ny + 2), -1, np.int64)
    io_seen = set()
    for b, (t, (x, y, z)) in enumerate(zip(block_types, pos.tolist())):
        r = rules[t]
        where = f"block {b} ({t}) at ({x},{y},{z})"
        if r.is_io:
            edge = (x in (0, nx + 1)) != (y in (0, ny + 1))
            if not (edge and 0 <= x <= nx + 1 and 0 <= y <= ny + 1
                    and 0 <= z < r.capacity):
                bad.append(f"{where}: not a pad site")
            elif (x, y, z) in io_seen:
                bad.append(f"{where}: pad site taken")
            io_seen.add((x, y, z))
            continue
        if not (1 <= x <= nx and 1 <= y and y + r.height - 1 <= ny):
            bad.append(f"{where}: footprint leaves the grid")
            continue
        if column_type(rules, x) != t:
            bad.append(f"{where}: column {x} holds "
                       f"{column_type(rules, x)}")
        if (y - 1) % r.height or z != 0:
            bad.append(f"{where}: not anchored at a row 1 + k x "
                       f"{r.height}")
        rows = owner[x, y:y + r.height]
        if (rows >= 0).any():
            bad.append(f"{where}: overlaps block {int(rows.max())}")
        owner[x, y:y + r.height] = b
    say("placement", bad)

    # ---- 2. pins a tile row: IPINs and OPINs by the spread rule
    want = np.zeros((2, nx + 2, ny + 2), np.int64)      # [is_out, x, y]
    for x in range(nx + 2):
        for y in range(ny + 2):
            interior = 1 <= x <= nx and 1 <= y <= ny
            if interior:
                r = rules[column_type(rules, x)]
                y0 = 1 + (y - 1) // r.height * r.height
                if y0 + r.height - 1 > ny:
                    continue            # left over above the last block
            elif (x in (0, nx + 1)) != (y in (0, ny + 1)):
                r, y0 = rules["io"], y
            else:
                continue
            for p in range(r.num_pins):
                if p % r.height == y - y0:
                    want[int(r.is_output(p)), x, y] += r.capacity
    got = np.zeros_like(want)
    for kind, o in ((IPIN, 0), (OPIN, 1)):
        m = ntype == kind
        np.add.at(got[o], (xlow[m], ylow[m]), 1)
    say("pins a row", [
        f"tile ({x},{y}): {got[0, x, y]} IPINs, {got[1, x, y]} OPINs; "
        f"the rule gives {want[0, x, y]}, {want[1, x, y]}"
        for x, y in np.argwhere((got != want).any(axis=0)).tolist()])

    # ---- 3. net terminals: footprint, the pin's own node, capacity
    pin_key = {}        # (is_out, x, y, ptc) -> node
    for kind, o in ((IPIN, 0), (OPIN, 1)):
        for n in np.flatnonzero(ntype == kind).tolist():
            pin_key[(o, int(xlow[n]), int(ylow[n]), int(ptc[n]))] = n
    # the SOURCE an OPIN hangs from / the SINK an IPIN feeds
    tied: Dict[int, int] = {}
    e_ipin = (ntype[in_src] == IPIN) & (ntype[in_dst] == SINK)
    tied.update(zip(in_src[e_ipin].tolist(), in_dst[e_ipin].tolist()))
    e_opin = (ntype[in_src] == SOURCE) & (ntype[in_dst] == OPIN)
    tied.update(zip(in_dst[e_opin].tolist(), in_src[e_opin].tolist()))

    bad = []

    def terminal(what: str, node: int, b: int, p: int, is_out: bool):
        t = block_types[b]
        r = rules[t]
        x, y, z = (int(v) for v in pos[b])
        where = f"{what}: node {node}, pin {p} of block {b} ({t})"
        if ntype[node] != (SOURCE if is_out else SINK):
            bad.append(f"{where}: not a "
                       f"{'SOURCE' if is_out else 'SINK'}")
            return
        if not (xlow[node] == x and y <= ylow[node]
                and yhigh[node] <= y + r.height - 1):
            bad.append(f"{where}: outside the footprint")
        pin = pin_key.get((int(is_out), x, y + p % r.height,
                           z * r.num_pins + p))
        if pin is None or tied.get(pin) != node:
            bad.append(f"{where}: not the node of its pin's class")
        if cap[node] != r.class_capacity(p):
            bad.append(f"{where}: capacity {int(cap[node])}, the pin's "
                       f"class holds {r.class_capacity(p)}")

    for r_, (s, b, p) in enumerate(zip(nets["source"].tolist(),
                                       nets["src_block"].tolist(),
                                       nets["src_pin"].tolist())):
        terminal(f"net {r_} source", s, b, p, True)
        for k, snk in enumerate(nets["sinks"][r_].tolist()):
            if snk >= 0:
                terminal(f"net {r_} sink {k}", snk,
                         int(nets["sink_block"][r_, k]),
                         int(nets["sink_pin"][r_, k]), False)
    say("terminal", bad)

    # ---- 4. Fc: distinct wires a pin reaches in each channel beside it
    wire = (ntype == CHANX) | (ntype == CHANY)
    e_out = (ntype[in_src] == OPIN) & wire[in_dst]
    e_in = wire[in_src] & (ntype[in_dst] == IPIN)
    pin_n = np.concatenate([in_src[e_out], in_dst[e_in]])
    wire_n = np.concatenate([in_dst[e_out], in_src[e_in]])
    is_x = ntype[wire_n] == CHANX
    chan = np.where(is_x, ylow[wire_n], xlow[wire_n]) * 2 + is_x
    trip = np.unique(np.stack([pin_n, chan, wire_n], axis=1), axis=0)
    pair, n_wires = np.unique(trip[:, :2], axis=0, return_counts=True)
    is_out = ntype[pair[:, 0]] == OPIN
    want_w = np.where(is_out, max(1, int(round(fc[True] * W))),
                      max(1, int(round(fc[False] * W))))
    bad = [f"pin node {int(p)} reaches {int(n)} wires of channel "
           f"{'CHANX' if c % 2 else 'CHANY'} {int(c) // 2}, Fc gives "
           f"{int(w)}" for (p, c), n, w in
           zip(pair[n_wires != want_w].tolist(),
               n_wires[n_wires != want_w].tolist(),
               want_w[n_wires != want_w].tolist())]
    pins = np.flatnonzero((ntype == OPIN) | (ntype == IPIN))
    n_chan = np.bincount(pair[:, 0], minlength=N)[pins]
    interior = ((xlow[pins] >= 1) & (xlow[pins] <= nx)
                & (ylow[pins] >= 1) & (ylow[pins] <= ny))
    short = n_chan != np.where(interior, 4, 1)
    bad += [f"pin node {int(p)} at ({int(xlow[p])},{int(ylow[p])}) "
            f"reaches {int(n)} channels" for p, n in
            zip(pins[short].tolist(), n_chan[short].tolist())]
    say("Fc", bad)
    return out
