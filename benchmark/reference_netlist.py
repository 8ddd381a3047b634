"""The plain reference of the NETLIST: what a register-free stand-in
with primary inputs of real fanout has to satisfy, recounted from the
configuration's numbers and plain lists.

numpy and plain Python only.  Nothing of the program is imported and
none of its statistics code (``netlist/``, ``pack/``) is asked: the
circuit arrives as one tuple a primitive, ``(kind, output net, input
nets, the index of the block it was packed into)``, beside the names of
the nets the program routes and the sink count it holds for each.
``reference.py`` judges a ROUTING; ``reference_device.py`` the device
and the placement; this file judges the circuit the routing is of,
which no reference covered while every stand-in's widest net had a
dozen sinks and nothing hung on what the fanout was.

The rules (``benchmark/problems/synth_placed_fanout.py`` and the
configuration's ``circuit`` block):

* the circuit has ``num_luts`` LUTs, ``num_inputs`` input pads,
  ``num_outputs`` output pads and NO register;
* every LUT has 2..6 distinct inputs, and every LUT input pin is fed by
  a LUT output or by a primary input: pins = LUT-fed + input-fed, and
  the input-fed share lies within ``pi_share_band`` of
  ``pi_pin_share``, above it or below (the draw is one Bernoulli a
  pin, so over 14 thousand pins it alone stands within half a point of
  its parameter; the rest of the band is for the pins a window emptied
  by the depth cap hands to the inputs, three points at 3,690 LUTs);
* a net's CLUSTER-SINK count is the number of distinct blocks, other
  than its driver's, that hold a primitive reading it; the program's
  ``num_sinks`` of every routed net equals it (the inputs of a cluster
  are equivalent: a net enters a cluster once);
* the ``num_inputs`` input nets are the ``num_inputs`` widest nets of
  the circuit by cluster sinks, and the widest has at least
  ``min_widest_sinks``: a build whose inputs are not the wide nets is
  not the deployment the configuration describes.

``pi_share_band`` and ``min_widest_sinks`` are numbers of the
configuration's ``circuit`` block, stated beside ``pi_pin_share``.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

INPAD, OUTPAD, LUT, FF = 0, 1, 2, 3


def count_netlist(prims) -> dict:
    """Primitive counts, the LUT pins by what feeds them, and per net
    its LUT-pin fanout and its cluster sinks."""
    kinds = [k for k, _, _, _ in prims]
    driver_kind: Dict[str, int] = {}
    driver_block: Dict[str, int] = {}
    for kind, out, _, block in prims:
        if out is not None:
            driver_kind[out] = kind
            driver_block[out] = block
    lut_pins: Dict[str, int] = {}
    reader_blocks: Dict[str, set] = {}
    pins_lut_fed = pins_input_fed = pins_other = 0
    bad_fanin: List[str] = []
    for kind, out, ins, block in prims:
        if kind == LUT and not (2 <= len(ins) <= 6
                                and len(set(ins)) == len(ins)):
            bad_fanin.append(out)
        for n in ins:
            reader_blocks.setdefault(n, set()).add(block)
            if kind != LUT:
                continue
            lut_pins[n] = lut_pins.get(n, 0) + 1
            fed_by = driver_kind.get(n)
            if fed_by == LUT:
                pins_lut_fed += 1
            elif fed_by == INPAD:
                pins_input_fed += 1
            else:
                pins_other += 1
    cluster_sinks = {n: len(b - {driver_block.get(n)})
                     for n, b in reader_blocks.items()}
    return {"luts": kinds.count(LUT), "ffs": kinds.count(FF),
            "inputs": kinds.count(INPAD), "outputs": kinds.count(OUTPAD),
            "pins_lut_fed": pins_lut_fed,
            "pins_input_fed": pins_input_fed, "pins_other": pins_other,
            "bad_fanin": bad_fanin, "lut_pins": lut_pins,
            "cluster_sinks": cluster_sinks,
            "input_nets": [out for kind, out, _, _ in prims
                           if kind == INPAD]}


def netlist_problems(circuit: dict, prims, routed, num_sinks) -> list:
    """Every rule the built circuit breaks, as text (empty: none).

    ``circuit`` is the configuration's block, ``prims`` the primitives
    as (kind, output, inputs, block), ``routed`` the names of the nets
    the program routes and ``num_sinks`` its sink count for each."""
    c = count_netlist(prims)
    out: List[str] = []
    for key, want in (("luts", circuit["num_luts"]),
                      ("inputs", circuit["num_inputs"]),
                      ("outputs", circuit["num_outputs"]), ("ffs", 0)):
        if c[key] != want:
            out.append(f"{key}: built {c[key]}, the configuration "
                       f"says {want}")
    if c["bad_fanin"]:
        out.append(f"{len(c['bad_fanin'])} LUTs without 2..6 distinct "
                   f"inputs (first: {c['bad_fanin'][0]})")
    pins = c["pins_lut_fed"] + c["pins_input_fed"]
    if c["pins_other"]:
        out.append(f"{c['pins_other']} LUT pins fed by neither a LUT "
                   f"nor a primary input")
    share = c["pins_input_fed"] / max(1, pins)
    if abs(share - circuit["pi_pin_share"]) > circuit["pi_share_band"]:
        out.append(f"input-fed share of the LUT pins {share:.4f} "
                   f"({c['pins_input_fed']} of {pins}) is not within "
                   f"{circuit['pi_share_band']} of pi_pin_share "
                   f"{circuit['pi_pin_share']}")
    mine = np.asarray([c["cluster_sinks"].get(n, 0) for n in routed])
    theirs = np.asarray(num_sinks)
    if mine.shape != theirs.shape or (mine != theirs).any():
        bad = (np.flatnonzero(mine != theirs)
               if mine.shape == theirs.shape else [])
        out.append(f"{len(bad)} routed nets whose sink count is not "
                   f"their cluster-sink count"
                   + (f" (first: {routed[bad[0]]}: {theirs[bad[0]]} "
                      f"against {mine[bad[0]]})" if len(bad) else ""))
    wide = sorted(c["cluster_sinks"].items(),
                  key=lambda kv: (-kv[1], kv[0]))[:len(c["input_nets"])]
    if {n for n, _ in wide} != set(c["input_nets"]):
        out.append(f"the {len(c['input_nets'])} widest nets are not the "
                   f"input nets (widest: {wide[:3]})")
    if not wide or wide[0][1] < circuit["min_widest_sinks"]:
        out.append(f"the widest net has {wide[0][1] if wide else 0} "
                   f"cluster sinks, under {circuit['min_widest_sinks']}")
    return out


def fanout_summary(prims) -> dict:
    """What a configuration's ``as_built`` states of the fanout: the
    cluster sinks of each input net, the share of all cluster sinks they
    hold, and the pin identity's three numbers."""
    c = count_netlist(prims)
    cs = c["cluster_sinks"]
    input_nets = set(c["input_nets"])
    inputs = [int(cs.get(n, 0)) for n in c["input_nets"]]
    return {"input_net_sinks": inputs,
            "input_sink_share": sum(inputs) / max(1, sum(cs.values())),
            "lut_pins": c["pins_lut_fed"] + c["pins_input_fed"],
            "pins_lut_fed": c["pins_lut_fed"],
            "pins_input_fed": c["pins_input_fed"],
            "max_lut_output_sinks": max(
                (v for n, v in cs.items() if n not in input_nets),
                default=0)}
