"""Problem builder ``synth_placed_fanout``: a register-free stand-in
whose primary inputs have the fanout a PLA-derived circuit gives them.

``synth_placed_levelled``'s draw (2..K inputs a LUT, one to three
cubes, the ``locality`` latest signals, none of them ``max_lut_levels``
LUTs behind an input) tops out at a dozen sinks a net: a signal leaves
the window 40 LUTs after it was made.  A combinational circuit of a few
thousand LUTs over sixteen inputs cannot look like that: every LUT pin
is fed by a LUT or by an input, and what the LUT outputs do not feed
the sixteen inputs must.  This builder adds ONE rule to the draw: each
LUT input pin is a primary input, drawn uniformly, with probability
``circuit.pi_pin_share``, else a signal of the levelled window (LUT
outputs only; where the window holds too few, as at the first LUT and
wherever the depth cap has emptied it, the rest are inputs too).  There
are no registers, so no clock pad.

The builder calls ``benchmark/reference_netlist.py`` on what it built
before it returns: that file recounts the circuit and every net's
fanout from plain lists and refuses a build whose input nets are not
the wide ones.
"""

from __future__ import annotations

import random


def fanout_circuit(num_luts: int, num_inputs: int, num_outputs: int,
                   K: int, pi_pin_share: float, locality: int,
                   max_lut_levels: int, seed: int, name: str = "synth"):
    """The netlist, and every signal's LUT level (0 = a primary
    input)."""
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD, LogicalNetlist, Primitive)

    rng = random.Random(seed)
    pi_rng = random.Random(f"pi:{seed}")
    nl = LogicalNetlist(name=name)
    pis, signals, level = [], [], {}
    for i in range(num_inputs):
        n = f"pi{i}"
        nl.add(Primitive(name=n, kind=PRIM_INPAD, output=n))
        pis.append(n)
        level[n] = 0
    for i in range(num_luts):
        window = [s for s in signals[-locality:]
                  if level[s] < max_lut_levels]
        fanin = rng.randint(2, min(K, len(window) + num_inputs))
        from_pi = sum(pi_rng.random() < pi_pin_share
                      for _ in range(fanin))
        from_pi = min(num_inputs, max(from_pi, fanin - len(window)))
        ins = (rng.sample(window, fanin - from_pi)
               + pi_rng.sample(pis, from_pi))
        out = f"n{i}"
        rows = ["".join(rng.choice("01-") for _ in range(len(ins))) + " 1"
                for _ in range(rng.randint(1, 3))]
        nl.add(Primitive(name=out, kind=PRIM_LUT, inputs=ins, output=out,
                         truth_table=rows))
        signals.append(out)
        level[out] = 1 + max(level[s] for s in ins)
    # primary outputs tap the most recently produced signals
    for i in range(num_outputs):
        src = signals[-(i % min(len(signals), locality)) - 1]
        nl.add(Primitive(name=f"out:po{i}", kind=PRIM_OUTPAD, inputs=[src]))
    nl.finalize()
    return nl, level


def plain_netlist(f) -> dict:
    """The built problem as ``reference_netlist`` reads it: plain lists
    and arrays, nothing of the program's own counting."""
    import numpy as np

    block_of = {}
    for bi, b in enumerate(f.pnl.blocks):
        for pi in b.prims:
            block_of[pi] = bi
    prims = [(int(p.kind), p.output, list(p.inputs), block_of.get(i, -1))
             for i, p in enumerate(f.nl.primitives)]
    return {"prims": prims,
            "routed": [f.pnl.nets[ni].name for ni in f.term.net_ids],
            "num_sinks": np.asarray(f.term.num_sinks)}


def netlist_problems(config: dict, f) -> list:
    """``reference_netlist``'s verdict on a built problem."""
    from benchmark import reference_netlist

    return reference_netlist.netlist_problems(config["circuit"],
                                              **plain_netlist(f))


def build(config: dict, chan_width: int):
    """FlowResult of the configuration's circuit, placed, at a width."""
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.arch import builtin
    from parallel_eda_tpu.rr import terminals

    if not hasattr(terminals, "fanout_ladder"):
        # a program from before fanout classes: its tables are dense in
        # the widest net, 204 sink slots for every one of 2,159 nets,
        # which is not the deployment the configuration describes
        raise SystemExit("benchmark: this program keeps no fanout "
                         "classes (rr.terminals.fanout_ladder)")
    a, c, p = config["arch"], config["circuit"], config["placement"]
    if c.get("ff_ratio", 0.0) != 0.0:
        raise ValueError("synth_placed_fanout builds a register-free "
                         "circuit: circuit.ff_ratio must be 0")
    arch = getattr(builtin, a["builder"])(chan_width=chan_width,
                                          **a["args"])
    nl, _ = fanout_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=arch.K,
        pi_pin_share=c["pi_pin_share"], locality=c["locality"],
        max_lut_levels=c["max_lut_levels"], seed=c["generator_seed"])
    f = F.prepare(nl, arch, chan_width,
                  bb_factor=config["router"]["opts"]["bb_factor"])
    f = getattr(F, p["placer"])(f, **p["args"])
    problems = netlist_problems(config, f)
    if problems:
        raise ValueError("reference_netlist refuses the built problem:"
                         "\n  " + "\n  ".join(problems[:20]))
    return f
