"""Problem builder ``synth_placed_levelled``: ``synth_placed``'s stand-in
with its logic depth held to a stated number of LUT levels.

``netlist/generate.py`` lets a LUT draw its inputs from the ``locality``
latest signals whatever their depth, so a stand-in's longest
combinational path grows with its size: 106 timing levels at tseng's
1,047 LUTs, 414 at elliptic's 3,604, where a technology-mapped circuit
of either size is a few dozen.  Everything a route pays once per level
(the fused STA sweeps every timing node once a level, forward and back,
every iteration) is then priced by the generator and not by the circuit.
Widening ``locality`` does not mend it: at 640 the depth is 43 levels
and the placement no longer routes at W=128 (my CPU runs, PR 30).

This builder keeps the generator's draw -- 2..K inputs out of the
``locality`` latest signals, one to three cubes, a flip-flop behind the
LUT with probability ``ff_ratio`` -- and adds ONE rule: a signal
``max_lut_levels`` LUTs behind a register or a primary input is no
longer offered as an input.  Register outputs and primary inputs are
level 0, so every window holds signals to draw.  The flip-flops are
drawn from a stream of their own: their count is then monotone in
``ff_ratio`` and does not move with the other parameters.

The configuration states ``circuit.locality`` and
``circuit.max_lut_levels`` beside the keys ``synth_placed`` reads.
"""

from __future__ import annotations

import random


def levelled_circuit(num_luts: int, num_inputs: int, num_outputs: int,
                     K: int, ff_ratio: float, locality: int,
                     max_lut_levels: int, seed: int, name: str = "synth"):
    """The netlist, and every signal's LUT level (0 = a primary input
    or a register's output)."""
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_FF, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD, LogicalNetlist,
        Primitive)

    rng = random.Random(seed)
    ff_rng = random.Random(f"ff:{seed}")
    nl = LogicalNetlist(name=name)
    clock = "clk"
    nl.add(Primitive(name=clock, kind=PRIM_INPAD, output=clock))
    signals, level = [], {}
    for i in range(num_inputs):
        n = f"pi{i}"
        nl.add(Primitive(name=n, kind=PRIM_INPAD, output=n))
        signals.append(n)
        level[n] = 0
    for i in range(num_luts):
        window = [s for s in signals[-locality:]
                  if level[s] < max_lut_levels]
        fanin = rng.randint(2, min(K, len(window)))
        ins = rng.sample(window, fanin)
        out = f"n{i}"
        rows = ["".join(rng.choice("01-") for _ in range(fanin)) + " 1"
                for _ in range(rng.randint(1, 3))]
        nl.add(Primitive(name=out, kind=PRIM_LUT, inputs=ins, output=out,
                         truth_table=rows))
        if ff_rng.random() < ff_ratio:
            q = f"q{i}"
            nl.add(Primitive(name=q, kind=PRIM_FF, inputs=[out], output=q,
                             clock=clock))
            signals.append(q)
            level[q] = 0
        else:
            signals.append(out)
            level[out] = 1 + max(level[s] for s in ins)
    # primary outputs tap the most recently produced signals
    for i in range(num_outputs):
        src = signals[-(i % min(len(signals), locality)) - 1]
        nl.add(Primitive(name=f"out:po{i}", kind=PRIM_OUTPAD, inputs=[src]))
    nl.finalize()
    return nl, level


def build(config: dict, chan_width: int):
    """FlowResult of the configuration's circuit, placed, at a width."""
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.arch import builtin

    a, c, p = config["arch"], config["circuit"], config["placement"]
    arch = getattr(builtin, a["builder"])(chan_width=chan_width,
                                          **a["args"])
    nl, _ = levelled_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=arch.K, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"])
    f = F.prepare(nl, arch, chan_width,
                  bb_factor=config["router"]["opts"]["bb_factor"])
    return getattr(F, p["placer"])(f, **p["args"])
