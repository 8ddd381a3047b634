"""Problem builder ``synth_placed_hetero``: ``synth_placed_levelled``'s
stand-in with hard blocks in its stream, placed on a typed device, and
held to ``reference_device`` before it is handed out.

The soft logic is ``levelled_circuit``'s draw, rule for rule: 2..K
inputs out of the ``locality`` latest signals that lie fewer than
``max_lut_levels`` LUTs behind a register or an input, one to three
cubes, a flip-flop behind the LUT with probability ``ff_ratio`` from a
stream of its own.  ``circuit.hard_blocks`` adds ``.subckt`` instances:
before LUT number ``at_lut`` is drawn the block is inserted.  Each of
its USED input pins takes a distinct signal, sampled by the LUTs' rule
from a window widened to hold a bus (the latest max(``locality``, twice
the used pins) signals under the depth cap: 64 operand bits cannot be
drawn distinct out of 40 signals); its used outputs are offered as
level-0 signals (the blocks are registered); unused pins stay
unconnected.  Ports are positional in the architecture's published
order, each at its published width.

``build`` packs through the normal packer, places with the
configuration's placer, and calls ``reference_device.device_problems``
on the result: a run of the cell cannot start on a device or a
placement the plain reference refuses.
"""

from __future__ import annotations

import random

import numpy as np


def hetero_circuit(num_luts: int, num_inputs: int, num_outputs: int,
                   K: int, ff_ratio: float, locality: int,
                   max_lut_levels: int, seed: int, hard_blocks=(),
                   name: str = "synth"):
    """The netlist, and every signal's LUT level (0 = a primary input,
    a register's output or a hard block's)."""
    from parallel_eda_tpu.netlist.netlist import (
        PRIM_FF, PRIM_HARD, PRIM_INPAD, PRIM_LUT, PRIM_OUTPAD,
        LogicalNetlist, Primitive)

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
    by_pos = {}
    for h in hard_blocks:
        by_pos.setdefault(int(h["at_lut"]), []).append(h)

    def insert(h):
        used = sum(u for _, _, u in h["inputs"])
        window = [s for s in signals[-max(locality, 2 * used):]
                  if level[s] < max_lut_levels]
        drawn = iter(rng.sample(window, used))
        ins = [next(drawn) if k < u else None
               for _, w, u in h["inputs"] for k in range(w)]
        outs = [f"{h['name']}.{port}{k}" if k < u else None
                for port, w, u in h["outputs"] for k in range(w)]
        nl.add(Primitive(name=h["name"], kind=PRIM_HARD, model=h["model"],
                         inputs=ins, outputs=outs, clock=clock))
        for o in outs:
            if o is not None:
                signals.append(o)
                level[o] = 0

    for i in range(num_luts):
        for h in by_pos.get(i, ()):
            insert(h)
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


def net_pins(f) -> dict:
    """The routed nets as ``reference_device`` reads them: the SOURCE
    and SINK nodes the program chose, beside the (block, pin) each
    stands for."""
    t, pnl = f.term, f.pnl
    R, S = t.sinks.shape
    out = {"source": np.asarray(t.source), "sinks": np.asarray(t.sinks),
           "src_block": np.zeros(R, np.int64),
           "src_pin": np.zeros(R, np.int64),
           "sink_block": np.full((R, S), -1, np.int64),
           "sink_pin": np.full((R, S), -1, np.int64)}
    for r, ni in enumerate(t.net_ids.tolist()):
        net = pnl.nets[ni]
        out["src_block"][r] = net.driver.block
        out["src_pin"][r] = net.driver.pin
        for s, pin in enumerate(net.sinks):
            out["sink_block"][r, s] = pin.block
            out["sink_pin"][r, s] = pin.pin
    return out


def device_problems(config: dict, f) -> list:
    """``reference_device``'s verdict on a built problem."""
    from benchmark import reference_device

    return reference_device.device_problems(
        config["published"], f.grid.nx, f.grid.ny, f.rr.chan_width, f.rr,
        [b.type_name for b in f.pnl.blocks], f.pos, net_pins(f))


def build(config: dict, chan_width: int):
    """FlowResult of the configuration's circuit, placed, at a width."""
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.arch import builtin

    a, c, p = config["arch"], config["circuit"], config["placement"]
    make_arch = getattr(builtin, a["builder"], None)
    if make_arch is None:
        # a program from before typed columns and tall blocks
        raise SystemExit(f"benchmark: this program has no architecture "
                         f"builder {a['builder']!r}")
    arch = make_arch(chan_width=chan_width, **a["args"])
    nl, _ = hetero_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=arch.K, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"], hard_blocks=c["hard_blocks"])
    f = F.prepare(nl, arch, chan_width,
                  bb_factor=config["router"]["opts"]["bb_factor"])
    f = getattr(F, p["placer"])(f, **p["args"])
    problems = device_problems(config, f)
    if problems:
        raise ValueError("reference_device refuses the built problem:\n  "
                         + "\n  ".join(problems[:20]))
    return f
