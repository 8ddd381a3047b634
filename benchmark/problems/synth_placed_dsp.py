"""Problem builder ``synth_placed_dsp``: ``synth_placed_hetero``'s
stand-in with COMBINATIONAL hard blocks in its stream, held to
``reference_device`` and ``reference_netlist`` before it is handed out.

The soft logic is ``hetero_circuit``'s draw, rule for rule (2..K inputs
out of the ``locality`` latest signals under the depth cap, one to
three cubes, a flip-flop behind the LUT with probability ``ff_ratio``
from a stream of its own), and so is a REGISTERED block (an entry of
``circuit.hard_blocks`` without ``levels``: a RAM; clocked, its outputs
level-0 signals).  An entry WITH ``levels`` is a combinational block
(the published ``mult_36``): no clock, and the generator's level count
runs THROUGH it: its operands are drawn from the signals at most
``max_lut_levels - 1 - levels`` LUTs deep and its outputs are offered
at the deepest operand's level + ``levels``, so the depth cap still
bounds every path that crosses multipliers and a product bit can still
be read by a LUT.  ``operands_from`` {port: block}
wires a port's used pins to the first used outputs of an EARLIER block
(a product fed straight into the next multiplier: the path through two
multipliers in series the cell exists for); every other used pin takes
a distinct signal from the latest max(``locality``, twice the used
pins) signals the block may read.  ``mode`` names the mode the instance runs in
(``Primitive.mode``: the architecture's pin-to-pin delay by mode).

``build`` is the sibling's: packed by the normal packer, placed by the
configuration's placer, refused unless both plain references pass.
"""

from __future__ import annotations

import random

from benchmark import harness

HERE = harness.HERE


def _sibling():
    """``synth_placed_hetero``, found beside this file as the harness
    finds a builder (its name carries no dot, but ``problems/`` is no
    package)."""
    return harness.load_module(harness.find_file(
        [HERE], "problems", "synth_placed_hetero", ".py"))


def dsp_circuit(num_luts: int, num_inputs: int, num_outputs: int,
                K: int, ff_ratio: float, locality: int,
                max_lut_levels: int, seed: int, hard_blocks=(),
                name: str = "synth"):
    """The netlist, and every signal's level (LUTs, and ``levels`` a
    combinational block, behind a primary input or a register)."""
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
    outs_of = {}        # block name -> its used outputs, in pin order
    # block name -> levels of the blocks its product is wired on through
    # (the depth cap counts the whole chain)
    onward = {}
    for h in sorted(hard_blocks, key=lambda h: -int(h["at_lut"])):
        for src in h.get("operands_from", {}).values():
            onward[src] = max(onward.get(src, 0), int(h.get("levels", 0))
                              + onward.get(h["name"], 0))

    def insert(h):
        depth = int(h.get("levels", 0))
        wired = h.get("operands_from", {})
        given = {port: outs_of[src] for port, src in wired.items()}
        used = sum(u for port, _, u in h["inputs"] if port not in given)
        taken = {s for port, _, u in h["inputs"] if port in given
                 for s in given[port][:u]}
        n = max(locality, 2 * used)
        if depth:
            # the latest n signals the block may read: a product has one
            # LUT's level to go under the cap (every register of the
            # stand-in sits behind a LUT: bits offered AT the cap could
            # be read by nothing), and a multiplier's 36 product bits
            # lie deep and all at once, so the window reaches back past
            # them
            deepest = max_lut_levels - 1 - depth - onward.get(h["name"], 0)
            window = [s for s in signals
                      if level[s] <= deepest and s not in taken][-n:]
        else:
            window = [s for s in signals[-n:] if level[s] < max_lut_levels]
        drawn = iter(rng.sample(window, used))
        ins = []
        for port, w, u in h["inputs"]:
            src = iter(given[port]) if port in given else drawn
            ins += [next(src) if k < u else None for k in range(w)]
        outs = [f"{h['name']}.{port}{k}" if k < u else None
                for port, w, u in h["outputs"] for k in range(w)]
        nl.add(Primitive(name=h["name"], kind=PRIM_HARD, model=h["model"],
                         inputs=ins, outputs=outs, mode=h.get("mode"),
                         clock=None if depth else clock))
        at = depth + max(level[s] for s in ins if s is not None) \
            if depth else 0
        outs_of[h["name"]] = [o for o in outs if o is not None]
        for o in outs_of[h["name"]]:
            signals.append(o)
            level[o] = at

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


def multipliers_in_series(nl) -> int:
    """The most combinational hard blocks (an instance without a clock)
    any one path of ``dsp_circuit``'s netlist crosses between registers
    or pads.  The stream's order is topological: a primitive reads
    only what was drawn before it."""
    from parallel_eda_tpu.netlist.netlist import PRIM_HARD, PRIM_LUT

    crossed = {}        # net -> most blocks crossed on the way to it
    for p in nl.primitives:
        comb = p.kind == PRIM_HARD and p.clock is None
        if p.kind != PRIM_LUT and not comb:
            continue            # a launch point: its outputs read 0
        here = int(comb) + max((crossed.get(n, 0) for n in p.inputs
                                if n is not None), default=0)
        for o in ([p.output] if p.kind == PRIM_LUT else p.outputs):
            if o is not None:
                crossed[o] = here
    return max(crossed.values(), default=0)


def netlist_problems(config: dict, f) -> list:
    """The built circuit recounted by ``reference_netlist.count_netlist``
    (plain tuples in, nothing of the program's own counting) against
    the counts the configuration publishes: primitives by kind, every
    LUT on 2..6 distinct inputs, and every routed net's sink count the
    number of distinct blocks that read it.  (That file's
    ``netlist_problems`` is the register-free sibling's rule set: no
    flip-flop, the inputs the widest nets.)"""
    import numpy as np

    from benchmark import reference_netlist

    HARD = 4
    block_of = {pi: bi for bi, b in enumerate(f.pnl.blocks)
                for pi in b.prims}
    prims = [(int(p.kind), p.output,
              [n for n in p.inputs if n is not None], block_of.get(i, -1))
             for i, p in enumerate(f.nl.primitives)]
    c = reference_netlist.count_netlist(prims)
    pub = config["published"]["circuit"]
    models = [p.model for p in f.nl.primitives if int(p.kind) == HARD]
    built = {"luts": c["luts"], "flip_flops": c["ffs"],
             "inputs": c["inputs"] - 1,             # less the clock pad
             "outputs": c["outputs"],
             "multipliers": models.count("multiply"),
             "memories": models.count("dual_port_ram")}
    out = [f"{k}: built {v}, the configuration publishes {pub[k]}"
           for k, v in built.items() if v != pub[k]]
    if c["bad_fanin"]:
        out.append(f"{len(c['bad_fanin'])} LUTs without 2..6 distinct "
                   f"inputs (first: {c['bad_fanin'][0]})")
    mine = np.asarray([c["cluster_sinks"].get(f.pnl.nets[ni].name, 0)
                       for ni in f.term.net_ids])
    bad = np.flatnonzero(mine != np.asarray(f.term.num_sinks))
    if len(bad):
        out.append(f"{len(bad)} routed nets whose sink count is not the "
                   f"number of blocks that read them (first: net "
                   f"{f.pnl.nets[f.term.net_ids[bad[0]]].name})")
    return out


def build(config: dict, chan_width: int):
    """FlowResult of the configuration's circuit, placed, at a width."""
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.arch import builtin

    sib = _sibling()
    a, c, p = config["arch"], config["circuit"], config["placement"]
    try:
        arch = getattr(builtin, a["builder"])(chan_width=chan_width,
                                              **a["args"])
    except (AttributeError, TypeError):
        # no such builder, or one from before a hard block could be
        # built combinational
        raise SystemExit(f"benchmark: this program has no architecture "
                         f"builder {a['builder']!r} that takes "
                         f"{sorted(a['args'])}") from None
    nl, _ = dsp_circuit(
        num_luts=c["num_luts"], num_inputs=c["num_inputs"],
        num_outputs=c["num_outputs"], K=arch.K, ff_ratio=c["ff_ratio"],
        locality=c["locality"], max_lut_levels=c["max_lut_levels"],
        seed=c["generator_seed"], hard_blocks=c["hard_blocks"])
    f = F.prepare(nl, arch, chan_width,
                  bb_factor=config["router"]["opts"]["bb_factor"])
    f = getattr(F, p["placer"])(f, **p["args"])
    problems = netlist_problems(config, f) + sib.device_problems(config, f)
    if multipliers_in_series(nl) < 2:
        problems.append("no path crosses two multipliers in series")
    if problems:
        raise ValueError("the references refuse the built problem:\n  "
                         + "\n  ".join(problems[:20]))
    return f
