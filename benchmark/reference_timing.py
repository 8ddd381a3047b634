"""The plain reference of the TIMING analysis: arrival, required time,
slack and criticality of a routed circuit, worked out from the logical
netlist, the packing and the configuration's delays alone.

Plain Python in float64, one pass in topological order and one back.
Nothing of ``parallel_eda_tpu/timing/`` is imported: no timing graph,
no ELL tables, no device.  The netlist and the packing arrive as the
objects the problem is made of (a BLIF and a ``.net`` are files of the
same content); the routed delay of every connection as a plain dict,
in the benchmark's runs the float64 sums ``reference.tree_sink_delays``
recounts along the routed trees.

The model (VPR 7 ``path_delay.c``: ``alloc_and_load_timing_graph``,
``do_timing_analysis``), pin by pin:

* an input pad launches at 0; an output pad is an endpoint;
* a LUT's output arrives at max over its inputs of (arrival at the
  driver + the connection's delay) + the cluster's ``T_comb``;
* a flip-flop's D pin is an endpoint at + ``T_setup``; its Q pin
  launches at ``T_clk_to_q``; clocks are ideal;
* a REGISTERED hard block (a RAM) is a flip-flop a pin: every used
  input pin an endpoint at the block's ``T_setup``, every used output
  pin a launch at its ``T_clk_to_q``;
* a COMBINATIONAL hard block (the published ``mult_36``): the arrival
  at every used output pin is the max over the block's used input pins
  of (arrival + the connection's delay) + the ``delay_constant`` of the
  mode the instance runs in; no endpoint and no launch there, so a
  path runs THROUGH the block;
* a connection inside one block costs ``t_local``; one between blocks
  costs its routed delay; one the router does not route (a global) 0;
* required times flow back from the endpoints (single clock: every
  endpoint's is the critical-path delay; constrained: its domain's
  period), slack of a connection = required at its sink pin - arrival
  at its driver - its delay (the sink's own constant included), and
  criticality = clip(1 - slack / D, 0, max_crit) with D the
  critical-path delay (constrained: the period of the endpoint that
  sets the sink's required time).

Departures from ``path_delay.c``, each deliberate:

1. a cluster is three constants (LUT and crossbar lumped into
   ``T_comb``, one ``t_local`` for any feedback connection) where VPR
   walks the pb_graph's pins; the configuration states them under
   ``assumed``;
2. a hard block has ONE pin-to-pin delay a mode (the published
   ``delay_constant`` gives a -> out and b -> out the same ``max``);
   a per-pin ``delay_matrix`` is not modelled;
3. criticality is clipped at ``max_crit`` (VPR's ``--max_criticality``
   0.99) and not raised to an exponent (``--criticality_exp`` 1);
4. constrained analysis knows ``create_clock`` periods only: a domain
   a clock net, unclocked endpoints (pads) on ``default_period``; no
   multicycle, false path or I/O delay, and a connection's slack is
   normalised by the period of the ONE endpoint that sets its required
   time where VPR analyses each pair of domains;
5. no clock skew, no hold analysis.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

# primitive kinds: the numbering is part of the netlist's format,
# repeated here so that nothing is imported
INPAD, OUTPAD, LUT, FF, HARD = 0, 1, 2, 3, 4


def block_timing(config: dict) -> dict:
    """``{"t_local": s, "blocks": {type name: timing}}`` of a
    configuration file: each hard block's ``timing`` from its
    ``published`` entry, the cluster's lumped constants from
    ``block_timing`` (stated under ``assumed``)."""
    blocks = {name: dict(spec["timing"])
              for name, spec in config["published"]["blocks"].items()
              if "timing" in spec}
    own = config["block_timing"]
    blocks.update({k: dict(v) for k, v in own["blocks"].items()})
    return {"t_local": float(own["t_local"]), "blocks": blocks}


def registered(timing: dict, like: str = "clb") -> dict:
    """The control: every combinational hard block taken as REGISTERED
    at block ``like``'s setup and clock-to-Q (the semantics of the
    program before it could run a path through a hard block)."""
    ref = timing["blocks"][like]
    blocks = {
        name: ({"kind": "registered", "T_setup": ref["T_setup"],
                "T_clk_to_q": ref["T_clk_to_q"]}
               if t.get("kind") == "combinational" else t)
        for name, t in timing["blocks"].items()}
    return {"t_local": timing["t_local"], "blocks": blocks}


def connection_delays(pnl, net_ids, sink_delay) -> Dict[Tuple[str, int],
                                                        float]:
    """``{(net name, sink block): seconds}`` from the router's
    numbering: row r of ``sink_delay`` is packed net ``net_ids[r]``,
    column s that net's s-th sink."""
    out = {}
    for r, ni in enumerate(list(net_ids)):
        net = pnl.nets[int(ni)]
        for s, pin in enumerate(net.sinks):
            out[(net.name, int(pin.block))] = float(sink_delay[r][s])
    return out


def _comb_delay(t: dict, mode: Optional[str]) -> float:
    return float(t["delay_constant"].get(mode, t["default"]))


def analyze(nl, pnl, timing: dict, conn_delay: Dict[Tuple[str, int], float],
            periods: Optional[Dict[str, float]] = None,
            default_period: Optional[float] = None,
            max_crit: float = 0.99) -> dict:
    """Longest-path analysis of one routed circuit.

    ``periods`` (clock net -> seconds) switches to constrained mode.

    Pins are named ``(primitive index, role, net)``: ``"out"`` a pad's,
    LUT's or flip-flop's output (net None), ``"in"`` a flip-flop's D or
    an output pad (net None), ``"hin"`` / ``"hout"`` a hard block's
    used input / output pin on ``net``.

    Returns ``{"arrival": {pin: s}, "required": {pin: s}, "dmax": s,
    "worst_slack": s, "crit": {(net, sink block): criticality of the
    routed connection, the max over the pins it feeds},
    "slack": {(driver pin, sink pin): s}, "path": [pins, endpoint
    first], "hard_arcs": combinational hard blocks crossed by it}``."""
    prims = nl.primitives
    clocks = set(nl.clocks)
    block_of = {p: bi for bi, b in enumerate(pnl.blocks) for p in b.prims}

    def t_of(i):
        return timing["blocks"][pnl.blocks[block_of[i]].type_name]

    def is_comb(i):
        return (prims[i].kind == HARD
                and t_of(i).get("kind") == "combinational")

    def driver_pin(net):
        d = nl.net_driver[net]
        return (d, "hout", net) if prims[d].kind == HARD else (
            d, "out", None)

    def conn(net, i):
        """Delay of the connection of ``net`` into primitive i."""
        d = nl.net_driver[net]
        if block_of[d] == block_of[i]:
            return timing["t_local"]
        return conn_delay.get((net, block_of[i]), 0.0)

    # ---- the pins, and every timing arc (src pin, dst pin, delay,
    # the routed connection it rides on or None)
    arcs = []
    seed: Dict[tuple, float] = {}       # launch points
    endpoint: Dict[tuple, Optional[str]] = {}   # -> clock net or None
    for i, p in enumerate(prims):
        nets = [n for n in dict.fromkeys(p.inputs)
                if n is not None and n not in clocks]
        if p.kind == INPAD:
            seed[(i, "out", None)] = 0.0
        elif p.kind == LUT:
            for n in p.inputs:
                if n is None or n in clocks:
                    continue
                arcs.append((driver_pin(n), (i, "out", None),
                             conn(n, i) + t_of(i)["T_comb"], n))
        elif p.kind == FF:
            seed[(i, "out", None)] = t_of(i)["T_clk_to_q"]
            endpoint[(i, "in", None)] = p.clock
            for n in nets:
                arcs.append((driver_pin(n), (i, "in", None),
                             conn(n, i) + t_of(i)["T_setup"], n))
        elif p.kind == OUTPAD:
            endpoint[(i, "in", None)] = None
            for n in nets[:1]:
                arcs.append((driver_pin(n), (i, "in", None), conn(n, i),
                             n))
        elif is_comb(i):
            d = _comb_delay(t_of(i), getattr(p, "mode", None))
            outs = [o for o in p.outputs if o is not None]
            for n in nets:
                arcs.append((driver_pin(n), (i, "hin", n), conn(n, i), n))
                for o in outs:
                    arcs.append(((i, "hin", n), (i, "hout", o), d, None))
        else:                                   # registered hard block
            for n in nets:
                endpoint[(i, "hin", n)] = p.clock
                arcs.append((driver_pin(n), (i, "hin", n),
                             conn(n, i) + t_of(i)["T_setup"], n))
            for o in p.outputs:
                if o is not None:
                    seed[(i, "hout", o)] = t_of(i)["T_clk_to_q"]

    pins = set(seed) | set(endpoint)
    fan_in: Dict[tuple, list] = {}
    fan_out: Dict[tuple, list] = {}
    for a in arcs:
        pins.update(a[:2])
        fan_in.setdefault(a[1], []).append(a)
        fan_out.setdefault(a[0], []).append(a)

    # ---- topological order (Kahn); what is left over is a loop
    left = {v: len(fan_in.get(v, ())) for v in pins}
    order = [v for v in pins if left[v] == 0]
    for v in order:                     # grows while it is walked
        for a in fan_out.get(v, ()):
            left[a[1]] -= 1
            if left[a[1]] == 0:
                order.append(a[1])
    if len(order) != len(pins):
        raise ValueError("combinational loop in the netlist")

    # ---- forward
    NEG = -math.inf
    arrival = {v: seed.get(v, NEG) for v in pins}
    via: Dict[tuple, tuple] = {}
    for v in order:
        for a in fan_in.get(v, ()):
            c = arrival[a[0]] + a[2]
            if c > arrival[v]:
                arrival[v], via[v] = c, a
    ends = [v for v in endpoint if arrival[v] > NEG]
    dmax = max((arrival[v] for v in ends), default=0.0)

    # ---- backward
    constrained = periods is not None
    req0, per0 = {}, {}
    for v, clk in endpoint.items():
        if constrained:
            p = periods.get(clk, default_period) if clk is not None \
                else default_period
            req0[v] = math.inf if p is None else p
            per0[v] = 0.0 if p is None else p
        else:
            req0[v], per0[v] = dmax, 0.0
    required = {v: req0.get(v, math.inf) for v in pins}
    period = {v: per0.get(v, 0.0) for v in pins}
    for v in reversed(order):
        for a in fan_out.get(v, ()):
            c = required[a[1]] - a[2]
            if c < required[v]:
                required[v], period[v] = c, period[a[1]]

    # ---- slack and criticality of every arc
    floor = max(dmax, 1e-30)
    slack, crit = {}, {}
    for a in arcs:
        if not (math.isfinite(arrival[a[0]])
                and math.isfinite(required[a[1]])):
            continue
        s = required[a[1]] - arrival[a[0]] - a[2]
        slack[(a[0], a[1])] = s
        d = period[a[1]] if constrained and period[a[1]] > 0 else floor
        c = min(max(1.0 - s / d, 0.0), max_crit)
        key = (a[3], block_of[a[1][0]])
        if (a[3] is not None and key in conn_delay
                and block_of[nl.net_driver[a[3]]] != key[1]):
            crit[key] = max(crit.get(key, 0.0), c)
    worst = min((req0[v] - arrival[v] for v in ends
                 if math.isfinite(req0[v])), default=0.0)

    # ---- the critical path, endpoint first
    path, hard_arcs = [], 0
    if ends:
        v = max(ends, key=lambda e: arrival[e])
        path.append(v)
        while v in via:
            a = via[v]
            hard_arcs += a[0][1] == "hin" and a[1][1] == "hout"
            v = a[0]
            path.append(v)
    return {"arrival": arrival, "required": required, "dmax": dmax,
            "worst_slack": worst, "crit": crit, "slack": slack,
            "path": path, "hard_arcs": int(hard_arcs)}
