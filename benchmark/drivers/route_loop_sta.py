"""Driver ``route_loop_sta``: ``route_loop``, and the timed path's
critical path held to the plain timing reference.

``route_loop.run`` is called unchanged: the window, its clocks, its
checks and its ``ctx`` are that driver's.  After it, outside every
clock, this wrapper builds the problem again (``route_loop``'s outcome
carries the routes and not the problem; the build is deterministic and
``setup_s`` was taken before), recounts every timed route's sink delays
in float64 along its routed trees (``reference.py``) and analyses them
with ``reference_timing.py``: the logical netlist, the packing and the
configuration's published delays alone.  Three checks join the
outcome's:

* ``crit_path_gap``: the relative gap between the critical-path delay
  the timed path reported for its last route (``ctx["crit_path_ns"]``)
  and the reference's on that route, at most the traffic file's limit;
* ``crit_path_ref_spread``: the reference's critical path over EVERY
  timed route, exactly 0 (the routes of a run are identical, so the
  one compared route speaks for all);
* ``crit_path_crosses_multiplier``: the reference's critical path
  holds at least one combinational hard block's pin-to-pin arc: were it
  False the cell would not measure what it is for.
"""

from __future__ import annotations

from benchmark import harness, problem, reference, reference_timing


def run(cell: harness.Cell, env: harness.Env) -> harness.Outcome:
    route_loop = harness.load_module(cell.find("drivers", "route_loop",
                                               ".py"))
    out = route_loop.run(cell, env)

    f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    timing = reference_timing.block_timing(cell.config)
    g = reference.GraphArrays.of(f.rr)
    t = f.term
    refs = []
    for r in out.ctx["routes"]:
        legal = reference.check_legality(g, t.source, t.sinks,
                                         t.num_sinks, r.paths)
        delays = reference.tree_sink_delays(g, t.source, t.sinks,
                                            t.num_sinks, legal["parents"])
        refs.append(reference_timing.analyze(
            f.nl, f.pnl, timing, reference_timing.connection_delays(
                f.pnl, t.net_ids, delays)))
    last = refs[-1]
    got = out.ctx["crit_path_ns"] * 1e-9
    harness.say(phase="timing_reference", crit_path_ns=last["dmax"] * 1e9,
                reported_ns=out.ctx["crit_path_ns"],
                hard_arcs=last["hard_arcs"], path_pins=len(last["path"]))
    dmaxes = [r["dmax"] for r in refs]
    out.checks += [
        harness.at_most("crit_path_gap",
                        abs(got - last["dmax"]) / last["dmax"],
                        cell.traffic["limits"]["crit_path_gap"]),
        harness.exactly("crit_path_ref_spread",
                        max(dmaxes) - min(dmaxes), 0),
        harness.exactly("crit_path_crosses_multiplier",
                        last["hard_arcs"] >= 1, True),
    ]
    return out
