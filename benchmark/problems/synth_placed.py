"""Problem builder ``synth_placed``: a seeded stand-in circuit with a
published circuit's statistics, packed and placed on an architecture
the program builds from arguments.

A copy of ``bench.py``'s ``build(place=True)`` (synthetic circuit ->
pack -> rr graph -> anneal) in which everything is named by the
configuration file: the architecture is a builder of
``parallel_eda_tpu.arch.builtin`` with its arguments, the placer is a
function of ``parallel_eda_tpu.flow`` with its arguments.
A configuration whose problem is built another way (an architecture
XML, a netlist file) names another module of this directory.
"""

from __future__ import annotations


def build(config: dict, chan_width: int):
    """FlowResult of the configuration's circuit, placed, at a width."""
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.arch import builtin

    a, c, p = config["arch"], config["circuit"], config["placement"]
    arch = getattr(builtin, a["builder"])(chan_width=chan_width,
                                          **a["args"])
    f = F.synth_flow(num_luts=c["num_luts"], num_inputs=c["num_inputs"],
                     num_outputs=c["num_outputs"], chan_width=chan_width,
                     seed=c["generator_seed"], ff_ratio=c["ff_ratio"],
                     arch=arch, bb_factor=config["router"]["opts"]["bb_factor"])
    return getattr(F, p["placer"])(f, **p["args"])
