"""The placed routing problem of a ``placed_route`` configuration.

The configuration names the module under ``problems/`` that builds it.
The circuit AND the placement are fixed by the configuration file, as a
benchmark BLIF and its ``.place`` are fixed files; ``--seed`` never
reaches a builder.  ``fingerprint`` says whether the problem built here
is still the one the cell was measured on.
"""

from __future__ import annotations

import hashlib

import numpy as np


def build_placed(cell, chan_width: int):
    """FlowResult of the cell's configuration, placed, at a width: built
    by the module ``problems/<config["problem"]>.py``, found by name
    like a driver, so a configuration that is built another way brings
    its own builder."""
    from benchmark import harness

    builder = harness.load_module(cell.find(
        "problems", cell.config["problem"], ".py"))
    return builder.build(cell.config, chan_width)


def router_opts(config: dict, overrides: dict):
    """The configuration's RouterOpts: the program's defaults with the
    fields the file states under ``router.opts``, then any the harness
    forces (the control)."""
    from parallel_eda_tpu.route.router import RouterOpts

    kw = dict(config["router"]["opts"])
    kw.update(overrides)
    return RouterOpts(**kw)


def fingerprint(f) -> str:
    """sha256 over what defines the routing problem: the grid, the rr
    graph's edges and delays, and every net's terminals."""
    h = hashlib.sha256()
    h.update(np.asarray([f.grid.nx, f.grid.ny, f.rr.num_nodes,
                         f.rr.chan_width], np.int64).tobytes())
    for a in (f.rr.in_row_ptr, f.rr.in_src, f.rr.in_delay,
              f.rr.capacity, f.term.source, f.term.sinks,
              f.term.num_sinks):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()
