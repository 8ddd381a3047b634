"""Share of the timing graph's in-edges that belong to a node wider
than the fused STA's in-edge table (the junction of a combinational
hard block: a multiplier's max over its operand pins): the gauge
``route.timing.in_edges_wide`` over ``route.timing.in_edges``, set where
the timing graph is built.  The table keeps the LUT's width and the
wide nodes' further in-edges are folded in from a flat list once a
level (device scope ``route.dev.sta.wide_fold``), so this share is what
the dense form would have multiplied every node's row by.  0 on a
circuit without such a block.  None where the program sets no such
gauge (a program from before it could run a path through a hard
block)."""


def read(ctx):
    reg = ctx.get("registry") or {}
    wide = reg.get("route.timing.in_edges_wide")
    edges = reg.get("route.timing.in_edges")
    if wide is None or not edges:
        return None
    return 100.0 * wide / edges
