"""Relaxation sweeps an executed wave ran, on average over a route
(``RouteResult.total_relax_steps`` over ``total_waves``, both summed on
the device in the window programs' ledger).  A wave is one relaxation
to a fixpoint: a scan crosses a row in one sweep and every change of
track or channel costs one more, so this is the number a routing
architecture moves (span-4 single-driver wires against length-1
bidirectional ones).  None where the program's result counts no waves."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    waves = getattr(routes[0], "total_waves", None)
    if not waves:
        return None
    return routes[0].total_relax_steps / waves
