"""Share of a route's relaxation sweeps that ran in batches of a fanout
class above the first (``RouteResult.total_relax_steps_wide`` over
``total_relax_steps``; per window the rows' ``relax_steps_wide``): how
much of the route the handful of nets with sinks in the hundreds are.
A batch of them runs the full canvas for every wave of its widest net,
so the share follows how often negotiation re-routes them, not how many
they are.  0 on a circuit of one class; None where the program's result
counts no sweeps or lacks the count (a program from before fanout
classes)."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    steps = getattr(routes[0], "total_relax_steps", None)
    wide = getattr(routes[0], "total_relax_steps_wide", None)
    if not steps or wide is None:
        return None
    return 100.0 * wide / steps
