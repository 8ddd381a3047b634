"""Share of a route's relaxation sweeps whose result the route threw
away (``RouteResult.total_relax_steps_discarded`` over
``total_relax_steps``): the windows after the wirelength finishing
pass's snapshot, when re-legalisation ran out of iterations and the
driver restored the snapshot (the counter
``route.endgame.finish_restored_total`` counts those routes).  0 where
the finished route is kept, or no finishing pass ran; None where the
program's result counts no sweeps or lacks the discarded count (a
program from before the counter)."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    steps = getattr(routes[0], "total_relax_steps", None)
    discarded = getattr(routes[0], "total_relax_steps_discarded", None)
    if not steps or discarded is None:
        return None
    return 100.0 * discarded / steps
