"""Share of a route's relaxation sweeps that ran on a cropped rung of
the crop ladder (``RouteResult.total_relax_steps_cropped`` over
``total_relax_steps``, both summed from the window programs' packed
``scal``).  The rest swept the whole canvas.  0 where the grid's ladder
has no rung a net fits (``route_k6n10_relaxed``: 11x11, one 8x8 rung,
every net needs 9 or more); None where the program's result counts no
sweeps or lacks the cropped count."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    steps = getattr(routes[0], "total_relax_steps", None)
    cropped = getattr(routes[0], "total_relax_steps_cropped", None)
    if not steps or cropped is None:
        return None
    return 100.0 * cropped / steps
