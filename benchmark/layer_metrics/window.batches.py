"""Batches a route ran: the sum of ``batches`` over the
``RouteResult.stats`` rows of the run's first timed route, each the
``nexec`` of the window programs' packed ``scal`` -- the batch groups
that executed a whole rip-up / relaxation / commit step, skipped groups
not counted.  A batch costs about the same however many of its slots
hold a net that routes, so this is what a route's seconds are counted
in; ``window.net_routes_per_batch`` says how full they ran.  None where
there are no rows."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows:
        return None
    return sum(s.batches for s in rows)
