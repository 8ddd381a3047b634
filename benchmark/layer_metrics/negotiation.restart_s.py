"""Seconds a route spends in its phase-2 restart: the ``route_time_s``
of the ``RouteResult.stats`` rows of kind ``restart`` (every net ripped
up and re-routed precisely, at most once a route), the run's first
timed route.  0 where no restart fired; None where the rows carry no
``kind`` (a program from before the window ledger)."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows or not all(getattr(s, "kind", "") for s in rows):
        return None
    return sum(s.route_time_s for s in rows if s.kind == "restart")
