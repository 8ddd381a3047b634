"""Share of a timed route that lies outside every window: 100 x
(``route_times[0]`` - the sum of the rows' ``route_time_s``) over
``route_times[0]``, the run's first timed route.  ``route_times`` is
the benchmark's clock around ``flow.run_route``; the rest is the
router's set-up, the route's prologue, the host's control steps between
windows, the epilogue and the STA after the route (``RouteResult.wall``
and ``FlowResult.times`` name them in seconds).  Reads only what the
rows always had; None without rows."""


def read(ctx):
    routes, times = ctx.get("routes"), ctx.get("route_times")
    if not routes or not times or not routes[0].stats or not times[0]:
        return None
    inside = sum(s.route_time_s for s in routes[0].stats)
    return 100.0 * (times[0] - inside) / times[0]
