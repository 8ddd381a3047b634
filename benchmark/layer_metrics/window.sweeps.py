"""Relaxation sweeps of one route (``RouteResult.total_relax_steps``)."""


def read(ctx):
    routes = ctx.get("routes")
    return routes[0].total_relax_steps if routes else None
