"""PathFinder iterations of one route (``RouteResult.iterations``;
every route of a run does the same work, the first is read)."""


def read(ctx):
    routes = ctx.get("routes")
    return routes[0].iterations if routes else None
