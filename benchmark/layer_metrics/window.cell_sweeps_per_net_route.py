"""Canvas cells the relaxation swept for every net it routed: the
first timed route's ``RouteResult.total_cell_sweeps`` (over its
windows' rungs, a rung's sweeps x its batch width x the cells of the
canvas it ran on: the whole grid's, or its crop tile's, which the host
knows and ``bytes_model.py`` cannot tell from outside) over the sum of
its rows' ``net_routes``.  What a net's route costs in relaxing work:
the number a serial router's heap pops a net stand against, what the
crop ladder exists to lower, and the one count that grows with the
CANVAS where the others grow with the nets.  None on a program whose
result lacks the field, or that routed no net."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    swept = getattr(routes[0], "total_cell_sweeps", None)
    net_routes = sum(s.net_routes for s in routes[0].stats)
    if not swept or not net_routes:
        return None
    return swept / net_routes
