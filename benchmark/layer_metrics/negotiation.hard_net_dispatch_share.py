"""Share of the nets a route's windows dispatched that have a terminal
on a hard block (a RAM, a multiplier): the counter
``route.hetero.net_dispatches_hard_total`` over the sum of
``route.crop.net_dispatches_full_total`` and
``route.crop.net_dispatches_cropped_total`` (nets x windows, counted
where the window driver builds its dispatch).  Read it beside the gauge
``route.hetero.nets_hard`` over the routed nets: a dispatch share above
the nets' share says the buses into a hard column are what negotiation
keeps re-routing.  0 on a device of identical clusters.  The registry
is the process's and the routes of a run are identical, so the ratio
over a run is one route's.  None where the program has no such counter
or dispatched nothing."""


def read(ctx):
    reg = ctx.get("registry") or {}
    hard = reg.get("route.hetero.net_dispatches_hard_total")
    full = reg.get("route.crop.net_dispatches_full_total")
    cropped = reg.get("route.crop.net_dispatches_cropped_total")
    if hard is None or full is None or cropped is None \
            or not full + cropped:
        return None
    return 100.0 * hard / (full + cropped)
