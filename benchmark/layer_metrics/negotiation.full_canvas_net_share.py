"""Share of the nets a route's windows dispatched that went to the
full canvas and not to a cropped rung: the counter
``route.crop.net_dispatches_full_total`` over its sum with
``route.crop.net_dispatches_cropped_total`` (nets x windows, counted
where the window driver builds its dispatch).  The registry is the
process's and is not reset between routes; the routes of a run are
identical, so the ratio over all of them is one route's.  None where
the program has no such counters or dispatched nothing."""


def read(ctx):
    reg = ctx.get("registry") or {}
    full = reg.get("route.crop.net_dispatches_full_total")
    cropped = reg.get("route.crop.net_dispatches_cropped_total")
    if full is None or cropped is None or not full + cropped:
        return None
    return 100.0 * full / (full + cropped)
