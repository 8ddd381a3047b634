"""Whole-route price of a sweep in the negotiation proper: the seconds
of the ``RouteResult.stats`` rows of kind ``first`` or ``negotiate``
over their ``relax_steps``, in microseconds, the run's first timed
route.  Everything a window does is in the seconds, as in
``kernel.busy_us_per_sweep``; unlike that one it is taken over the
whole route, not a slice, and it does not move when a window of another
kind (a restart, a finishing pass, what follows it) comes or goes.
None where the rows carry no ``kind`` (a program from before the window
ledger) or the chosen rows ran no sweep."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows or not all(getattr(s, "kind", "") for s in rows):
        return None
    rows = [s for s in rows if s.kind in ("first", "negotiate")]
    sweeps = sum(s.relax_steps for s in rows)
    if not sweeps:
        return None
    return 1e6 * sum(s.route_time_s for s in rows) / sweeps
