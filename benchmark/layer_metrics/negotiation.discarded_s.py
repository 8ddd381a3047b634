"""Seconds of a route whose result the route threw away: the
``route_time_s`` of the ``RouteResult.stats`` rows with ``kept`` False
(the windows past the finishing pass's snapshot, when re-legalisation
ran out of iterations and the snapshot was restored), the run's first
timed route.  ``negotiation.discarded_sweep_share`` is the same rows in
sweeps.  0 where every window is kept; None where the rows carry no
``kind`` (a program from before the window ledger)."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows or not all(getattr(s, "kind", "") for s in rows):
        return None
    return sum(s.route_time_s for s in rows if not s.kept)
