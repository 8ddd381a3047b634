"""Seconds a route spends in the wirelength finishing pass: the
``route_time_s`` of the ``RouteResult.stats`` rows of kind ``finish``
(the multi-sink nets re-routed one sink a wave after the first legal
window), the run's first timed route.  What re-legalises the pass
afterwards is kind ``relegalise`` and not in here.  0 where no pass
ran; None where the rows carry no ``kind`` (a program from before the
window ledger)."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows or not all(getattr(s, "kind", "") for s in rows):
        return None
    return sum(s.route_time_s for s in rows if s.kind == "finish")
