"""Seconds of a route whose windows ran with the relaxation's scans
guarded against a predecessor 2-cycle: the ``route_time_s`` of the
``RouteResult.stats`` rows with ``scan_guard`` True, the run's first
timed route.  The window driver switches the guard on (a static field
of the relaxation's graph, so guarded windows are programs of their
own) after a window that ended with nothing over capacity, a sink
unreached and no snapshot to return, the state in which the unguarded
programs run out the route's iterations and return it NOT legal; from
there to the route's end every window is guarded.  0 on a route that
never met the state; None where the rows carry no such field (a
program from before the guard) or no ``kind``."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    if not rows or not all(getattr(s, "kind", "") for s in rows):
        return None
    if not all(hasattr(s, "scan_guard") for s in rows):
        return None
    return sum(s.route_time_s for s in rows if s.scan_guard)
