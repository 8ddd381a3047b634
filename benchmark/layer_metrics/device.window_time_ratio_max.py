"""How far the same window of two routes of one run differs in time:
over ALL the run's timed routes, the largest ratio, over the window
index, of the longest to the shortest ``route_time_s`` of that window.
The routes of a run are identical in every count, so on a quiet device
it reads 1.000-1.002; a route that met a slow window (the device, or
the runtime under one dispatch, running the same program slower) shows
as that window's ratio.  Reads only what the rows always had; None with
fewer than two routes, routes of unequal row counts, or a window that
took no time."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes or len(routes) < 2:
        return None
    tables = [[s.route_time_s for s in r.stats] for r in routes]
    if not tables[0] or any(len(t) != len(tables[0]) for t in tables):
        return None
    by_window = list(zip(*tables))
    if any(min(w) <= 0 for w in by_window):
        return None
    return max(max(w) / min(w) for w in by_window)
