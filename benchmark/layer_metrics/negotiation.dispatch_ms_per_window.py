"""Host milliseconds a window spends handing its program(s) to the
runtime: ``route.pipeline.dispatch_ms_total`` (the time inside the
route's ``route.pipeline.dispatch`` spans) over the route's windows,
for the window's first route.  None where the program has no such
counter."""


def read(ctx):
    gauges, routes = ctx.get("pipeline_gauges"), ctx.get("routes")
    if not gauges or not routes or not routes[0].stats:
        return None
    total_ms = gauges[0].get("route.pipeline.dispatch_ms_total")
    if total_ms is None:
        return None
    return total_ms / len(routes[0].stats)
