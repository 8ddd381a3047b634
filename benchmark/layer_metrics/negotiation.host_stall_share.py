"""Share of a route's wall in which the host worked with no window in
flight on the device: ``route.pipeline.host_serial_ms_total`` over the
route's wall, for the window's first route.  A HOST share, from the
host's clock."""


def read(ctx):
    gauges, times = ctx.get("pipeline_gauges"), ctx.get("route_times")
    if not gauges or not times:
        return None
    serial_ms = gauges[0].get("route.pipeline.host_serial_ms_total")
    if serial_ms is None:
        return None
    return 100.0 * (serial_ms / 1e3) / times[0]
