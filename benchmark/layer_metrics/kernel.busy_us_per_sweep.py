"""Device busy time of one route over its sweeps.  The profiler runs
for a slice of the window, so a route's busy time is the slice's busy
share times the route's median wall.  An UPPER bound on the time of one
sweep until the kernels carry names: busy time holds the cost-field
gathers, the traceback, the STA and the commit as well."""

from benchmark import bytes_model


def read(ctx):
    busy_s = bytes_model.route_busy_s(ctx)
    if busy_s is None:
        return None
    sweeps = ctx["routes"][0].total_relax_steps
    return 1e6 * busy_s / sweeps if sweeps else None
