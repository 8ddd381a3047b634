"""1 - busy / window of the traced window, from the profiler's trace:
busy is the union of the intervals in which an op ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["n_device_planes"]:
        return None
    return 100.0 * trace["idle_share"]
