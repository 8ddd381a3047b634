"""Modeled bytes of one route's sweeps over its device busy time, as a
share of the chip's peak HBM bandwidth.  The bound is HBM.  A LOWER
bound on the relaxation's roofline share: the bytes are a lower bound
of the model (``bytes_model.route_bytes_lower_bound``) and busy time
holds more than the sweeps.  "Modeled" because the byte count is
declared from shapes, not measured."""

from benchmark import bytes_model


def read(ctx):
    busy_s, peak = bytes_model.route_busy_s(ctx), ctx.get(
        "peak_hbm_bytes_per_s")
    if busy_s is None or not peak:
        return None
    r = ctx["routes"][0]
    W, nx, ny = ctx["plane_shape"]
    cropped = r.total_relax_steps_cropped
    nbytes = bytes_model.route_bytes_lower_bound(
        W, nx, ny, ctx["batch_size"], r.total_relax_steps - cropped,
        cropped)
    return 100.0 * nbytes / busy_s / peak
