"""The DECLARED bytes of a relaxation sweep, from shapes alone.

``xla_bytes_per_cell`` is a copy of
``parallel_eda_tpu/route/planes_pallas.py``'s function of that name: a
model ("~15 canvas traversals" of the XLA lowering) that no chip run
has checked.  A share worked out from it is called "modeled" for that
reason, wherever it is printed.
"""

from __future__ import annotations

# smallest rung of the router's crop ladder (router._size_class_buckets
# base): a cropped sweep covers at least a tile of this side
CROP_TILE_MIN = 8


def xla_bytes_per_cell(itemsize: int = 4) -> int:
    """Modeled HBM bytes one cell moves per XLA sweep: the three
    loop-carried sets (dist, wenter, congestion) in the plane dtype and
    twelve f32 scan and turn intermediates -- 60 B/cell in f32."""
    return 3 * int(itemsize) + 12 * 4


def plane_cells(W: int, nx: int, ny: int) -> int:
    """Cells of one net's canvas pair: CHANX [W, nx, ny+1] and CHANY
    [W, nx+1, ny]."""
    return W * nx * (ny + 1) + W * (nx + 1) * ny


def route_bytes_lower_bound(W: int, nx: int, ny: int, batch: int,
                            sweeps_full: int, sweeps_cropped: int,
                            itemsize: int = 4) -> float:
    """Modeled bytes of one route's relaxation sweeps.  A sweep moves
    ``batch`` nets' canvases; a full sweep covers the whole grid, a
    cropped one is counted at the smallest tile of the crop ladder
    (which tile each sweep ran on cannot be told from outside), so this
    is a lower bound of the model."""
    tile = plane_cells(W, min(nx, CROP_TILE_MIN), min(ny, CROP_TILE_MIN))
    cells = (sweeps_full * plane_cells(W, nx, ny)
             + sweeps_cropped * tile) * batch
    return float(cells * xla_bytes_per_cell(itemsize))


def route_busy_s(ctx: dict):
    """Device busy seconds of one route of the window: the traced
    slice's busy share times the routes' median wall (None where there
    is no trace, or no op ran in it)."""
    import statistics

    trace, times = ctx.get("trace"), ctx.get("route_times")
    if not trace or not times or not trace["busy_s"]:
        return None
    return (trace["busy_s"] / trace["window_s"]) * statistics.median(times)
