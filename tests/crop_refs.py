"""The crop scaffolding as `planes_relax_cropped` ran it until PR 41:
every cut a ``jax.vmap(lax.dynamic_slice)`` and every write-back a
``jax.vmap(lax.dynamic_update_slice)`` with per-net start indices --
which XLA:TPU expands into a loop over the batch, 27 of them a call --
and all of it, the 15 geometry cuts included, redone by every wave.
Kept as the REFERENCE the select forms of ``route/planes.py``
(`cut_tiles`, `put_tiles`, `crop_cut`, `planes_relax_cropped`) are held
to, bit for bit, alone and inside a whole route (not a test file:
imported by tests/test_planes.py and tools/crop_forms.py)."""

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

from parallel_eda_tpu.route import planes
from parallel_eda_tpu.route.planes import PlanesGeom, PlanesGraph


def cut_tiles_vmap(a, ox, oy, xs: int, ys: int):
    """planes.cut_tiles by a dynamic slice a net: ``a`` [G, ..., X, Y]
    (G == 1 shared, or G == B) -> [B, ..., xs, ys]."""
    lead = a.shape[1:-2]

    def one(t, x0, y0):
        return lax.dynamic_slice(t, (0,) * len(lead) + (x0, y0),
                                 lead + (xs, ys))

    if a.shape[0] == 1:
        return jax.vmap(lambda x0, y0: one(a[0], x0, y0))(ox, oy)
    return jax.vmap(one)(a, ox, oy)


def put_tiles_vmap(full, tiles, ox, oy):
    """planes.put_tiles by a dynamic update slice a net."""
    lead = (0,) * (full.ndim - 3)
    full = jnp.broadcast_to(full, tiles.shape[:1] + full.shape[1:])
    return jax.vmap(lambda f, t, x0, y0: lax.dynamic_update_slice(
        f, t, lead + (x0, y0)))(full, tiles, ox, oy)


def geom_cropped_vmap(pg: PlanesGraph, ox, oy, cnx: int, cny: int,
                      full=None) -> PlanesGeom:
    """planes.geom_cropped with every cut a vmapped dynamic slice."""
    full = full if full is not None else planes.geom_full(pg)

    def crop(a, xs, ys):
        return cut_tiles_vmap(a, ox, oy, xs, ys)

    return PlanesGeom(
        brk_before_x=crop(full.brk_before_x, cnx, cny + 1),
        brk_after_x=crop(full.brk_after_x, cnx, cny + 1),
        brk_before_y=crop(full.brk_before_y, cnx + 1, cny),
        brk_after_y=crop(full.brk_after_y, cnx + 1, cny),
        first_x=crop(full.first_x, cnx, cny + 1),
        last_x=crop(full.last_x, cnx, cny + 1),
        first_y=crop(full.first_y, cnx + 1, cny),
        last_y=crop(full.last_y, cnx + 1, cny),
        delay_x=crop(full.delay_x, cnx, cny + 1),
        delay_y=crop(full.delay_y, cnx + 1, cny),
        delay_y_rot0=crop(full.delay_y_rot0, cnx + 1, cny),
        delay_y_rot1=crop(full.delay_y_rot1, cnx + 1, cny),
        idxx=crop(full.idxx, cnx, cny + 1),
        idxy=crop(full.idxy, cnx + 1, cny),
        base_par=crop(full.base_par, cnx + 1, cny + 1),
        stride_x=pg.shape_x[2], directional=pg.directional,
        inc_track=pg.inc_track, group_tracks=pg.group_tracks)


def crop_state_vmap(pg: PlanesGraph, d0_flat, cc_flat, wenter0, ox, oy,
                    cnx: int, cny: int):
    """The six state cuts of one call: (full canvases (dxf, dyf, wxf,
    wyf), tiles (dx, dy, ccx, ccy, wx, wy))."""
    B = d0_flat.shape[0]
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1

    def crop4(a, xs, ys):
        return cut_tiles_vmap(a, ox, oy, xs, ys)

    dxf = d0_flat[:, :ncx].reshape(B, W, NX, NYp1)
    dyf = d0_flat[:, ncx:].reshape(B, W, NXp1, NY)
    ccxf = cc_flat[:, :ncx].reshape(B, W, NX, NYp1)
    ccyf = cc_flat[:, ncx:].reshape(B, W, NXp1, NY)
    wxf = wenter0[:, :ncx].reshape(B, W, NX, NYp1)
    wyf = wenter0[:, ncx:].reshape(B, W, NXp1, NY)
    return ((dxf, dyf, wxf, wyf),
            (crop4(dxf, cnx, cny + 1), crop4(dyf, cnx + 1, cny),
             crop4(ccxf, cnx, cny + 1), crop4(ccyf, cnx + 1, cny),
             crop4(wxf, cnx, cny + 1), crop4(wyf, cnx + 1, cny)))


def scatter_state_vmap(gm_full: PlanesGeom, fulls, tiles, ox, oy):
    """The six write-backs of one call, flattened to planes_relax's
    (dist, pred, wenter)."""
    dxf, dyf, wxf, wyf = fulls
    dx, dy, predx, predy, wx, wy = tiles
    B = dxf.shape[0]

    def put(full, tile):
        return put_tiles_vmap(full, tile, ox, oy)

    def flat(a, b):
        return jnp.concatenate([a.reshape(B, -1), b.reshape(B, -1)],
                               axis=1)

    return (flat(put(dxf, dx), put(dyf, dy)),
            flat(put(gm_full.idxx, predx), put(gm_full.idxy, predy)),
            flat(put(wxf, wx), put(wyf, wy)))


def planes_relax_cropped_vmap(pg: PlanesGraph, d0_flat, cc_flat, crit_c,
                              wenter0, nsweeps: int, ox, oy,
                              cnx: int, cny: int, plane_dtype: str = "f32",
                              cut=None):
    """planes.planes_relax_cropped as it was: everything cut inside the
    call, from ``cc_flat`` (a caller's ``cut`` is not looked at)."""
    gm_full = planes.geom_full(pg)
    gm = geom_cropped_vmap(pg, ox, oy, cnx, cny, full=gm_full)
    fulls, (dx, dy, cc_x, cc_y, wx, wy) = crop_state_vmap(
        pg, d0_flat, cc_flat, wenter0, ox, oy, cnx, cny)
    if plane_dtype != "f32":
        dt = planes.plane_jnp_dtype(plane_dtype)
        cc_x = cc_x.astype(dt).astype(jnp.float32)
        cc_y = cc_y.astype(dt).astype(jnp.float32)
    predx = jnp.broadcast_to(gm.idxx, dx.shape)
    predy = jnp.broadcast_to(gm.idxy, dy.shape)
    costs = planes._sweep_costs(gm, crit_c, cc_x, cc_y)

    def sweep(s):
        return planes._sweep_once(gm, s, crit_c, cc_x, cc_y, costs)

    tiles, stats = planes._run_relax(
        sweep, (dx, dy, predx, predy, wx, wy), nsweeps, plane_dtype)
    if plane_dtype != "f32":
        tiles = planes._dequantize_plane_state(tiles)
    return scatter_state_vmap(gm_full, fulls, tiles, ox, oy) + (stats,)


@contextlib.contextmanager
def vmap_scaffolding():
    """Inside: ``planes.planes_relax_cropped`` is the parent's, so every
    program traced cuts and writes back by per-net slices, wave by wave.
    Yields a list that holds an entry a trace of it.  The jitted window
    programs are dropped on the way in and out (they hold what they
    traced)."""
    def drop_programs():
        for prog in (planes.route_window_planes,
                     planes.route_batch_resident_planes):
            prog.clear_cache()

    traced = []

    def counted(*a, **kw):
        traced.append(1)
        return planes_relax_cropped_vmap(*a, **kw)

    built = planes.planes_relax_cropped
    drop_programs()
    planes.planes_relax_cropped = counted
    try:
        yield traced
    finally:
        planes.planes_relax_cropped = built
        drop_programs()
