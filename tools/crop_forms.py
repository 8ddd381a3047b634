"""Time the crop scaffolding of a cropped relaxation ALONE, form by
form, at the benchmark cells' shapes (ROADMAP Queue 1 "How to price an
item": a form timed alone on the chip x a count of its calls; the
calls are `RouteResult.total_waves_cropped`).

    python3 tools/crop_forms.py [--shapes route_relaxed,...] [--reps 20]
        [--forms vmap,select,gather,flat]
        [--out chiprun_out/crop_forms.json] [--allow-cpu]

A cropped relaxation (`planes.planes_relax_cropped`) cuts each net's
(tile x tile) window out of the batch's canvases at a per-net origin,
sweeps the tiles and writes them back.  For each shape (grid, W, B,
tile) and its routing architecture's real geometry: seeded canvases (a
tenth of the cells INF), seeded origins in [0, grid - tile], and the
microseconds a call of each third of that scaffolding in each FORM:

    vmap     the parent's (tests/crop_refs.py): `jax.vmap` of
             `lax.dynamic_slice` / `dynamic_update_slice`, which XLA:TPU
             expands into one loop over the batch a cut or a put
    select   `planes.cut_tiles` / `put_tiles`: one select a bit of the
             largest origin between static slices, one select under the
             tile's footprint (no gather, no scatter, no loop)
    gather   `lax.gather` / `lax.scatter` written out with explicit
             batching dimensions and `promise_in_bounds` (what `vmap`
             emits already, less the clip)
    flat     element gathers / scatters on the [B, ncells] flats the
             callers hold (`take_along_axis`, `.at[].set`), indices
             from iotas

Columns of a row (all microseconds a call, one jitted loop of
``--reps`` dependent calls under the host's clock -- the loop carries
the origins or a canvas through every output, so no call is hoisted or
narrowed -- the best of three):

    <form>.geom_us   the 15 geometry cuts (shared [1, W, X, Y] canvases)
    <form>.cuts_us   the 6 state cuts (dist, congestion, entry weight; x, y)
    <form>.puts_us   the 6 write-backs (dist, pred, entry weight; x, y)
    <form>.all_us    all 27 in one call: the parent's scaffolding of ONE
                     cropped wave (`vmap` and `select` only)
    wave_us          what a wave keeps with the step's cut hoisted: 4
                     cuts, the scaled congestion tiles, 6 write-backs
                     (`planes.crop_state` + `CropCut.scaled` +
                     `scatter_state`)
    step_us          what a step does once: `planes.crop_cut` (15 + 2)
    floor_us         the harness and the least a cut can cost: the 6 state
                     cuts at ONE origin for the whole batch (a plain
                     dynamic slice)
    sweep_full_us    one `_sweep_once` on the full canvases, for scale
    sweep_tile_us    one on the tiles

The bar of ISSUE 41: ``wave_us`` at most a third of ``vmap.all_us`` at
every shape.  Prints one JSON line a shape and writes them all to
``--out``.  Refuses to run off the TPU (exit 2, chip_smoke.py's rule)
unless ``--allow-cpu`` asks for a rehearsal, whose lines say
``"device": "cpu"`` and are no device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# cell -> (architecture builder, its arguments, grid, W, B, tile): the
# six cells that dispatch a cropped rung (ISSUE 41)
_K4N4 = ("minimal_arch", {"K": 4, "N": 4, "I": 10, "io_capacity": 2})
SHAPES = {
    "route_relaxed": _K4N4 + (22, 20, 64, 16),
    "route_tight": _K4N4 + (22, 16, 64, 16),
    "route_scale": ("k6_n10_40nm_arch", {}, 19, 88, 64, 16),
    "route_fanout": ("k6_n10_40nm_arch", {}, 20, 56, 64, 16),
    "route_hetero": ("k6_frac_n10_mem32k_40nm_arch", {}, 25, 64, 64, 16),
    "route_dsp": ("k6_frac_n10_mem32k_40nm_arch",
                  {"mult_combinational": True}, 24, 64, 64, 16),
}
FORMS = ("vmap", "select", "gather", "flat")


def build_planes_of(builder: str, args: dict, n: int, W: int):
    """PlanesGraph of an n x n device of the architecture, from shapes
    alone (no netlist, no route)."""
    from parallel_eda_tpu.arch import builtin
    from parallel_eda_tpu.route.planes import build_planes
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import make_grid

    arch = getattr(builtin, builder)(chan_width=W, **args)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the k6 file asks for Wilton
        return build_planes(build_rr_graph(arch, make_grid(arch, n, n)))


# ---- the candidate forms (the parent's is tests/crop_refs.py, the
# chosen one planes.cut_tiles / put_tiles) ----

def cut_tiles_gather(a, ox, oy, xs: int, ys: int):
    import jax.numpy as jnp
    from jax import lax

    a = jnp.broadcast_to(a, ox.shape + a.shape[1:])
    nd = a.ndim
    dn = lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, nd)), collapsed_slice_dims=(),
        start_index_map=(nd - 2, nd - 1), operand_batching_dims=(0,),
        start_indices_batching_dims=(0,))
    return lax.gather(a, jnp.stack([ox, oy], axis=1), dn,
                      (1,) + a.shape[1:-2] + (xs, ys),
                      indices_are_sorted=True, unique_indices=True,
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def put_tiles_gather(full, tiles, ox, oy):
    import jax.numpy as jnp
    from jax import lax

    full = jnp.broadcast_to(full, tiles.shape[:1] + full.shape[1:])
    nd = full.ndim
    dn = lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, nd)), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(nd - 2, nd - 1),
        operand_batching_dims=(0,), scatter_indices_batching_dims=(0,))
    return lax.scatter(full, jnp.stack([ox, oy], axis=1), tiles, dn,
                       indices_are_sorted=True, unique_indices=True,
                       mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _flat_index(shape, ox, oy, xs: int, ys: int):
    """[B, prod(lead) * xs * ys] positions of the tiles' elements in
    the row-major flat of a [..., X, Y] canvas."""
    import jax.numpy as jnp

    lead = int(np.prod(shape[1:-2], dtype=np.int64))
    X, Y = shape[-2:]
    w = jnp.arange(lead, dtype=jnp.int32)[None, :, None, None]
    x = jnp.arange(xs, dtype=jnp.int32)[None, None, :, None]
    y = jnp.arange(ys, dtype=jnp.int32)[None, None, None, :]
    idx = ((w * X + ox[:, None, None, None] + x) * Y
           + oy[:, None, None, None] + y)
    return idx.reshape(ox.shape[0], -1)


def cut_tiles_flat(a, ox, oy, xs: int, ys: int):
    import jax.numpy as jnp

    B = ox.shape[0]
    flat = jnp.broadcast_to(a, (B,) + a.shape[1:]).reshape(B, -1)
    idx = _flat_index(a.shape, ox, oy, xs, ys)
    return jnp.take_along_axis(flat, idx, axis=1).reshape(
        (B,) + a.shape[1:-2] + (xs, ys))


def put_tiles_flat(full, tiles, ox, oy):
    import jax.numpy as jnp

    B = tiles.shape[0]
    flat = jnp.broadcast_to(full, (B,) + full.shape[1:]).reshape(B, -1)
    idx = _flat_index(full.shape, ox, oy, *tiles.shape[-2:])
    rows = jnp.arange(B)[:, None]
    return flat.at[rows, idx].set(
        tiles.reshape(B, -1), unique_indices=True).reshape(
        (B,) + full.shape[1:])


def form_pair(form: str):
    """(cut, put) of a form, under planes.cut_tiles / put_tiles' own
    signatures."""
    if form == "vmap":
        import crop_refs
        return crop_refs.cut_tiles_vmap, crop_refs.put_tiles_vmap
    if form == "select":
        from parallel_eda_tpu.route import planes
        return planes.cut_tiles, planes.put_tiles
    if form == "gather":
        return cut_tiles_gather, put_tiles_gather
    if form == "flat":
        return cut_tiles_flat, put_tiles_flat
    raise ValueError(f"no form {form!r} (have {FORMS})")


# ---- the thirds of one call's scaffolding, written over (cut, put) ----

def geom_third(cut, gm_full, ox, oy, tile: int):
    """The 15 shared canvases geom_cropped cuts, each to the tile its
    plane takes (an x plane (tile, tile + 1), a y plane (tile + 1,
    tile), the parity plane (tile + 1, tile + 1))."""
    import jax

    nx, ny = (n - 1 for n in gm_full.base_par.shape[-2:])
    return [cut(a, ox, oy, tile + a.shape[-2] - nx, tile + a.shape[-1] - ny)
            for a in jax.tree_util.tree_leaves(gm_full) if a.ndim >= 3]


def cuts_third(cut, canv, ox, oy, tile: int):
    """canv: (dx, dy, ccx, ccy, wx, wy) full canvases."""
    return [cut(a, ox, oy, tile + (i % 2), tile + 1 - (i % 2))
            for i, a in enumerate(canv)]


def puts_third(put, gm_full, canv, tiles, ox, oy):
    """canv as above; tiles (dx, dy, predx, predy, wx, wy)."""
    dxf, dyf, _, _, wxf, wyf = canv
    dx, dy, px, py, wx, wy = tiles
    return [put(dxf, dx, ox, oy), put(dyf, dy, ox, oy),
            put(gm_full.idxx, px, ox, oy), put(gm_full.idxy, py, ox, oy),
            put(wxf, wx, ox, oy), put(wyf, wy, ox, oy)]


def _used(outs):
    """A scalar that reads every element of every output."""
    import jax.numpy as jnp

    return sum(jnp.sum(o != 0, dtype=jnp.int32) for o in outs)


def timed_loop(body_outs, reps: int):
    """``body_outs(ox, oy, d)`` -> list of arrays, as a jitted loop of
    ``reps`` calls: the origins and the canvas ``d`` each call is
    handed depend on every element the call before it produced (an
    origin moves by one, inside its range, on a count the compiler
    cannot know to be impossible)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(ox, oy, d, omax):
        def body(_, c):
            ox, oy, d = c
            used = _used(body_outs(ox, oy, d))
            odd = used == -7
            return (jnp.where(odd, (ox + 1) % (omax + 1), ox),
                    jnp.where(odd, (oy + 1) % (omax + 1), oy),
                    d.at[0, 0, 0, 0].add(jnp.where(odd, 1.0, 0.0)))
        return lax.fori_loop(0, reps, body, (ox, oy, d))

    return loop


def us_per_call(loop, args, reps: int) -> float:
    """Microseconds a call inside ``loop``: the best of three timed
    runs after one that compiles."""
    import jax

    jax.block_until_ready(loop(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*args))
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / reps


def seeded_inputs(pg, B: int, tile: int, seed: int):
    """(ox, oy, omax, canv, tiles, crit_c): origins over their whole
    range (the first net at 0, the second at the clamp), six canvases
    and six tiles, a tenth of the float cells INF."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    W, NX, NYp1 = pg.shape_x
    omax = NX - tile
    ox = rng.integers(0, omax + 1, B).astype(np.int32)
    oy = rng.integers(0, omax + 1, B).astype(np.int32)
    ox[:2] = oy[:2] = (0, omax)

    def field(shape, inf=True):
        a = rng.uniform(1e-10, 1e-8, shape).astype(np.float32)
        if inf:
            a[rng.random(shape) < 0.1] = np.inf
        return jnp.asarray(a)

    sx, sy = (B,) + pg.shape_x, (B,) + pg.shape_y
    tx, ty = (B, W, tile, tile + 1), (B, W, tile + 1, tile)
    canv = tuple(field(s) for s in (sx, sy, sx, sy, sx, sy))
    tiles = (field(tx), field(ty),
             jnp.asarray(rng.integers(0, pg.ncells, tx).astype(np.int32)),
             jnp.asarray(rng.integers(0, pg.ncells, ty).astype(np.int32)),
             field(tx, inf=False), field(ty, inf=False))
    crit_c = jnp.asarray(rng.uniform(0, 0.9, (B, 1, 1, 1))
                         .astype(np.float32))
    return jnp.asarray(ox), jnp.asarray(oy), omax, canv, tiles, crit_c


def time_shape(name: str, forms, reps: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from parallel_eda_tpu.route import planes

    builder, args, n, W, B, tile = SHAPES[name]
    pg = build_planes_of(builder, args, n, W)
    assert pg.shape_x[:2] == (W, n), (pg.shape_x, W, n)
    gm_full = planes.geom_full(pg)
    ox, oy, omax, canv, tiles, crit_c = seeded_inputs(pg, B, tile, seed)
    d = canv[0]
    row = {"shape": name, "grid": n, "W": W, "B": B, "tile": tile,
           "directional": bool(pg.directional), "origins": omax + 1,
           "device": jax.devices()[0].platform}

    def t(body_outs):
        return us_per_call(timed_loop(body_outs, reps),
                           (ox, oy, d, jnp.int32(omax)), reps)

    def with_d(d):
        return (d,) + canv[1:]

    for form in forms:
        cut, put = form_pair(form)
        if form != "flat":      # a shared canvas has no per-net flat
            row[f"{form}.geom_us"] = t(
                lambda ox, oy, d: geom_third(cut, gm_full, ox, oy, tile))
        row[f"{form}.cuts_us"] = t(
            lambda ox, oy, d: cuts_third(cut, with_d(d), ox, oy, tile))
        row[f"{form}.puts_us"] = t(
            lambda ox, oy, d: puts_third(put, gm_full, with_d(d), tiles,
                                         ox, oy))
        if form in ("vmap", "select"):
            row[f"{form}.all_us"] = t(
                lambda ox, oy, d:
                geom_third(cut, gm_full, ox, oy, tile)
                + cuts_third(cut, with_d(d), ox, oy, tile)
                + puts_third(put, gm_full, with_d(d), tiles, ox, oy))

    # the program's own split: a wave's share and a step's
    def flat(a, b):
        return jnp.concatenate([a.reshape(B, -1), b.reshape(B, -1)], 1)

    w_flat = flat(canv[4], canv[5])
    cw = 1.0 - crit_c[:, 0, 0, 0]

    def wave(ox, oy, d):
        base = planes.CropCut(gm=None, cc_x=tiles[0], cc_y=tiles[1])
        fulls, (dx, dy, wx, wy) = planes.crop_state(
            pg, flat(d, canv[1]), w_flat, ox, oy, tile, tile)
        # a weight that moves with the loop, as a wave's does
        cc = base.scaled(cw + 1e-9 * ox.astype(jnp.float32))
        # the relaxed tiles stand in as seeded ones: the write-back's
        # cost does not depend on what it writes
        return list(planes.scatter_state(
            gm_full, fulls, (tiles[0] + dx, tiles[1] + dy, tiles[2],
                             tiles[3], tiles[4] + wx, tiles[5] + wy),
            ox, oy)) + [cc.cc_x, cc.cc_y]

    row["wave_us"] = t(wave)

    def step(ox, oy, d):
        c = planes.crop_cut(pg, ox, oy, tile, tile, flat(d, canv[1]))
        return jax.tree_util.tree_leaves(c)

    row["step_us"] = t(step)
    row["floor_us"] = t(
        lambda ox, oy, d: [jax.lax.dynamic_slice(
            a, (0, 0, ox[0], oy[0]),
            a.shape[:2] + (tile + (i % 2), tile + 1 - (i % 2)))
            for i, a in enumerate(with_d(d))])

    # one sweep on the full canvases and one on the tiles, for scale
    def sweep_of(gm, state, cc_x, cc_y):
        costs = planes._sweep_costs(gm, crit_c, cc_x, cc_y)

        @jax.jit
        def loop(s):
            return jax.lax.fori_loop(
                0, reps, lambda _, s: planes._sweep_once(
                    gm, s, crit_c, cc_x, cc_y, costs), s)
        return loop, state

    def sweep_us(loop, state):
        return us_per_call(loop, (state,), reps)

    idxx = jnp.broadcast_to(gm_full.idxx, canv[0].shape)
    idxy = jnp.broadcast_to(gm_full.idxy, canv[1].shape)
    row["sweep_full_us"] = sweep_us(*sweep_of(
        gm_full, (canv[0], canv[1], idxx, idxy, canv[4], canv[5]),
        canv[2], canv[3]))
    gm = planes.geom_cropped(pg, ox, oy, tile, tile)
    cut = planes.cut_tiles
    row["sweep_tile_us"] = sweep_us(*sweep_of(
        gm, (tiles[0], tiles[1],
             jnp.broadcast_to(gm.idxx, tiles[0].shape),
             jnp.broadcast_to(gm.idxy, tiles[1].shape),
             tiles[4], tiles[5]),
        cut(canv[2], ox, oy, tile, tile + 1),
        cut(canv[3], ox, oy, tile + 1, tile)))
    return row


def forms_agree(name: str, forms, seed: int, B: int = 0) -> bool:
    """Every form's thirds equal the parent's, element for element, on
    the shape's seeded inputs (``B`` nets of them; 0 = the shape's)."""
    import crop_refs
    from parallel_eda_tpu.route import planes

    builder, args, n, W, B0, tile = SHAPES[name]
    pg = build_planes_of(builder, args, n, W)
    gm_full = planes.geom_full(pg)
    ox, oy, _, canv, tiles, _ = seeded_inputs(pg, B or B0, tile, seed)
    ref = (geom_third(crop_refs.cut_tiles_vmap, gm_full, ox, oy, tile)
           + cuts_third(crop_refs.cut_tiles_vmap, canv, ox, oy, tile)
           + puts_third(crop_refs.put_tiles_vmap, gm_full, canv, tiles,
                        ox, oy))
    for form in forms:
        cut, put = form_pair(form)
        got = (geom_third(cut, gm_full, ox, oy, tile)
               + cuts_third(cut, canv, ox, oy, tile)
               + puts_third(put, gm_full, canv, tiles, ox, oy))
        for a, b in zip(got, ref):
            if a.shape != b.shape or a.dtype != b.dtype or not bool(
                    (a == b).all()):
                return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "crop_forms.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the TPU; the times mean nothing")
    a = ap.parse_args(argv)
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.allow_cpu:
        print(f"crop_forms: the device is {platform!r}, not a TPU; a "
              "time from it is no device number (--allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    forms = a.forms.split(",")
    rows = []
    for name in a.shapes.split(","):
        rows.append(time_shape(name, forms, a.reps, a.seed))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
