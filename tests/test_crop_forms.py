"""The crop scaffolding of a cropped relaxation (ISSUE 41):
``planes.cut_tiles`` / ``put_tiles`` -- selects between static slices,
no loop over the batch -- and ``crop_cut``, the geometry and the
congestion tiles cut once a step, against the per-net dynamic slices
they replaced (``tests/crop_refs.py``), alone, in one relaxation and in
a whole route; ``tools/crop_forms.py`` on a tiny shape."""

import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.route import Router, RouterOpts
from test_planes import _field_graph, _placed


def _crop_forms_tool():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / \
        "crop_forms.py"
    spec = importlib.util.spec_from_file_location("crop_forms", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# (grid, W, tile) of the cells that dispatch a cropped rung: the tool's
CROP_CELLS = {name: (shape[2], shape[3], shape[5])
              for name, shape in _crop_forms_tool().SHAPES.items()}


@pytest.mark.parametrize("cell", list(CROP_CELLS))
def test_the_cut_and_the_write_back_equal_the_per_net_slices(cell):
    """planes.cut_tiles / put_tiles at a cell's (grid, W, tile), three
    nets -- origins (0, 0), the clamp grid - tile, and seeded -- are
    vmapped dynamic slices bit for bit: float canvases holding INF, a
    shared bool mask, the shared s32 id plane (written back: self ids
    outside the tile, the tiles' payload inside) and the 3-d parity
    plane."""
    import jax

    from crop_refs import cut_tiles_vmap, put_tiles_vmap
    from parallel_eda_tpu.route.planes import cut_tiles, put_tiles

    n, W, tile = CROP_CELLS[cell]
    B = 3
    rng = np.random.default_rng(n * W)
    ox = np.array([0, n - tile, rng.integers(0, n - tile + 1)], np.int32)
    oy = np.array([0, n - tile, rng.integers(0, n - tile + 1)], np.int32)
    oy[2] = (oy[2] + 1) % (n - tile + 1)

    def field(shape):
        a = rng.uniform(1e-10, 1e-8, shape).astype(np.float32)
        a[rng.random(shape) < 0.3] = np.inf
        return a

    def both(cut, put):
        outs = []
        for X, Y, xs, ys in ((n, n + 1, tile, tile + 1),
                             (n + 1, n, tile + 1, tile)):
            d = field((B, W, X, Y))
            mask = rng.random((1, W, X, Y)) < 0.5
            ids = np.arange(W * X * Y, dtype=np.int32).reshape(1, W, X, Y)
            outs += [cut(d, ox, oy, xs, ys), cut(mask, ox, oy, xs, ys),
                     cut(ids, ox, oy, xs, ys)]
            outs += [put(d, field((B, W, xs, ys)), ox, oy),
                     put(ids, rng.integers(0, 1 << 20, (B, W, xs, ys))
                         .astype(np.int32), ox, oy)]
        par = (np.add.outer(np.arange(n + 1), np.arange(n + 1)) % 2
               ).astype(np.int32)[None]
        return outs + [cut(par, ox, oy, tile + 1, tile + 1)]

    state = rng.bit_generator.state
    got = jax.jit(lambda: both(cut_tiles, put_tiles))()
    rng.bit_generator.state = state
    ref = jax.jit(lambda: both(cut_tiles_vmap, put_tiles_vmap))()
    for a, b in zip(got, ref):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.isinf(np.asarray(got[0])).any()


def _seeded_crop_fields(pg, B, cnx, cny, seed):
    """(d0, cc, crit_c, wenter0, ox, oy): cost fields finite inside
    each net's tile alone, two seeds a net inside it, the first net's
    tile at the origin and the second's at the clamp."""
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    ncx = W * NX * NYp1
    rng = np.random.default_rng(seed)
    ox = rng.integers(0, NX - cnx + 1, B).astype(np.int32)
    oy = rng.integers(0, NY - cny + 1, B).astype(np.int32)
    ox[:2] = (0, NX - cnx)
    oy[:2] = (0, NY - cny)
    cc = np.full((B, pg.ncells), np.inf, np.float32)
    ccx = cc[:, :ncx].reshape(B, W, NX, NYp1)
    ccy = cc[:, ncx:].reshape(B, W, NXp1, NY)
    for b in range(B):
        ccx[b, :, ox[b]:ox[b] + cnx, oy[b]:oy[b] + cny + 1] = rng.uniform(
            0.5e-10, 2e-10, (W, cnx, cny + 1))
        ccy[b, :, ox[b]:ox[b] + cnx + 1, oy[b]:oy[b] + cny] = rng.uniform(
            0.5e-10, 2e-10, (W, cnx + 1, cny))
    d0 = np.full((B, pg.ncells), np.inf, np.float32)
    w0 = np.zeros((B, pg.ncells), np.float32)
    for b in range(B):
        seeds = rng.choice(np.where(np.isfinite(cc[b]))[0], 2,
                           replace=False)
        d0[b, seeds] = (0.0, 1e-10)
        w0[b, seeds[1]] = 3e-11
    crit = rng.uniform(0.0, 0.9, (B, 1, 1, 1)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (d0, cc, crit, w0, ox, oy))


@pytest.mark.parametrize("plane_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("kind", ["k4n4", "directional_l4"])
def test_the_cropped_relaxation_equals_the_one_of_per_net_slices(
        kind, plane_dtype):
    """planes_relax_cropped on seeded fields is the parent's (every cut
    and write-back a vmapped dynamic slice, all inside the call) bit
    for bit -- dist, pred, wenter and the sweep counts -- in both wire
    models and both plane dtypes; and so is the call a step makes: the
    geometry and the UNSCALED field cut once (crop_cut), the tiles
    scaled by the wave's weight after.  Both sides op by op, the sweep
    loop alone compiled (the one body both share): compiled whole,
    XLA:CPU contracts a sum's product into a fused multiply-add or
    not by what it fused around it, and the per-net slices' program
    differs by an ulp from its own op-by-op run."""
    from crop_refs import planes_relax_cropped_vmap
    from parallel_eda_tpu.route.planes import (crop_cut,
                                               planes_relax_cropped)

    _, pg = _field_graph(kind)
    cnx = cny = 3
    assert pg.shape_x[1] - cnx == 2         # origins 0, 1, 2
    d0, cc, crit_c, w0, ox, oy = _seeded_crop_fields(pg, 5, cnx, cny, 7)

    def run(fn, field, **kw):
        return fn(pg, d0, field, crit_c, w0, 12, ox, oy, cnx, cny,
                  plane_dtype=plane_dtype, **kw)

    ref = run(planes_relax_cropped_vmap, cc)
    assert np.isfinite(np.asarray(ref[0])).sum() > 5 * 2
    assert int(ref[3][1]) > 1
    # _step_core's call: the field a wave scales, cut before the scale
    cw = 1.0 - crit_c[:, 0, 0, 0]
    base = cc / cw[:, None]
    scaled = cw[:, None] * base
    cut = crop_cut(pg, ox, oy, cnx, cny, base).scaled(cw)
    for got, want in (
            (run(planes_relax_cropped, cc), ref),
            (run(planes_relax_cropped, scaled, cut=cut),
             run(planes_relax_cropped_vmap, scaled))):
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_cropped_route_equals_the_route_of_per_net_slices():
    """A whole route that dispatches a cropped rung in every window is
    the route with the parent's scaffolding in the cropped relaxation's
    place -- paths, sink delays, occupancy and every count of every
    window row -- and books its cropped waves."""
    from crop_refs import vmap_scaffolding
    from sink_pick_refs import assert_same_route

    f = _placed("directional_l4_19x19")

    def route():
        return Router(f.rr, RouterOpts(batch_size=16)).route(f.term)

    with vmap_scaffolding() as traced:
        ref = route()
    assert traced, "the reference scaffolding was never traced"
    res = route()
    assert res.success
    assert_same_route(res, ref)
    assert 0 < res.total_waves_cropped < res.total_waves
    assert sum(s.waves_cropped for s in res.stats) \
        == res.total_waves_cropped


def test_the_crop_forms_tool_on_a_tiny_shape(capsys, monkeypatch):
    """tools/crop_forms.py: off the TPU it exits 2 before it times a
    form; rehearsed on a tiny shape its row holds every column, and
    every form cuts and writes what the per-net slices do.  No time of
    it means anything here."""
    tool = _crop_forms_tool()
    assert tool.main(["--shapes", "route_tight", "--reps", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "not a TPU" in err
    monkeypatch.setitem(tool.SHAPES, "tiny",
                        ("k6_n10_40nm_arch", {}, 6, 16, 3, 4))
    assert tool.forms_agree("tiny", tool.FORMS, seed=1)
    row = tool.time_shape("tiny", ("select",), reps=1, seed=1)
    assert row["device"] == "cpu" and row["directional"]
    assert (row["grid"], row["W"], row["B"], row["tile"],
            row["origins"]) == (6, 16, 3, 4, 3)
    want = {f"select.{third}_us"
            for third in ("geom", "cuts", "puts", "all")} | {
        "wave_us", "step_us", "floor_us", "sweep_full_us",
        "sweep_tile_us"}
    assert want <= set(row) and all(row[k] > 0 for k in want)
