"""The relaxation's min-plus scan on per-position slabs (ISSUE 45):
``planes._minplus_scan`` against ``lax.associative_scan`` over the whole
canvases (``tests/scan_refs.py``, the form it replaced) -- the same
odd-even tree, so the same BITS -- alone at every row length of the
benchmark's cells and inside one relaxation, whole and cropped, guarded
and not; ``tools/scan_forms.py`` on a tiny shape."""

import jax
import numpy as np
import pytest

from parallel_eda_tpu.route import planes
from scan_refs import minplus_scan_assoc, scan_form
from test_planes import _assert_route_unmoved_by, _field_graph, _placed

# a row of one, two, three cells; then every row length of the eight
# cells' canvases (n and n + 1 of 11, 19, 20, 22, 24, 25, 26) and of
# the crop rung's tiles (16, 17)
ROW_LENGTHS = (1, 2, 3, 11, 12, 16, 17, 19, 20, 21, 22, 23, 24, 25, 26, 27)


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("axis", [2, 3], ids=["x", "y"])
@pytest.mark.parametrize("n", ROW_LENGTHS)
def test_the_slab_scan_returns_the_whole_array_scans_bits(n, axis, reverse):
    """uint32 view against uint32 view: 30% of the cells INF (outside a
    net's box), 60% of the steps zero (inside a wire's span), a few
    steps INF (against a single-driver wire)."""
    shape = [3, 5, 6, 7]
    shape[axis] = n
    rng = np.random.default_rng(1000 * n + 10 * axis + reverse)
    d = rng.uniform(1e-10, 1e-8, shape).astype(np.float32)
    d[rng.random(shape) < 0.3] = np.inf
    c = rng.uniform(1e-10, 1e-8, shape).astype(np.float32)
    c[rng.random(shape) < 0.6] = 0.0
    c[rng.random(shape) < 0.05] = np.inf

    def run(form):
        return jax.jit(lambda d, c: form(d, c, axis, reverse))(d, c)

    got, want = run(planes._minplus_scan), run(minplus_scan_assoc)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(_bits(got), _bits(want))
    # and it is the recurrence: s[0] = d[0], s[i] <= d[i]
    first = -1 if reverse else 0
    assert np.array_equal(np.take(np.asarray(got), first, axis),
                          np.take(d, first, axis))
    assert (np.asarray(got) <= d).all()


def _relax_case(kind, guard, B=4, tile=3):
    """Seeded inputs of one relaxation over ``kind``'s canvas: two seeds
    a net, a fifth of the cells outside its box (INF), origins over
    their range."""
    _, pg = _field_graph(kind)
    pg = pg.replace(scan_guard=guard)
    rng = np.random.default_rng(7 + guard)
    cc = rng.uniform(0.5, 2.0, (B, pg.ncells)).astype(np.float32) * 1e-10
    cc[rng.random(cc.shape) < 0.2] = np.inf
    d0 = np.full((B, pg.ncells), np.inf, np.float32)
    for b in range(B):
        d0[b, rng.choice(np.where(np.isfinite(cc[b]))[0], 2,
                         replace=False)] = 0.0
    crit_c = rng.uniform(0.0, 0.9, (B, 1, 1, 1)).astype(np.float32)
    w0 = rng.uniform(0, 1e-10, (B, pg.ncells)).astype(np.float32)
    nx = pg.shape_x[1]
    ox = rng.integers(0, nx - tile + 1, B).astype(np.int32)
    oy = rng.integers(0, nx - tile + 1, B).astype(np.int32)
    return pg, (d0, cc, crit_c, w0), (ox, oy, tile)


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
@pytest.mark.parametrize("kind", ["bidirectional", "directional_l4"])
def test_a_relaxation_returns_the_planes_of_the_whole_array_form(kind,
                                                                 guard):
    """planes_relax and planes_relax_cropped, compiled whole, with the
    slab scan and with the form it replaced: distances, predecessors and
    entry weights (x and y planes of each: six) bit for bit, and the
    same sweeps to the fixpoint."""
    pg, args, (ox, oy, tile) = _relax_case(kind, guard)

    def both():
        full = jax.jit(lambda *a: planes.planes_relax(pg, *a, 24))(*args)
        crop = jax.jit(lambda *a: planes.planes_relax_cropped(
            pg, *a, 24, ox, oy, tile, tile))(*args)
        return full + crop

    got = both()
    with scan_form(minplus_scan_assoc) as traced:
        want = both()
    assert len(traced) == 8     # two relaxations of one sweep body each
    assert np.isfinite(np.asarray(got[0])).mean() > 0.2     # it relaxed
    assert int(got[3][0]) > 2
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("kind", ["k4n4", "directional_l4_19x19"])
def test_a_route_equals_the_route_under_the_whole_array_scan(kind,
                                                             monkeypatch):
    """A whole route -- two-way length-1 wires; length-4 single-driver
    wires on a 19 x 19 grid, which dispatches a cropped rung beside the
    full canvas -- is the route with ``lax.associative_scan`` in every
    relaxation's scans: node for node, in iterations, sweeps, waves and
    walk steps, and in every window's dirty set and colours."""
    _assert_route_unmoved_by(_placed(kind), monkeypatch,
                             {"_minplus_scan": minplus_scan_assoc})


def _scan_forms_tool():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / \
        "scan_forms.py"
    spec = importlib.util.spec_from_file_location("scan_forms", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_the_scan_forms_tool_on_a_tiny_shape(capsys, monkeypatch):
    """tools/scan_forms.py: off the TPU it exits 2 before it times a
    form; its shapes are the eight cells; rehearsed on a tiny shape its
    row holds every column and the slab form agrees with the whole-array
    one after the loops' calls.  No time of it means anything here."""
    import json
    import pathlib

    tool = _scan_forms_tool()
    assert tool.main(["--shapes", "route_tight", "--reps", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "not a TPU" in err
    manifest = json.loads((pathlib.Path(tool.REPO) / "BENCHMARK.json")
                          .read_text())
    assert set(tool.SHAPES) == {w["name"] for w in manifest["workloads"]}
    monkeypatch.setitem(tool.SHAPES, "tiny",
                        ("k6_n10_40nm_arch", {}, 6, 16, 3))
    row, agree = tool.run_shape("tiny", tool.FORMS, reps=2, seed=1)
    assert agree
    assert row["device"] == "cpu" and row["directional"]
    assert (row["grid"], row["W"], row["B"], row["reps"]) == (6, 16, 3, 2)
    want = {f"{form}.{what}_us" for form in tool.FORMS
            for what in list(tool.SCANS) + ["sweep"]}
    assert want <= set(row) and all(row[k] > 0 for k in want)
