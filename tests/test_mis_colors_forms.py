"""The conflict colouring's two forms and its skip (ISSUE 46):
``planes._mis_colors_short`` (dense compares of the path store against
the short list of overused nodes) against ``planes._mis_colors_full``
(the node-indexed table, the gather, the scatter) and
``tests/mis_colors_refs.py``'s searchsorted form, bit for bit in rrm and
colors, alone and under ``planes._mis_colors``'s ``lax.cond``; a rung
whose colours nobody reads; ``tools/mis_colors_forms.py`` on a tiny
shape."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mis_colors_refs import mis_colors_searchsorted
from parallel_eda_tpu.route import planes

# few nodes under many slots: an overused node lies on several nets
N, TOPK, KS, N_COLORS = 200, 32, 16, 5
CLASSES = {"one_class": [(40, 3, 12)],
           "fanout_classes": [(40, 3, 12), (4, 9, 12)]}
# overused nodes: none (two nets still miss a sink), one, around the
# short list's width, and past topk, where the dump column fills
N_OVER = {"nothing_over_a_sink_unreached": 0, "one": 1,
          "width_less_one": KS - 1, "width": KS, "width_plus_one": KS + 1,
          "over_topk": TOPK + 8}


def _tool():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / \
        "mis_colors_forms.py"
    spec = importlib.util.spec_from_file_location("mis_colors_forms", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def _dense(stores):
    """The class stores as ONE [R, Smax, Lmax] store, pads the sentinel."""
    S, L = (max(s.shape[i] for s in stores) for i in (1, 2))
    return jnp.concatenate([
        jnp.pad(s, ((0, 0), (0, S - s.shape[1]), (0, L - s.shape[2])),
                constant_values=N) for s in stores])


@pytest.mark.parametrize("over", sorted(N_OVER))
@pytest.mark.parametrize("classes", sorted(CLASSES))
def test_the_short_form_colours_as_the_full_form_does(classes, over,
                                                      monkeypatch):
    """short == full == searchsorted wherever the short form engages
    (at most ``MIS_SHORT_K`` nodes over), `_mis_colors` == full ==
    searchsorted everywhere, `window_colours` says which form ran, and a
    rung nobody reads returns zeros."""
    tool = _tool()
    monkeypatch.setattr(planes, "MIS_SHORT_K", KS)
    n_over = N_OVER[over]
    paths, fan, reached = tool.seeded_store(N, CLASSES[classes], seed=3)
    occ = tool.seeded_occ(N, paths, n_over, seed=3)
    dev = types.SimpleNamespace(num_nodes=N,
                                capacity=jnp.ones(N, jnp.int32))
    assert int((occ > dev.capacity).sum()) == n_over
    assert not bool(reached.all())

    def run(fn, *a, **kw):
        out = jax.jit(lambda occ, paths, reached: fn(
            dev, occ, paths, reached, *a, **kw))(occ, paths, reached)
        return [np.asarray(x) for x in out]

    fan_kw = {} if fan is None else {"fan": fan}
    want = run(mis_colors_searchsorted, TOPK, N_COLORS) if fan is None \
        else [np.asarray(x) for x in mis_colors_searchsorted(
            dev, occ, _dense(paths), reached, TOPK, N_COLORS)]
    full = run(planes._mis_colors_full, TOPK, N_COLORS, **fan_kw)
    auto = run(planes._mis_colors, TOPK, N_COLORS, **fan_kw)
    read = run(planes.window_colours, TOPK, N_COLORS, jnp.bool_(True), fan)
    forms = [full, auto, read[:2]]
    short_due = n_over <= KS
    if short_due:
        forms.append(run(planes._mis_colors_short, KS, N_COLORS, **fan_kw))
    for got in forms:
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
    assert int(read[2]) == (planes.MIS_SHORT if short_due
                            else planes.MIS_FULL)
    rrm, colors = want
    assert rrm[~np.asarray(reached)].all()
    assert (colors[~rrm] == N_COLORS - 1).all()
    if n_over:
        # nets fight: more than one colour among the dirty
        assert rrm.sum() > (~np.asarray(reached)).sum()
        assert len(set(colors[rrm].tolist())) > (1 if n_over > 1 else 0)
    else:
        assert np.array_equal(rrm, ~np.asarray(reached))

    skipped = run(planes.window_colours, TOPK, N_COLORS, jnp.bool_(False),
                  fan)
    assert skipped[0].dtype == bool and skipped[1].dtype == np.int32
    assert not skipped[0].any() and not skipped[1].any()
    assert skipped[0].shape == skipped[1].shape == rrm.shape
    assert int(skipped[2]) == planes.MIS_SKIPPED


def test_the_short_width_is_one_constant_under_topk():
    """One module constant, never past topk: beyond topk the full form
    drops nodes into its dump column."""
    assert planes.MIS_SHORT_K in (64, 128, 256)
    assert planes.mis_short_width(4096) == planes.MIS_SHORT_K
    assert planes.mis_short_width(32) == 32
    assert (planes.MIS_SKIPPED, planes.MIS_SHORT, planes.MIS_FULL) == \
        (0, 1, 2)
    assert planes.SCAL_MIS_FORM == planes.SCAL_LEN - 1


def test_the_mis_colors_forms_tool_on_a_tiny_shape(capsys, monkeypatch):
    """tools/mis_colors_forms.py: off the TPU it exits 2 before it times
    a form; its shapes are the eight cells' (and the largest store once
    grown); rehearsed on tiny shapes its row holds every form's times
    and layouts and the forms agree.  No time of it means anything
    here."""
    import json
    import pathlib

    tool = _tool()
    assert tool.main(["--shapes", "route_tight", "--reps", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "not a TPU" in err
    manifest = json.loads((pathlib.Path(tool.REPO) / "BENCHMARK.json")
                          .read_text())
    assert {s.split(".")[0] for s in tool.SHAPES} == {
        w["name"] for w in manifest["workloads"]}
    monkeypatch.setattr(tool, "TOPK", 128)
    monkeypatch.setattr(planes, "MIS_SHORT_K", 64)
    n_overs = [0, 8, 64, 100, 200]
    for name, classes in CLASSES.items():
        monkeypatch.setitem(tool.SHAPES, name, (3000, classes))
        forms = ("full", "short64", "chunk8", "auto", "skip")
        row, agree = tool.run_shape(name, forms, n_overs, reps=2, seed=1)
        assert agree
        assert row["device"] == "cpu" and row["topk"] == 128
        assert row["slots"] == sum(R * S * L for R, S, L in classes)
        for form in forms:
            took = row[f"{form}.us"]
            width = tool.width_of(form)
            assert set(took) == {str(n) for n in n_overs
                                 if width is None or n <= width}
            assert all(v > 0 for v in took.values())
            assert row["store_layouts"][form]
