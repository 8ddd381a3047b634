"""The ONE window program and the ONE driver of it.

``planes.route_window_planes`` is the jitted window program itself (no
wrapper, no fused or multi-job sibling): its name, argument order,
statics and donations are what every dispatch-variant key, the AOT
library's static split and the trace fixtures rest on, so they are
pinned here.  Its 23 results have names (``planes.WindowOut``), its
packed ``status`` word unpacks to the fields it also returns one by
one, and ``SCAL_*`` index ``scal`` as the class says.  ``Router.route``
on the planes path is a plain call: no generator, no ``yield`` in
``route/router.py`` outside the ``dispatching`` context manager.
"""

import ast
import inspect
import os

import numpy as np
import pytest

from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.route import Router, RouterOpts, planes
from parallel_eda_tpu.route import router as router_mod

FIELDS = ("occ", "acc", "paths", "sink_delay", "all_reached", "bb",
          "pres", "rrm", "colors", "n_over", "over_total", "nroutes",
          "nexec", "crit_all", "dmax_hist", "max_span", "dev_wide",
          "live_wh", "unreached", "steps_exec", "steps_useful", "status",
          "scal")
# the six the next dispatch is handed, and the seventh it donates
STATE = FIELDS[:6] + ("crit_all",)
PARAMS = (
    "pg", "dev", "occ", "acc", "paths", "sink_delay", "all_reached", "bb",
    "source_all", "sinks_all", "crit_all", "opin_node_all",
    "entry_cell_all", "entry_oidx_all", "entry_delay_all", "sink_uid_all",
    "uid_ucell", "uid_upin", "uid_pcdel", "uid_pcrank", "direct_oidx_all",
    "direct_ipin_all", "direct_delay_all", "sel_plan", "valid_plan",
    "full_bb", "pres0", "pres_mult", "max_pres", "acc_fac", "it0",
    "force_until", "K_iters", "nsweeps", "max_len", "num_waves", "group",
    "doubling", "topk", "n_colors", "mesh", "tdev", "req_seed",
    "sta_depth", "crit_exp", "max_crit", "use_sdc", "crop_tile",
    "bb0_all", "widen_ok", "plane_dtype", "fan", "fclass",
    # an ARRAY argument (PR 46): whether the host reads this rung's colours
    "colours_read")
STATICS = ("K_iters", "nsweeps", "max_len", "num_waves", "group",
           "doubling", "topk", "n_colors", "mesh", "sta_depth",
           "crit_exp", "max_crit", "use_sdc", "crop_tile", "plane_dtype",
           "fclass")


@pytest.fixture(scope="module")
def windows():
    """Every window of one tiny route, as the program returned it: the
    summary fields on the host (the state is donated on)."""
    f = synth_flow(num_luts=15, num_inputs=6, num_outputs=6,
                   chan_width=10, seed=3)
    real = planes.route_window_planes
    outs = []

    def spy(*a, **k):
        out = real(*a, **k)
        outs.append((type(out), out._fields, {
            n: np.asarray(getattr(out, n)) for n in FIELDS
            if n not in STATE}, len(out)))
        return out

    planes.route_window_planes = spy
    try:
        res = Router(f.rr, RouterOpts(batch_size=32)).route(f.term)
    finally:
        planes.route_window_planes = real
    assert res.success and outs
    return res, outs


def test_the_result_is_the_named_tuple_in_the_documented_order(windows):
    assert planes.WindowOut._fields == FIELDS
    for cls, fields, host, n in windows[1]:
        assert cls is planes.WindowOut and fields == FIELDS and n == 23
        assert issubclass(cls, tuple)
        assert host["scal"].shape == (planes.SCAL_LEN,)
        assert host["status"].shape == host["rrm"].shape


def test_the_packed_status_unpacks_to_the_fields_returned_beside_it(
        windows):
    res, outs = windows
    for _, _, h, _ in outs:
        rrm, colors, dev_wide, unreached, live_w, live_h = \
            planes.unpack_window_status(h["status"])
        assert np.array_equal(rrm, h["rrm"])
        assert np.array_equal(colors, h["colors"])
        assert np.array_equal(dev_wide, h["dev_wide"])
        assert np.array_equal(unreached, h["unreached"])
        wh = h["live_wh"].astype(np.int64)
        assert np.array_equal(live_w, (wh >> 8) * 8)
        assert np.array_equal(live_h, (wh & 0xFF) * 8)
    # the last window of a route that landed left nothing to re-route
    assert not outs[-1][2]["rrm"].any()


def test_scal_is_indexed_as_documented(windows):
    res, outs = windows
    names = {"n_over": planes.SCAL_N_OVER,
             "over_total": planes.SCAL_OVER_TOTAL,
             "nroutes": planes.SCAL_NROUTES, "nexec": planes.SCAL_NEXEC,
             "max_span": planes.SCAL_MAX_SPAN,
             "steps_exec": planes.SCAL_S_EXEC,
             "steps_useful": planes.SCAL_S_USEFUL}
    assert sorted(names.values()) == list(range(7))
    # five scalars, the step's ledger, the colouring's form
    assert planes.SCAL_LEN == 5 + planes.STEP_LEDGER_LEN + 1
    assert planes.SCAL_MIS_FORM == planes.SCAL_LEN - 1
    for _, _, h, _ in outs:
        # one rung a window here: every colouring was read
        assert h["scal"][planes.SCAL_MIS_FORM] in (planes.MIS_SHORT,
                                                    planes.MIS_FULL)
        for n, i in names.items():
            assert int(h["scal"][i]) == int(h[n]), n
        assert h["scal"][planes.SCAL_WALK_STEPS] <= \
            h["scal"][planes.SCAL_WALK_BUDGET]
        assert h["scal"][planes.SCAL_SINK_ROWS] <= \
            h["scal"][planes.SCAL_SINK_ROWS_DENSE]
    # the route's totals are the windows' ledgers added up
    scal = np.stack([h["scal"] for _, _, h, _ in outs])
    assert res.total_relax_steps == scal[:, planes.SCAL_S_EXEC].sum()
    assert res.total_waves == scal[:, planes.SCAL_WAVES].sum()


def _jitted(mod):
    return {n: v for n, v in vars(mod).items()
            if hasattr(v, "lower") and hasattr(v, "clear_cache")}


def test_planes_holds_two_jitted_entry_points_over_the_step():
    """The window program and the resident batch step
    (``__graft_entry__.py``): what a test that swaps a form into
    ``_step_core`` has to drop the caches of."""
    over_step = sorted(
        n for n, fn in _jitted(planes).items()
        if "_step_core(" in inspect.getsource(fn.__wrapped__))
    assert over_step == ["route_batch_resident_planes",
                         "route_window_planes"]
    fn = planes.route_window_planes
    assert fn.__name__ == "route_window_planes"
    assert tuple(inspect.signature(fn.__wrapped__).parameters) == PARAMS
    assert planes.WINDOW_STATIC_ARGNAMES == STATICS
    assert tuple(fn._jit_info.static_argnames) == STATICS
    assert tuple(fn._jit_info.donate_argnames) == STATE


def test_the_librarys_static_split_is_the_window_programs():
    from parallel_eda_tpu.serve import library as lib

    fn = planes.route_window_planes
    assert lib._statics() == set(STATICS)
    npos = PARAMS.index("force_until") + 1
    args = tuple("a:" + n for n in PARAMS[:npos])
    kwargs = {n: "k:" + n for n in PARAMS[npos:]}
    dyn_args, dyn_kwargs = lib._split_dynamic(fn, args, kwargs)
    assert dyn_args == args
    assert sorted(set(kwargs) - set(dyn_kwargs)) == sorted(STATICS)
    # passed by position the statics are dropped by name all the same
    dyn_args, dyn_kwargs = lib._split_dynamic(
        fn, tuple("a:" + n for n in PARAMS), {})
    assert dyn_args == tuple("a:" + n for n in PARAMS
                             if n not in STATICS) and not dyn_kwargs


def test_the_window_loop_is_a_plain_method():
    for fn in (Router.route, Router._route_planes_windows):
        assert not inspect.isgeneratorfunction(fn)
    assert not hasattr(Router, "route_gen")
    tree = ast.parse(open(os.path.join(
        os.path.dirname(router_mod.__file__), "router.py")).read())
    yields = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(isinstance(n, (ast.Yield, ast.YieldFrom))
                   for n in ast.walk(node)):
                yields.add(node.name)
    assert yields == {"dispatching"}


def test_a_rung_nobody_reads_skips_the_colouring_and_nothing_else():
    """``colours_read`` False (a rung that is not its window's last):
    rrm, colors, their bits of ``status`` and max_span come back zero,
    ``scal`` says MIS_SKIPPED, and every other result is the result of
    the same call with the flag True -- by ONE traced program."""
    import jax.numpy as jnp

    import __graft_entry__ as graft

    fn = planes.route_window_planes

    def call(read):
        p = graft.planes_step_problem()
        occ, acc, paths, sink_delay, all_reached, bb = p["state"]
        # route the plan's first net alone and call another unreached:
        # the colouring has a net to mark
        valid = p["valid"].at[1:].set(False)
        all_reached = all_reached.at[p["sel"][1]].set(False)
        out = fn(
            p["pg"], p["dev"], occ, acc, paths, sink_delay, all_reached,
            bb, *p["nets"], p["sel"][None], valid[None],
            p["full_bb"], jnp.float32(0.5), jnp.float32(1.0),
            jnp.float32(1.0), jnp.float32(0.0), jnp.int32(0), jnp.int32(1),
            1, p["nsweeps"], p["max_len"], p["num_waves"], p["group"],
            True, topk=64, colours_read=jnp.bool_(read))
        return {n: np.asarray(getattr(out, n)) for n in FIELDS}

    size0 = fn._cache_size()
    read, skipped = call(True), call(False)
    assert fn._cache_size() == size0 + 1
    assert read["rrm"].any() and read["max_span"] > 0
    assert read["scal"][planes.SCAL_MIS_FORM] == planes.MIS_SHORT
    assert skipped["scal"][planes.SCAL_MIS_FORM] == planes.MIS_SKIPPED
    for n in ("rrm", "colors", "max_span"):
        assert not skipped[n].any(), n
    assert skipped["scal"][planes.SCAL_MAX_SPAN] == 0
    rrm, colors, *rest = planes.unpack_window_status(skipped["status"])
    assert not rrm.any() and not colors.any()
    for a, b in zip(rest, planes.unpack_window_status(read["status"])[2:]):
        assert np.array_equal(a, b)
    own = [n for n in FIELDS
           if n not in ("rrm", "colors", "max_span", "status", "scal")]
    for n in own:
        assert np.array_equal(read[n], skipped[n], equal_nan=True), n
    keep = [i for i in range(planes.SCAL_LEN)
            if i not in (planes.SCAL_MAX_SPAN, planes.SCAL_MIS_FORM)]
    assert np.array_equal(read["scal"][keep], skipped["scal"][keep])
