"""The FORM of the window program's cost fields, read off their jaxprs:
a field over the canvas is never fetched by one element read per
(net, cell) out of a per-net table.  On the chip such a gather cost
about 10 ns an element -- 13.7 ms a wave at 64 nets x 20,240 cells, a
third of a route (PERF.md, PR 27) -- and on a CPU-only check nothing
else would tell if it came back: values and counts are the same."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cost_field_refs import entry_fields_gather, node_cost_field_gather
from mis_colors_refs import mis_colors_searchsorted
from parallel_eda_tpu.route.planes import (_mis_colors, _mis_colors_full,
                                           _mis_colors_short, entry_fields,
                                           live_pick_rungs, mis_short_width,
                                           node_cost_field, sink_pick,
                                           sink_pick_live, sink_pick_wave,
                                           sink_pin_costs)
from sink_pick_refs import sink_pick_flat, sink_pin_costs_flat

# (B, Ko, ncells, N) of the benchmark's three cells
SHAPES = {"route_relaxed": (64, 160, 20240, 29656),
          "route_k6n10_relaxed": (64, 24, 16896, 13560),
          "route_tight": (64, 128, 16192, 25608)}
# (S, K, C, P) of the cells' sink tables: sink slots a net, flat
# candidates a sink, distinct wire cells and pins among them;
# route_fanout's two fanout classes apart
SINK_SHAPES = {"route_relaxed": (8, 400, 80, 10),
               "route_k6n10_relaxed": (7, 1320, 256, 33),
               "route_tight": (8, 320, 64, 10),
               "route_scale": (9, 1716, 352, 33),
               "route_hetero": (13, 1600, 256, 40),
               "route_fanout": (15, 1056, 224, 33),
               "route_fanout.wide": (204, 1056, 224, 33)}
# (B, ncells) of the batches the live sink pick runs on: the six cells'
# and the wide class's at two of its batch widths
LIVE_SHAPES = {**{cell: (B, ncells)
                  for cell, (B, _, ncells, _) in SHAPES.items()},
               "route_scale": (64, 66880),
               "route_hetero": (64, 83200),
               "route_fanout": (64, 47040),
               "route_fanout.wide": (16, 47040),
               "route_fanout.wide32": (32, 47040)}
# (R, Smax, L, N) of the FOUR cells' path stores
MIS_SHAPES = {"route_relaxed": (962, 8, 192, 29656),
              "route_k6n10_relaxed": (1017, 7, 152, 13560),
              "route_tight": (962, 8, 192, 25608),
              "route_scale": (2985, 9, 216, 42008)}


def _eqns(fn, *avals):
    """Every equation of ``fn``'s jaxpr, nested jaxprs included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*avals).jaxpr))


def gather_index_rows(fn, *avals):
    """Index rows (one row = one slice fetched) of every ``gather`` in
    ``fn``'s jaxpr, nested jaxprs included."""
    return [int(np.prod(eqn.invars[1].aval.shape[:-1]))
            for eqn in _eqns(fn, *avals) if eqn.primitive.name == "gather"]


def _entry_avals(B, Ko, ncells, O=4):
    s = jax.ShapeDtypeStruct
    return (s((B, ncells), jnp.bool_), s((B, O), jnp.float32),
            s((B, ncells), jnp.float32), s((B,), jnp.float32),
            s((B,), jnp.bool_), s((B, Ko), jnp.int32),
            s((B, Ko), jnp.int32), s((B, Ko), jnp.float32))


def _node_avals(B, ncells, N):
    s = jax.ShapeDtypeStruct
    return s((B, N + 1), jnp.float32), s((ncells,), jnp.int32)


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_entry_fields_gather_by_the_entries_only(cell):
    B, Ko, ncells, _ = SHAPES[cell]
    rows = gather_index_rows(entry_fields, *_entry_avals(B, Ko, ncells))
    assert rows and max(rows) == B * Ko, rows
    # the guard sees the form it guards against: exactly one
    # canvas-sized gather in the reference
    ref = gather_index_rows(entry_fields_gather,
                            *_entry_avals(B, Ko, ncells))
    assert sorted(ref)[-2:] == [B * Ko, B * ncells], ref


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_node_cost_field_gathers_one_row_a_cell(cell):
    B, _, ncells, N = SHAPES[cell]
    rows = gather_index_rows(node_cost_field, *_node_avals(B, ncells, N))
    assert rows == [ncells], rows
    ref = gather_index_rows(node_cost_field_gather,
                            *_node_avals(B, ncells, N))
    assert ref == [B * ncells], ref


def _sink_avals(cell):
    """((dist, congj_p1, crit_w, cw), factored tables, flat tables)."""
    B, _, ncells, N = SHAPES[cell]
    S, K, C, P = SINK_SHAPES[cell]
    s, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    wave = (s((B, ncells), f32), s((B, N + 1), f32), s((B,), f32),
            s((B,), f32))
    fact = (s((B, S, C), i32), s((B, S, P), i32), s((B, S, P, C), f32),
            s((B, S, P, C), i32))
    flat = (s((B, S, K), i32), s((B, S, K), i32), s((B, S, K), f32))
    return wave, fact, flat


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_sink_pick_gathers_a_distance_per_cell_of_a_sink(cell):
    """Once a wave: B * S * C element reads of the distances (a fifth of
    the candidates), and B * S more for the winner's three fields."""
    B = SHAPES[cell][0]
    S, K, C, P = SINK_SHAPES[cell]
    (dist, _, crit_w, cw), fact, flat = _sink_avals(cell)
    s = jax.ShapeDtypeStruct
    rows = gather_index_rows(sink_pick, dist, s((B, S, P), jnp.float32),
                             crit_w, cw, fact)
    assert sorted(rows) == [B * S] * 3 + [B * S * C], rows
    ref = gather_index_rows(sink_pick_flat, dist,
                            s((B, S, K), jnp.float32), crit_w, cw, flat)
    assert sorted(ref) == [B * S] * 4 + [B * S * K], ref
    assert K >= 5 * C


def _live_avals(cell):
    """(B, S, C, sink_pick_live's avals) of the cell's batch."""
    B, ncells = LIVE_SHAPES[cell]
    S, _, C, P = SINK_SHAPES[cell.replace("wide32", "wide")]
    s, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    return B, S, C, (
        s((B, ncells), f32), s((B, S, P), f32), s((B,), f32), s((B,), f32),
        (s((B, S, C), i32), s((B, S, P), i32), s((B, S, P, C), f32),
         s((B, S, P, C), i32)), s((B, S), jnp.bool_))


@pytest.mark.parametrize("cell", sorted(LIVE_SHAPES))
def test_the_live_sink_pick_gathers_a_distance_per_cell_of_a_live_sink(
        cell):
    """On a rung of width M the wave's pick fetches M rows of each
    table and M * C distances, and nothing of the batch's B * S * C;
    the wave's switch holds one such branch a rung and the dense pick
    once, past the widest."""
    B, S, C, avals = _live_avals(cell)
    rungs = live_pick_rungs(B, S)
    assert len(rungs) == 3 and rungs[-1] * 2 <= B * S + 16
    for M in rungs:
        rows = gather_index_rows(
            lambda *a: sink_pick_live(*a, M), *avals)
        # the distances; then rows of the four tables, the pins' costs
        # and the nets' two weights, and the winner's three fields
        assert sorted(rows) == [M] * 10 + [M * C], (M, rows)
    rows = gather_index_rows(
        lambda *a: sink_pick_wave(*a, rungs), *avals)
    assert sorted(r for r in rows if r > max(rungs)) == sorted(
        [M * C for M in rungs] + [B * S] * 3 + [B * S * C]), rows
    # a mesh's program: the dense pick alone
    rows = gather_index_rows(lambda *a: sink_pick_wave(*a, ()), *avals)
    assert sorted(rows) == [B * S] * 3 + [B * S * C], rows


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_sink_pin_costs_gather_a_cost_per_pin_of_a_sink(cell):
    """Once a step: B * S * P element reads of the node costs."""
    B = SHAPES[cell][0]
    S, K, C, P = SINK_SHAPES[cell]
    (_, congj_p1, _, _), fact, flat = _sink_avals(cell)
    rows = gather_index_rows(sink_pin_costs, congj_p1, fact)
    assert rows == [B * S * P], rows
    ref = gather_index_rows(sink_pin_costs_flat, congj_p1, flat)
    assert ref == [B * S * K], ref


@pytest.mark.parametrize("cell", sorted(MIS_SHAPES))
def test_mis_colors_gather_the_path_store_once(cell):
    """Once a window: with many nodes over, ONE read of R * Smax * L
    slots out of the node-indexed table, and nothing that loops or
    sorts; with few (PR 46), a loop of dense compares an overused node
    that gathers nothing of the store's size, sorts nothing and
    scatters nothing.  The searchsorted form read the store twice in
    the open and thirteen times inside its search's loop: half of
    ``route_scale``'s traced slice on the chip (PERF.md, PR 31)."""
    import types

    R, S, L, N = MIS_SHAPES[cell]
    s = jax.ShapeDtypeStruct
    avals = (s((N,), jnp.int32), s((N,), jnp.int32),
             s((R, S, L), jnp.int32), s((R,), jnp.bool_))

    def form(fn):
        def call(cap, occ, paths, all_reached):
            dev = types.SimpleNamespace(num_nodes=N, capacity=cap)
            return fn(dev, occ, paths, all_reached, min(4096, N), 5)
        return call

    def names(fn):
        return {e.primitive.name for e in _eqns(fn, *avals)}

    new = form(_mis_colors_full)
    assert gather_index_rows(new, *avals) == [R * S * L]
    assert not names(new) & {"while", "scan", "sort"}
    short = form(lambda dev, occ, paths, reached, topk, n_colors:
                 _mis_colors_short(dev, occ, paths, reached,
                                   mis_short_width(topk), n_colors))
    assert max(gather_index_rows(short, *avals), default=0) <= 1
    assert "while" in names(short)
    assert not names(short) & {"sort", "scatter", "scan"}
    # the two under their cond: the store is gathered in ONE branch
    assert [r for r in gather_index_rows(form(_mis_colors), *avals)
            if r > 1] == [R * S * L]
    # the guard sees the form it guards against: three reads of the
    # store, one of them inside the search's loop, and the ids' sort
    ref = form(mis_colors_searchsorted)
    assert gather_index_rows(ref, *avals) == [R * S * L] * 3
    ref_names = names(ref)
    assert "sort" in ref_names and ref_names & {"while", "scan"}


def test_a_whole_step_gathers_no_result_of_candidate_size(monkeypatch):
    """`_step_core` whole (the resident batch step on entry()'s
    problem): nothing is fetched once per flat candidate; with the flat
    forms patched in, the distances a wave and the pins' costs a step
    are."""
    import __graft_entry__ as graft
    from parallel_eda_tpu.obs import get_metrics
    from parallel_eda_tpu.route import planes
    from sink_pick_refs import flat_forms

    fn, args = graft.entry()
    p = graft.planes_step_problem()
    g = get_metrics().values("route.sink_pick.")
    K, C, P = (g["route.sink_pick.cands_per_sink"],
               g["route.sink_pick.cells_per_sink"],
               g["route.sink_pick.pins_per_sink"])
    B, S = p["sel"].shape[0], p["nets"][1].shape[1]
    assert K > C > P > 1
    rows = gather_index_rows(fn, *args)
    assert B * S * K not in rows
    assert rows.count(B * S * C) == 1 and rows.count(B * S * P) == 1
    assert max(rows) < B * S * K

    pin_costs_flat, pick_flat = flat_forms(K, p["dev"].num_nodes)
    monkeypatch.setattr(planes, "sink_pin_costs", pin_costs_flat)
    monkeypatch.setattr(planes, "sink_pick", pick_flat)
    # the flat pick stands in for the dense rung alone
    monkeypatch.setattr(planes, "live_pick_rungs", lambda B, S: ())
    planes.route_batch_resident_planes.clear_cache()
    try:
        ref = gather_index_rows(graft.entry()[0], *args)
    finally:
        planes.route_batch_resident_planes.clear_cache()
    assert ref.count(B * S * K) == 2
