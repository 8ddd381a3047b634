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
from parallel_eda_tpu.route.planes import entry_fields, node_cost_field

# (B, Ko, ncells, N) of the benchmark's three cells
SHAPES = {"route_relaxed": (64, 160, 20240, 29656),
          "route_k6n10_relaxed": (64, 24, 16896, 13560),
          "route_tight": (64, 128, 16192, 25608)}


def gather_index_rows(fn, *avals):
    """Index rows (one row = one slice fetched) of every ``gather`` in
    ``fn``'s jaxpr, nested jaxprs included."""
    rows = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                idx = eqn.invars[1].aval.shape
                rows.append(int(np.prod(idx[:-1])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*avals).jaxpr)
    return rows


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
