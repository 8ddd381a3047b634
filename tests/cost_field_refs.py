"""The cost fields as `_step_core` built them until PR 27: an
element-wise gather of a whole canvas for every net of a batch, out of
a per-net table.  Kept as the REFERENCE the gather-free builders of
``route/planes.py`` (`entry_fields`, `node_cost_field`) are held to,
bit for bit, alone and inside a whole route (not a test file: imported
by tests/test_planes.py and tests/test_cost_field_forms.py)."""

import jax.numpy as jnp

INF = jnp.inf


def node_cost_field_gather(congj_p1, node_of_cell):
    """One index vector broadcast to every net, then B * ncells
    independent element reads."""
    B = congj_p1.shape[0]
    noc_b = jnp.broadcast_to(node_of_cell[None, :],
                             (B, node_of_cell.shape[0]))
    return jnp.take_along_axis(congj_p1, noc_b, axis=1)


def entry_fields_gather(seed_cells, opin_du, cc_flat, crit_w, valid,
                        ecell, eoidx, edelay):
    """`wenter0` read per CELL: the winning entry's delay gathered out
    of the [B, Ko + 1] table at every cell of every net."""
    B, ncells = seed_cells.shape
    Ko = ecell.shape[1]
    arangeB = jnp.arange(B)
    d_seed = jnp.where(seed_cells, 0.0, INF)
    e_du = jnp.take_along_axis(opin_du, eoidx, axis=1)
    cc_flat_p1 = jnp.concatenate([cc_flat, jnp.full((B, 1), INF)], axis=1)
    e_cc = jnp.take_along_axis(cc_flat_p1, jnp.minimum(ecell, ncells),
                               axis=1)
    e_cost = jnp.where(valid[:, None],
                       e_du + crit_w[:, None] * edelay + e_cc, INF)
    d0 = d_seed.at[arangeB[:, None], ecell].min(e_cost, mode="drop")
    entry_flag = d0 < d_seed
    d0_at_e = jnp.take_along_axis(
        jnp.concatenate([d0, jnp.full((B, 1), INF)], axis=1),
        jnp.minimum(ecell, ncells), axis=1)
    e_won = d0_at_e == e_cost
    wk = jnp.full((B, ncells), Ko, jnp.int32).at[
        arangeB[:, None], ecell].min(
        jnp.where(e_won, jnp.arange(Ko, dtype=jnp.int32)[None, :], Ko),
        mode="drop")
    edelay_p1 = jnp.concatenate([edelay, jnp.zeros((B, 1))], axis=1)
    wenter0 = jnp.where(
        entry_flag,
        jnp.take_along_axis(edelay_p1, jnp.minimum(wk, Ko), axis=1), 0.0)
    return d0, entry_flag, wk, wenter0
