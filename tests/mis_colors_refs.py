"""`_mis_colors` as the window program ran it until PR 31: a path slot's
column of the conflict matrix found by a binary search of the WHOLE
path store against the sorted ids of the top-K overused nodes
(``searchsorted``: log2(topk) + 1 dependent gather rounds of
R * Smax * L elements), a second gather of the store to see whether the
search hit, and a third out of the overuse flags for ``rrm`` -- fifteen
rounds to learn one small integer that depends only on the node in the
slot.  Kept as the REFERENCE the node-indexed table of
``route/planes.py`` ``_mis_colors`` is held to, bit for bit, alone and
inside a whole route (not a test file: imported by tests/test_planes.py
and tests/test_cost_field_forms.py)."""

import jax.numpy as jnp
from jax import lax


def mis_colors_searchsorted(dev, occ, paths, all_reached, topk: int,
                            n_colors: int):
    """(rrm [R], colors [R]); U's columns in id order."""
    N = dev.num_nodes
    R = paths.shape[0]
    over = jnp.maximum(occ - dev.capacity, 0)
    over_p1 = jnp.append(over > 0, False)
    rrm = over_p1[paths].any(axis=(1, 2)) | ~all_reached
    val, ids = lax.top_k(over, topk)
    ids = jnp.where(val > 0, ids, N)
    ids_sorted = jnp.sort(ids)
    flat = paths.reshape(R, -1)
    pos = jnp.clip(jnp.searchsorted(ids_sorted, flat), 0, topk - 1)
    hit = (ids_sorted[pos] == flat) & (flat < N)
    U = jnp.zeros((R, topk + 1), bool).at[
        jnp.arange(R)[:, None], jnp.where(hit, pos, topk)].set(
        True)[:, :topk]
    U = U & rrm[:, None]
    prio = jnp.arange(R, dtype=jnp.int32)
    color = jnp.full(R, n_colors - 1, jnp.int32)
    uncol = rrm
    for c in range(n_colors - 1):
        Uc = U & uncol[:, None]
        claim = jnp.min(jnp.where(Uc, prio[:, None], R), axis=0)
        conflict = (Uc & (claim[None, :] != prio[:, None])).any(axis=1)
        joins = uncol & ~conflict
        color = jnp.where(joins, c, color)
        uncol = uncol & ~joins
    return rrm, color
