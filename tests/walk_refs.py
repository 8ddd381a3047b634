"""The wave's two element scatters as `_step_core` ran them until PR 42
(`planes.walk_scatters_dense`, which a GSPMD mesh still runs: ONE
scatter-min of all B x G x Kw walk records into the tree buffer and ONE
scatter of all of them into the path rows, whatever the walks ran) as
the REFERENCE `planes.walk_scatters` is held to, bit for bit, alone and
inside a whole route, and the seeded walks both are fed (not a test
file: imported by tests/test_walk_forms.py and tools/walk_forms.py)."""

import numpy as np

from parallel_eda_tpu.route.planes import walk_scatters_dense  # noqa: F401


def seeded_walks(B, G, Kw, ncells, N, lengths, seed, direct=0.0,
                 max_len=None, kept=()):
    """The inputs of one wave's scatters, as `_step_core` hands them
    over, for walks of the given ``lengths`` [B, G] (0 = a direct or an
    invalid pick: no record): (buf, seg, walk_cells, walk_tdel, nodes_w,
    keep, posn, last), numpy; ``last`` the length of the longest walk
    that is kept.  A walk's cells are distinct, two walks of a net may
    share cells (a min decides), a walk's node repeats where a wire
    spans cells (``keep`` drops the repeat) and a share ``direct`` of
    the walks is walked but not ``ok`` (its cells go to the dump
    column, its nodes are not kept: a walk that overran its budget is
    one of these), never one of the (b, g) in ``kept``."""
    rng = np.random.default_rng(seed)
    max_len = Kw + 4 if max_len is None else max_len
    lengths = np.broadcast_to(np.asarray(lengths), (B, G))
    ks = np.arange(Kw)[None, None, :]
    ran = ks < lengths[:, :, None]
    cells = np.argsort(rng.random((B, G, max(Kw, min(ncells, 4 * Kw)))),
                       axis=2)
    cells = (cells[:, :, :Kw] + rng.integers(0, ncells, (B, G, 1))) % ncells
    cells_w = np.where(ran, cells, ncells).astype(np.int32)
    # a node a run of one to three cells
    nodes = np.cumsum(rng.random((B, G, Kw)) < 0.6, axis=2) \
        + rng.integers(0, max(1, N - Kw - 1), (B, G, 1))
    nodes_w = np.where(ran, nodes % N, N).astype(np.int32)
    ok = rng.random((B, G)) >= direct
    for at in kept:
        ok[at] = True
    dup = np.concatenate([np.zeros((B, G, 1), bool),
                          nodes_w[:, :, 1:] == nodes_w[:, :, :-1]], axis=2)
    keep = ~dup & (nodes_w < N) & ok[:, :, None]
    posn = (np.cumsum(keep, axis=2) - 1).astype(np.int32)
    walk_cells = np.where(ok[:, :, None], cells_w, ncells).astype(np.int32)
    walk_tdel = rng.uniform(1e-10, 1e-8, (B, G, Kw)).astype(np.float32)
    buf = np.full((B, ncells + 1), np.inf, np.float32)
    seg = np.full((B, G, max_len), N, np.int32)
    seg[:, :, :2] = rng.integers(0, N, (B, G, 2))
    return (buf, seg, walk_cells, walk_tdel, nodes_w, keep, posn,
            np.int32(np.where(ok, lengths, 0).max()))


def dense_scatters():
    """Inside: every program traced scatters ALL of a wave's walk slots
    (``planes.walk_scatters`` is ``planes.walk_scatters_dense``)."""
    from sink_pick_refs import patched_planes

    return patched_planes("walk_scatters", walk_scatters_dense)
