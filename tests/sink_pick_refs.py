"""The sink pick as `_step_core` ran it until PR 29: every (wire cell ->
IPIN -> SINK) hop of a sink as one FLAT candidate (cell, ipin, delay),
K a sink, its distance and its pin's cost each fetched by an element
read per candidate -- B * S * K reads out of per-net tables, a wave and
a step -- and the winner taken by ``argmin`` (equal costs -> lowest k).
Kept as the REFERENCE the factored tables and pick of ``route/planes.py``
(`build_planes_terminals`, `sink_pin_costs`, `sink_pick`) are held to,
bit for bit, alone and inside a whole route (not a test file: imported
by tests/test_planes.py and tests/test_cost_field_forms.py)."""

import contextlib

import jax.numpy as jnp
import numpy as np

from parallel_eda_tpu.route.planes import _ragged_flat, _within

INF = jnp.inf


def flat_sink_tables(rr, sinks, cell_of_node, ncells):
    """The flat host tables: (sink_uid [R, S], uid_cell, uid_ipin,
    uid_delay [U + 1, K]; pads ncells / N / 0.0), candidates pin-major:
    the sink's IPINs in in-edge order, then each IPIN's in-edges."""
    R, S = sinks.shape
    N = rr.num_nodes
    irp, isrc, idel = rr.in_row_ptr, rr.in_src, rr.in_delay
    sk_flat = sinks.reshape(-1).astype(np.int64)
    valid = sk_flat >= 0
    uniq, inv = np.unique(sk_flat[valid], return_inverse=True)
    U = len(uniq)
    f1, u_of_1 = _ragged_flat(irp, uniq)
    ipins = isrc[f1].astype(np.int64)
    w1 = idel[f1].astype(np.float64)
    f2, p_of_2 = _ragged_flat(irp, ipins)
    wires2 = isrc[f2].astype(np.int64)
    wtot = (w1[p_of_2] + idel[f2]).astype(np.float32)
    u_of_2 = u_of_1[p_of_2]
    k2, cand_cnt = _within(u_of_2, U)
    K = max(1, int(cand_cnt.max()) if U else 1)
    u_cell = np.full((U + 1, K), ncells, dtype=np.int32)
    u_ipin = np.full((U + 1, K), N, dtype=np.int32)
    u_del = np.zeros((U + 1, K), dtype=np.float32)
    u_cell[u_of_2, k2] = cell_of_node[wires2]
    u_ipin[u_of_2, k2] = ipins[p_of_2]
    u_del[u_of_2, k2] = wtot
    sink_uid = np.full(R * S, U, dtype=np.int32)
    sink_uid[valid] = inv.astype(np.int32)
    return sink_uid.reshape(R, S), u_cell, u_ipin, u_del


def sink_pin_costs_flat(congj_p1, flat_tabs):
    """The pin's node cost of every candidate, [B, S, K]."""
    b_sipin = flat_tabs[1]
    B, S, K = b_sipin.shape
    return jnp.take_along_axis(
        congj_p1, b_sipin.reshape(B, -1), axis=1).reshape(B, S, K)


def sink_pick_flat(dist, ipin_congj, crit_w, cw, flat_tabs):
    """(sink_dist, ent_cell, ent_ipin, ent_wdel), each [B, S], from the
    batch's flat tables (b_scell, b_sipin, b_swdel), each [B, S, K]."""
    b_scell, b_sipin, b_swdel = flat_tabs
    B, S, K = b_scell.shape
    dist_p1 = jnp.concatenate([dist, jnp.full((B, 1), INF)], axis=1)
    cand = (jnp.take_along_axis(
        dist_p1, b_scell.reshape(B, -1), axis=1).reshape(B, S, K)
        + crit_w[:, None, None] * b_swdel
        + cw[:, None, None] * ipin_congj)
    kstar = jnp.argmin(cand, axis=2)[:, :, None]

    def at_kstar(a):
        return jnp.take_along_axis(a, kstar, axis=2)[:, :, 0]

    return (at_kstar(cand), at_kstar(b_scell), at_kstar(b_sipin),
            at_kstar(b_swdel))


def flat_tabs_of(sink_tabs, K, ncells, N):
    """A batch's factored tables (planes.sink_pick's ``sink_tabs``) laid
    back out as the flat ones: the hop of rank k at position k, the
    flat pads (ncells / N / 0.0) elsewhere."""
    b_ucell, b_upin, b_pcdel, b_pcrank = sink_tabs
    B, S, P, C = b_pcrank.shape
    bi = jnp.arange(B)[:, None, None, None]
    si = jnp.arange(S)[None, :, None, None]

    def lay(vals, pad):
        return jnp.full((B, S, K), pad, vals.dtype).at[
            bi, si, b_pcrank].set(
            jnp.broadcast_to(vals, (B, S, P, C)), mode="drop")

    return (lay(b_ucell[:, :, None, :], ncells),
            lay(b_upin[:, :, :, None], N), lay(b_pcdel, 0.0))


def flat_forms(K, N):
    """(sink_pin_costs, sink_pick) under planes' own signatures that
    compute by the flat forms: what a whole route is patched with."""
    def sink_pin_costs(congj_p1, sink_tabs):
        return sink_pin_costs_flat(
            congj_p1, flat_tabs_of(sink_tabs, K, 0, N))

    def sink_pick(dist, ipin_congj, crit_w, cw, sink_tabs):
        return sink_pick_flat(
            dist, ipin_congj, crit_w, cw,
            flat_tabs_of(sink_tabs, K, dist.shape[1], N))

    return sink_pin_costs, sink_pick


# ---- the live pick's ladder forced to the dense rung (ISSUE 38) ----

@contextlib.contextmanager
def patched_planes(name, value):
    """Inside: ``planes.<name>`` is ``value`` in every program traced.
    The jitted window programs are dropped on the way in and out (they
    hold what they traced)."""
    from parallel_eda_tpu.route import planes

    def drop_programs():
        for prog in (planes.route_window_planes,
                     planes.route_batch_resident_planes):
            prog.clear_cache()

    built = getattr(planes, name)
    drop_programs()
    setattr(planes, name, value)
    try:
        yield
    finally:
        setattr(planes, name, built)
        drop_programs()


def dense_ladder():
    """Inside: ``planes.live_pick_rungs`` gives the empty ladder, so
    every wave of every program traced takes the DENSE sink_pick."""
    return patched_planes("live_pick_rungs", lambda B, S: ())


# fields of a RouteResult / a stats row that a clock, a process-wide id
# or the pick's own ledger moves
_RESULT_SKIP = {"paths", "sink_delay", "occ", "stats", "wall", "route_id",
                "checkpoint", "total_sink_reads", "total_sink_reads_dense"}
_ROW_SKIP = {"route_time_s", "stall_s", "plan_s", "dispatch_ms",
             "control_s", "sink_reads", "sink_reads_dense"}


def assert_same_route(res, ref, but=()):
    """``res`` and ``ref`` (RouteResults) are one route: paths, sink
    delays and occupancy node for node, every counter (less the ones
    named in ``but``) and every field of every window row but the
    clocks and the pick's own two."""
    import dataclasses

    def same(a, b):
        if isinstance(a, (tuple, list)):
            return len(a) == len(b) and all(map(same, a, b))
        return np.array_equal(np.asarray(a), np.asarray(b),
                              equal_nan=np.asarray(a).dtype.kind == "f")

    for name in ("paths", "sink_delay", "occ"):
        assert same(getattr(res, name), getattr(ref, name)), name
    for f in dataclasses.fields(res):
        if f.name not in _RESULT_SKIP and f.name not in but:
            assert getattr(res, f.name) == getattr(ref, f.name), f.name
    assert len(res.stats) == len(ref.stats)
    for row, row_ref in zip(res.stats, ref.stats):
        for f in dataclasses.fields(row):
            if f.name not in _ROW_SKIP:
                a, b = getattr(row, f.name), getattr(row_ref, f.name)
                assert a == b or (a != a and b != b), (row.window, f.name)
