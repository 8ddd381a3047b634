"""In the tail of a full rebuild the window program re-packs the nets
that still need a re-route into dense groups (``planes.repack_plan``): a
pure function of the plan, its segments and the live mask, tested alone
against a loop that says what the groups should be; and then through
``tests/test_endgame.py``'s circuit, where it is what shortens the
finishing pass's batches and leaves every other window the parent's."""

import jax
import numpy as np
import pytest

from parallel_eda_tpu.route import Router, RouterOpts
from parallel_eda_tpu.route.planes import repack_plan
from parallel_eda_tpu.route.router import _order_and_chunk


# the same route at the parent commit (d79fe76), where a group ran whole
# as soon as one of its nets was dirty: windows of the kinds first,
# negotiate x 3, finish, relegalise
PARENT_BATCHES = [6, 10, 15, 5, 7, 1]


def _plan(chunks, G, B):
    """A host-style plan: ``chunks`` is a list of (segment, net ids), one
    a group, the segments in runs; pad groups and pad slots are 0."""
    sel = np.zeros((G, B), np.int32)
    seg = np.zeros((G, B), np.int8)
    for g, (s, nets) in enumerate(chunks):
        sel[g, :len(nets)] = nets
        seg[g, :len(nets)] = s
    return sel, seg


def _by_the_loop(sel, seg, live):
    """What the groups are: segment by segment (a run of groups with one
    id), the live slots in row-major order, B at a time, from the
    segment's first group on; the rest of its groups empty."""
    G, B = sel.shape
    seg_g = seg.max(axis=1)
    out = [[] for _ in range(G)]
    g = 0
    while g < G:
        g1 = g
        while g1 < G and seg_g[g1] == seg_g[g]:
            g1 += 1
        nets = [int(sel[i, b]) for i in range(g, g1) for b in range(B)
                if live[i, b]]
        for k, lo in enumerate(range(0, len(nets), B)):
            out[g + k] = nets[lo:lo + B]
        g = g1
    return out


def _random_case(seed, G=16, B=8):
    """Three segments of full chunks with a short last one each, as
    _plan_groups builds them, then pad groups; a random share live."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 4 * B, size=3)
    nets = 1 + rng.permutation(int(sizes.sum())).astype(np.int32)
    chunks, lo = [], 0
    for s, n in enumerate(sizes):
        mine = nets[lo:lo + n]
        lo += n
        chunks += [(s + 1, mine[i:i + B]) for i in range(0, n, B)]
    sel, seg = _plan(chunks, G, B)
    live = (seg > 0) & (rng.random((G, B)) < rng.choice([0.1, 0.5, 0.9]))
    return sel, seg, live


_repack = jax.jit(repack_plan)


def _groups(sel, seg, live):
    """The re-packed plan as lists of nets a group, empty groups kept."""
    sel_o, valid_o = (np.asarray(a) for a in _repack(
        sel, seg.astype(np.int32), live))
    assert sel_o.shape == valid_o.shape == sel.shape
    assert valid_o.dtype == bool
    out = []
    for row, mask in zip(sel_o, valid_o):
        n = int(mask.sum())
        # a group fills from slot 0 and leaves 0 on its empty slots
        assert mask[:n].all() and not row[n:].any()
        out.append(row[:n].tolist())
    return out


@pytest.mark.parametrize("seed", range(12))
def test_live_slots_land_in_their_segments_leading_groups_in_plan_order(
        seed):
    sel, seg, live = _random_case(seed)
    got = _groups(sel, seg, live)
    assert got == _by_the_loop(sel, seg, live)
    assert sorted(n for b in got for n in b) == sorted(sel[live])
    # full but for the last of a segment
    seg_g = seg.max(axis=1)
    for s in np.unique(seg_g[seg_g > 0]):
        mine = [got[g] for g in np.flatnonzero(seg_g == s) if got[g]]
        assert all(len(b) == sel.shape[1] for b in mine[:-1])


@pytest.mark.parametrize("seed", range(6))
def test_no_group_holds_nets_of_two_segments(seed):
    sel, seg, live = _random_case(seed)
    home = {int(n): int(s) for n, s in zip(sel[seg > 0], seg[seg > 0])}
    seg_g = seg.max(axis=1)
    for g, b in enumerate(_groups(sel, seg, live)):
        assert {home[n] for n in b} <= {int(seg_g[g])}


@pytest.mark.parametrize("seed", range(4))
def test_with_every_valid_slot_live_the_plan_comes_back(seed):
    """The identity that makes a forced iteration the parent's."""
    sel, seg, _ = _random_case(seed)
    sel_o, valid_o = _repack(sel, seg.astype(np.int32), seg > 0)
    assert np.array_equal(np.asarray(valid_o), seg > 0)
    assert np.array_equal(np.asarray(sel_o), sel)


def test_the_routers_own_plans_come_back_as_they_went_in():
    """_plan_groups' plans at both widths (B_g = B, and narrowed to the
    largest chunk), colours and pad groups included."""
    from parallel_eda_tpu.flow import synth_flow

    f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8, chan_width=9,
                   seed=3)
    router = Router(f.rr, RouterOpts(batch_size=8))
    R = len(f.term.num_sinks)
    cx = (f.term.bb_xmin + f.term.bb_xmax) / 2.0
    cy = (f.term.bb_ymin + f.term.bb_ymax) / 2.0
    colors = np.arange(R) % 3
    for dirty in (np.arange(R), np.arange(0, R, 4)):
        sel, seg = router._plan_groups(dirty, colors, f.term.num_sinks, cx,
                                       cy, 8, R)
        assert seg.dtype == np.int8 and sel.shape == seg.shape
        assert sorted(sel[seg > 0]) == sorted(dirty)
        for c in range(3):
            assert set(seg[seg > 0][colors[sel[seg > 0]] == c]) == {c + 1}
        # a colour's chunks are _order_and_chunk's, in its order
        mine = dirty[colors[dirty] == 1]
        chunks = _order_and_chunk(mine, f.term.num_sinks, cx, cy, 8)
        assert list(sel[seg == 2]) == list(np.concatenate(chunks))
        sel_o, valid_o = _repack(sel, seg.astype(np.int32), seg > 0)
        assert np.array_equal(np.asarray(sel_o), sel)
        assert np.array_equal(np.asarray(valid_o), seg > 0)


def test_the_same_input_twice_gives_the_same_plan():
    sel, seg, live = _random_case(3)
    assert _groups(sel, seg, live) == _groups(
        sel.copy(), seg.copy(), live.copy())


def test_a_segment_with_no_live_slot_runs_no_group():
    sel, seg = _plan([(1, [5, 6, 7, 8]), (1, [9]), (2, [1, 2, 3, 4]),
                      (2, [10, 11]), (3, [12])], 8, 4)
    live = seg == 2
    live[2, 1] = False                       # net 2 is clean
    assert _groups(sel, seg, live) == [
        [], [], [1, 3, 4, 10], [11], [], [], [], []]
    # nothing live at all: every group comes back empty
    assert _groups(sel, seg, np.zeros_like(live)) == [[]] * 8


def test_a_bool_plan_is_one_segment():
    """What a caller that hands the window program a bool valid plan
    gets (``valid_plan.astype(int32)``): one segment over the groups."""
    sel, _ = _plan([(1, [3, 4, 5, 6]), (1, [7, 8, 9, 10]), (1, [11])], 4, 4)
    valid = sel > 0
    live = valid.copy()
    live[0, :3] = False
    live[1, 1] = False
    assert _groups(sel, valid, live) == [[6, 7, 9, 10], [11], [], []]


def test_on_the_endgame_circuit_the_route_runs_fewer_batches():
    """W=8 at 16 slots a batch (38 nets: three groups an iteration while
    they are one colour): legal, the same in every count twice over, and
    fewer batches than the parent dispatched for the same route, all of
    them saved in the finishing pass's tail: the negotiation windows
    before it are the parent's to the last count."""
    from parallel_eda_tpu.flow import synth_flow

    def route():
        f = synth_flow(num_luts=40, num_inputs=8, num_outputs=8,
                       chan_width=8, seed=3)
        res = Router(f.rr, RouterOpts(batch_size=16)).route(f.term)
        return res, [(s.iteration, s.overused_nodes, s.rerouted_nets,
                      s.relax_steps, s.batches) for s in res.stats]

    (a, wa), (b, wb) = route(), route()
    assert a.success and b.success
    assert wa == wb
    assert np.array_equal(a.paths, b.paths)
    assert (a.wirelength, a.iterations, a.total_relax_steps,
            a.total_waves, a.total_net_routes) == (
        b.wirelength, b.iterations, b.total_relax_steps, b.total_waves,
        b.total_net_routes)
    assert [s.kind for s in a.stats] == [
        "first", "negotiate", "negotiate", "negotiate", "finish"]
    assert [w[4] for w in wa[:4]] == PARENT_BATCHES[:4]
    assert wa[4][4] < PARENT_BATCHES[4]
    assert sum(w[4] for w in wa) == 42 < sum(PARENT_BATCHES)
