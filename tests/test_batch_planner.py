"""Host batch-planner unit tests: median-cut binning, spatial
round-robin ordering, fanout-classed chunking, size-class crop
bucketing, and the converged-net plan compaction in _plan_groups.

These are pure-numpy host functions (route/router.py) — the planner
must be deterministic and must place every dirty net in exactly one
batch slot, because the device programs trust the plan blindly (invalid
slots are masked, never re-checked)."""

import numpy as np
import pytest

from parallel_eda_tpu.route.router import (_crop_ladder,
                                           _median_cut_bins,
                                           _order_and_chunk,
                                           _pow2_at_least,
                                           _size_class_buckets,
                                           _spatial_order)


def _pts(n, seed, lo=0, hi=30):
    rng = np.random.default_rng(seed)
    return (rng.uniform(lo, hi, n).astype(np.float64),
            rng.uniform(lo, hi, n).astype(np.float64))


class TestMedianCutBins:
    def test_balanced_leaves(self):
        x, y = _pts(64, 0)
        bins = _median_cut_bins(x, y, depth=4)
        assert bins.shape == (64,)
        assert bins.min() >= 0 and bins.max() < 16
        _, counts = np.unique(bins, return_counts=True)
        # median cuts: every leaf within one of n / 2^depth
        assert counts.min() >= 3 and counts.max() <= 5

    def test_balanced_on_clustered_placement(self):
        # all points in one corner: a fixed spatial grid would put
        # everything in one bin; median cuts still balance by COUNT
        x, y = _pts(48, 1, lo=0.0, hi=0.5)
        bins = _median_cut_bins(x, y, depth=3)
        _, counts = np.unique(bins, return_counts=True)
        assert len(counts) == 8
        assert counts.max() - counts.min() <= 2

    def test_deterministic(self):
        x, y = _pts(40, 2)
        a = _median_cut_bins(x, y, depth=4)
        b = _median_cut_bins(x.copy(), y.copy(), depth=4)
        assert np.array_equal(a, b)

    def test_degenerate_identical_points(self):
        x = np.full(16, 3.0)
        y = np.full(16, 4.0)
        bins = _median_cut_bins(x, y, depth=2)
        # stable half-splits keep the leaves balanced even when every
        # median tie would otherwise put all points on one side
        _, counts = np.unique(bins, return_counts=True)
        assert counts.tolist() == [4, 4, 4, 4]


class TestSpatialOrder:
    def test_is_permutation(self):
        x, y = _pts(50, 3)
        idx = np.arange(10, 60, dtype=np.int64)
        cx = np.zeros(60)
        cy = np.zeros(60)
        cx[10:60], cy[10:60] = x, y
        out = _spatial_order(idx, cx, cy)
        assert sorted(out.tolist()) == idx.tolist()

    def test_deterministic(self):
        x, y = _pts(33, 4)
        idx = np.arange(33, dtype=np.int64)
        assert np.array_equal(_spatial_order(idx, x, y),
                              _spatial_order(idx, x, y))

    def test_consecutive_nets_spread(self):
        # two tight clusters: the round-robin deal spreads every
        # dealing round (= one batch-sized window) evenly across the
        # device, so no half-window comes from a single cluster
        n = 32
        cx = np.concatenate([np.full(n // 2, 1.0), np.full(n // 2, 20.0)])
        cy = np.concatenate([np.full(n // 2, 1.0), np.full(n // 2, 20.0)])
        out = _spatial_order(np.arange(n, dtype=np.int64), cx, cy)
        side = (out >= n // 2).astype(int)
        for lo in range(0, n, 16):
            w = side[lo:lo + 16]
            assert w.sum() == len(w) // 2, \
                f"window at {lo} not spread: {w}"

    def test_singleton_passthrough(self):
        idx = np.array([7], dtype=np.int64)
        assert np.array_equal(_spatial_order(idx, np.zeros(8), np.zeros(8)),
                              idx)


class TestOrderAndChunk:
    def test_every_net_exactly_once(self):
        rng = np.random.default_rng(5)
        g = np.arange(70, dtype=np.int64)
        nsinks = rng.integers(1, 9, 80)
        cx, cy = _pts(80, 6)
        chunks = _order_and_chunk(g, nsinks, cx, cy, B=16)
        flat = np.concatenate(chunks)
        assert sorted(flat.tolist()) == g.tolist()
        assert all(len(c) <= 16 for c in chunks)

    def test_fanout_classes_descend(self):
        # high-fanout classes first (deepest wave loops lead)
        g = np.arange(40, dtype=np.int64)
        nsinks = np.where(g < 20, 2, 8)
        cx, cy = _pts(40, 7)
        chunks = _order_and_chunk(g, nsinks, cx, cy, B=64)
        first = chunks[0]
        assert (nsinks[first][:20] == 8).all()

    def test_empty(self):
        assert _order_and_chunk(np.zeros(0, dtype=np.int64),
                                np.zeros(0), np.zeros(0),
                                np.zeros(0), 8) == []


class TestSizeClassBuckets:
    def test_every_net_exactly_one_bucket(self):
        rng = np.random.default_rng(8)
        w = rng.integers(2, 40, 100)
        h = rng.integers(2, 40, 100)
        classes, assign = _size_class_buckets(w, h, nx=40, ny=40)
        assert assign.shape == (100,)
        assert (assign >= 0).all() and (assign <= len(classes)).all()
        # partition: bucket counts + full-canvas count == n
        counts = [(assign == k).sum() for k in range(len(classes) + 1)]
        assert sum(counts) == 100

    def test_smallest_fitting_rung(self):
        w = np.array([4, 10, 20, 39])
        h = np.array([4, 10, 20, 39])
        classes, assign = _size_class_buckets(w, h, nx=40, ny=40)
        # ladder stops before 64x64 (clamped to 40x40 == the grid);
        # 32x32 stays (1024 < 0.8 * 1600)
        assert classes == [(8, 8), (16, 16), (32, 32)]
        # smallest fitting rung each; 39x39 fits none -> full canvas
        assert assign.tolist() == [0, 1, 2, 3]

    def test_ladder_stops_near_grid(self):
        # on a grid barely above base the ladder is empty: every net
        # takes the full canvas (a near-grid crop saves nothing)
        w = np.array([2, 3])
        h = np.array([2, 3])
        classes, assign = _size_class_buckets(w, h, nx=8, ny=8)
        assert classes == []
        assert (assign == 0).all()

    def test_rectangular_grid_clamps(self):
        w = np.array([10])
        h = np.array([10])
        classes, _ = _size_class_buckets(w, h, nx=64, ny=12, base=8,
                                         full_frac=0.8)
        for cw, ch in classes:
            assert cw <= 64 and ch <= 12

    def test_underpopulated_rung_merges_up(self):
        # one lone tiny net among many medium nets: the 8-rung would
        # hold a single net, so it merges into the 16-rung
        w = np.concatenate([[4], np.full(20, 12)])
        h = np.concatenate([[4], np.full(20, 12)])
        classes, assign = _size_class_buckets(w, h, nx=64, ny=64,
                                              min_count=4)
        assert (8, 8) not in classes
        assert classes[0] == (16, 16)
        assert (assign == 0).all()

    def test_deterministic(self):
        rng = np.random.default_rng(9)
        w = rng.integers(2, 30, 60)
        h = rng.integers(2, 30, 60)
        a = _size_class_buckets(w, h, 32, 32, min_count=3)
        b = _size_class_buckets(w.copy(), h.copy(), 32, 32, min_count=3)
        assert a[0] == b[0]
        assert np.array_equal(a[1], b[1])

    @pytest.mark.parametrize("n, ladder, assign", [
        (11, [(8, 8)], None),               # route_k6n10_relaxed
        (17, [(8, 8)], None),               # 256 >= 0.8 * 289
        (18, [(8, 8), (16, 16)], [0, 0, 1, 1]),     # the first 16 rung
        (19, [(8, 8), (16, 16)], [0, 0, 1, 1]),     # route_scale
        (22, [(8, 8), (16, 16)], [0, 0, 1, 1]),     # route_relaxed
        (29, [(8, 8), (16, 16)], [0, 0, 1, 1]),     # clma: 32 clamps to 29
        (40, [(8, 8), (16, 16), (32, 32)], [0, 0, 1, 2]),
    ])
    def test_ladder_of_the_benchmark_grids(self, n, ladder, assign):
        """The ladder is a function of the grid alone, and span-4 wires
        (a margin of 2 x 4 cells) decide who fits: a 1-cell box needs 9,
        so no net fits the 8 rung; boxes up to 8 fit the 16 rung; on a
        19 to 29 grid the rest, between 16 and the grid, has no rung
        and takes the full canvas."""
        assert _crop_ladder(n, n) == ladder
        box = np.array([1, 8, 9, n])
        classes, got = _size_class_buckets(box + 2 * 4, box + 2 * 4, n, n)
        # the 8 rung is never populated
        assert classes == ladder[1:]
        assert got.tolist() == (assign or [0, 0, 0, 0])


class TestPlanGroupsCompaction:
    @pytest.fixture(scope="class")
    def router(self):
        from parallel_eda_tpu.flow import synth_flow
        from parallel_eda_tpu.route import Router, RouterOpts

        f = synth_flow(num_luts=15, chan_width=10, seed=0)
        return Router(f.rr, RouterOpts(batch_size=32)), f

    def test_padding_inert_and_every_net_once(self, router):
        r, f = router
        R = f.term.sinks.shape[0]
        rng = np.random.default_rng(10)
        dirty = np.sort(rng.choice(R, min(R, 11), replace=False)
                        .astype(np.int64))
        nsinks = (np.asarray(f.term.sinks) >= 0).sum(axis=1)
        cx = np.asarray(f.term.bb_xmin + f.term.bb_xmax) / 2.0
        cy = np.asarray(f.term.bb_ymin + f.term.bb_ymax) / 2.0
        sel, valid = r._plan_groups(dirty, None, nsinks, cx, cy,
                                    B=32, R=R)
        # valid carries a net slot's conflict-colour segment (PR 36):
        # with no colouring the plan is the one segment, 0 on a pad
        assert set(np.unique(valid)) <= {0, 1}
        # every dirty net appears in exactly one VALID slot
        assert sorted(sel[valid > 0].tolist()) == dirty.tolist()
        # padding is inert: invalid slots carry the 0 sentinel and the
        # device masks them; no dirty net hides in an invalid slot
        assert (sel[valid == 0] == 0).all()

    def test_width_compacts_to_pow2_of_largest_chunk(self, router):
        r, f = router
        R = f.term.sinks.shape[0]
        dirty = np.arange(min(R, 5), dtype=np.int64)
        nsinks = (np.asarray(f.term.sinks) >= 0).sum(axis=1)
        cx = np.asarray(f.term.bb_xmin + f.term.bb_xmax) / 2.0
        cy = np.asarray(f.term.bb_ymin + f.term.bb_ymax) / 2.0
        sel, valid = r._plan_groups(dirty, None, nsinks, cx, cy,
                                    B=32, R=R)
        # 5 dirty nets: width narrows to max(8, pow2(chunk)) == 8, not
        # the full B=32 (converged-net compaction)
        assert sel.shape[1] == 8
        assert valid.shape == sel.shape
        # G padded to a power of two (compile-variant bound)
        assert sel.shape[0] == _pow2_at_least(sel.shape[0])

    def test_full_batch_keeps_width(self, router):
        r, f = router
        R = f.term.sinks.shape[0]
        dirty = np.arange(R, dtype=np.int64)
        nsinks = (np.asarray(f.term.sinks) >= 0).sum(axis=1)
        cx = np.asarray(f.term.bb_xmin + f.term.bb_xmax) / 2.0
        cy = np.asarray(f.term.bb_ymin + f.term.bb_ymax) / 2.0
        B = min(32, _pow2_at_least(R))
        sel, valid = r._plan_groups(dirty, None, nsinks, cx, cy,
                                    B=B, R=R)
        assert sel.shape[1] <= B
        assert sorted(sel[valid > 0].tolist()) == dirty.tolist()
