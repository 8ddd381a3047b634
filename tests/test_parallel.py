"""Multi-chip sharding tests on the 8-device virtual CPU mesh: the
sharded route step must be bit-identical to the single-device program for
every mesh shape (net-parallel, node-parallel, and 2-D), SURVEY §2.8."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.parallel.shard import ShardedRouter, make_mesh
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.route.device_graph import to_device
from parallel_eda_tpu.route.search import route_and_commit


pytestmark = pytest.mark.slow  # full-flow gate (pytest.ini)


def _setup(B=8):
    f = synth_flow(num_luts=25, chan_width=12, seed=2)
    rr, term = f.rr, f.term
    dev = to_device(rr)
    N = rr.num_nodes
    R, Smax = term.sinks.shape
    take = min(B, R)
    idx = np.arange(take)

    def pad(a, fill):
        out = np.full((B,) + a.shape[1:], fill, dtype=a.dtype)
        out[:take] = a[idx]
        return out

    args = dict(
        source=jnp.asarray(pad(term.source.astype(np.int32), 0)),
        sinks=jnp.asarray(pad(term.sinks.astype(np.int32), -1)),
        bb=jnp.asarray(pad(np.stack(
            [term.bb_xmin, term.bb_xmax, term.bb_ymin, term.bb_ymax],
            axis=1).astype(np.int32), 0)),
        crit=jnp.asarray(pad(np.zeros((R, Smax), np.float32), 0.0)),
        net_key=jnp.asarray(pad(np.arange(R, dtype=np.int32), 0)),
        valid=jnp.asarray(np.arange(B) < take),
        prev_paths=jnp.full((B, Smax, 96), N, jnp.int32),
        occ=jnp.zeros(N, jnp.int32),
        acc=jnp.ones(N, jnp.float32),
    )
    return dev, args


def _run(dev, a, mesh=None):
    kw = dict(max_steps=96, max_len=96, num_waves=2, group=1)
    if mesh is None:
        return route_and_commit(
            dev, a["occ"], a["acc"], jnp.float32(0.5), a["prev_paths"],
            a["source"], a["sinks"], a["bb"], a["crit"], a["net_key"],
            a["valid"], **kw)
    r = ShardedRouter(mesh)
    return r.route_step(
        r.shard_graph(dev), a["occ"], a["acc"], jnp.float32(0.5),
        a["prev_paths"], a["source"], a["sinks"], a["bb"], a["crit"],
        a["net_key"], a["valid"], **kw)


@pytest.mark.parametrize("shape", [(8, 1), (1, 8), (4, 2), (2, 4)])
def test_sharded_step_matches_single_device(shape):
    assert len(jax.devices()) >= 8, "conftest must provide 8 cpu devices"
    dev, a = _setup()
    p0, r0, d0, occ0, st0 = _run(dev, a)
    mesh = make_mesh(8, shape=shape)
    p1, r1, d1, occ1, st1 = _run(dev, a, mesh)
    assert np.array_equal(np.asarray(p0), np.asarray(p1)), shape
    assert np.array_equal(np.asarray(r0), np.asarray(r1))
    assert np.allclose(np.asarray(d0), np.asarray(d1), equal_nan=True)
    assert np.array_equal(np.asarray(occ0), np.asarray(occ1))
    assert int(st0) == int(st1)


def test_sharded_occupancy_consistent():
    # committed occupancy == sum of the returned nets' usage
    dev, a = _setup()
    mesh = make_mesh(8, shape=(4, 2))
    p1, r1, d1, occ1, _ = _run(dev, a, mesh)
    paths = np.asarray(p1)
    N = dev.num_nodes
    occ = np.zeros(N, dtype=np.int64)
    valid = np.asarray(a["valid"])
    for b in range(paths.shape[0]):
        if not valid[b]:
            continue
        nodes = np.unique(paths[b][paths[b] < N])
        occ[nodes] += 1
    assert np.array_equal(occ, np.asarray(occ1))


def test_batch_not_divisible_raises():
    dev, a = _setup(B=6)
    mesh = make_mesh(8, shape=(4, 2))
    with pytest.raises(ValueError):
        _run(dev, a, mesh)


def test_full_route_loop_sharded_matches_single_device():
    """The COMPLETE negotiation loop (rip-up, coloring, history, bb
    relaxation) under the mesh must converge and produce bit-identical
    paths/occupancy to the single-device run — the determinism oracle the
    reference buys with det_mutex logical clocks (det_mutex.cxx:100),
    here a property of fixed-order XLA collectives.  (4, 2) exercises
    both the net and node axes at once."""
    f = synth_flow(num_luts=20, chan_width=10, seed=5)
    rr, term = f.rr, f.term
    res0 = Router(rr, RouterOpts(batch_size=16)).route(term)
    mesh = make_mesh(8, shape=(4, 2))
    res1 = Router(rr, RouterOpts(batch_size=16), mesh=mesh).route(term)
    assert res0.success and res1.success
    assert res0.iterations == res1.iterations
    assert np.array_equal(res0.paths, res1.paths)
    assert np.array_equal(res0.occ, res1.occ)
    check_route(rr, term, res1.paths, occ=res1.occ)


@pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
def test_planes_window_sharded_matches_single_device(shape):
    """The FLAGSHIP program (route_window_planes: fused multi-iteration
    windows, planes relaxation with spatially sharded [B, W, X, Y]
    canvases, device MIS coloring, fused STA) on a 2-D mesh must be
    bit-identical to single-device — net axis = the MPI net partition,
    node axis = the spatial canvas shard (rr_graph_partitioner.h:840
    analogue), crit-path feedback device-resident throughout."""
    from parallel_eda_tpu.timing import TimingAnalyzer, build_timing_graph

    f = synth_flow(num_luts=20, chan_width=10, seed=5)
    rr, term = f.rr, f.term

    def run(mesh):
        tg = build_timing_graph(f.nl, f.pnl, term)
        ta = TimingAnalyzer(tg)
        r = Router(rr, RouterOpts(batch_size=16), mesh=mesh).route(
            term, analyzer=ta)
        return r, ta.crit_path_delay

    res0, cpd0 = run(None)
    res1, cpd1 = run(make_mesh(8, shape=shape))
    assert res0.success and res1.success
    assert res0.iterations == res1.iterations
    assert np.array_equal(res0.paths, res1.paths)
    assert np.array_equal(res0.occ, res1.occ)
    assert np.isclose(cpd0, cpd1, rtol=1e-6)
    check_route(rr, term, res1.paths, occ=res1.occ)
    # the mesh shards the batch: its waves scatter every walk slot with
    # B a batch dimension (planes.walk_scatters_dense), one device the
    # slots the kept walks ran
    assert 0 < res0.total_walk_slots_read < res0.total_walk_budget \
        == res1.total_walk_budget == res1.total_walk_slots_read


def test_windowed_sharded_matches_single_device():
    """The bb-windowed program under the (net, node) mesh: gather/scatter
    of per-net window tables must shard cleanly and stay bit-identical to
    the single-device run (the windowed analogue of the full-loop test
    above; fixture per test_router._big_grid_flow so windows engage)."""
    from tests.test_router import _big_grid_flow

    rr, term = _big_grid_flow(seed=13)
    opts = dict(batch_size=16, program="ell", sink_group=1, windowed=True)
    res0 = Router(rr, RouterOpts(**opts)).route(term)
    mesh = make_mesh(8, shape=(4, 2))
    res1 = Router(rr, RouterOpts(**opts), mesh=mesh).route(term)
    assert res0.success and res1.success
    assert res0.windowed_nets > 0 and \
        res0.windowed_nets == res1.windowed_nets
    assert np.array_equal(res0.paths, res1.paths)
    assert np.array_equal(res0.occ, res1.occ)
    check_route(rr, term, res1.paths, occ=res1.occ)


@pytest.mark.slow
def test_multislice_mesh_matches_single_device():
    """make_multislice_mesh (SURVEY §5.8 DCN deployment): 2 virtual
    slices x 4 chips, node axis intra-slice — the flagship window
    program must stay bit-identical to single-device under the
    slice-major layout (the mesh only moves WHERE the deterministic
    reductions run)."""
    from parallel_eda_tpu.parallel import make_multislice_mesh

    f = synth_flow(num_luts=20, chan_width=10, seed=5)
    rr, term = f.rr, f.term
    mesh = make_multislice_mesh(num_slices=2, chips_per_slice=4,
                                node_per_slice=2)
    assert mesh.shape == {"net": 4, "node": 2}
    r0 = Router(rr, RouterOpts(batch_size=16)).route(term)
    r1 = Router(rr, RouterOpts(batch_size=16), mesh=mesh).route(term)
    assert r0.success and r1.success
    assert np.array_equal(r0.paths, r1.paths)
    assert np.array_equal(r0.occ, r1.occ)
    check_route(rr, term, r1.paths, occ=r1.occ)
