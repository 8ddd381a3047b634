"""The planes relaxation is PER-NET, and plane_dtype names what commits.

First half: net independence of the XLA relaxation — a batch
relaxes each net on its own canvas against its own congestion view, so
`planes_relax` / `planes_relax_cropped` over a batch equal each net
relaxed alone, bit for bit (a converged net's extra trips while a
batchmate is still improving are identities).  Covers both stencil
kinds (bidirectional, directional) and two crop-ladder rungs.  The
host-side block-planning arithmetic (`serve/batcher.py`) is checked
beside it.

Second half, at full routing fidelity: a window of TWO populated rungs
escalates the history cost on its first rung only and threads the
donated state rung to rung, on the directional graph and a second
random circuit, in both plane dtypes; ``plane_dtype="bf16"`` COMMITS
bf16 — a legal route whose wirelength recount holds; the option values
that selected the deleted Pallas lowering and the shadow guards are
refused by name, and the options and flags of the deleted fused
scheduler by the dataclass or the parser itself.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import minimal_arch, unidir_arch
from parallel_eda_tpu.route.planes import (build_planes, planes_relax,
                                           planes_relax_cropped)
from parallel_eda_tpu.rr.graph import CHANX, CHANY, build_rr_graph
from parallel_eda_tpu.rr.grid import DeviceGrid
from parallel_eda_tpu.serve.batcher import (VMEM_BUDGET_BYTES,
                                            auto_block_nets,
                                            packed_layout,
                                            unpacked_lane_occupancy)


@pytest.fixture(autouse=True)
def _release_between_tests():
    """This module's routes map 57 of the 65 thousand memory mappings a
    process may hold (tests/conftest.py): entered by a worker that
    already holds nine, its last route dies in the compiler.  Its tests
    share no compiled program that matters, so the programs are let go
    between them past half the limit."""
    yield
    from conftest import release_compiled_programs
    release_compiled_programs(at=32000)


def _instance(arch, nx, ny, B, seed):
    grid = DeviceGrid(nx, ny, arch.io_capacity)
    rr = build_rr_graph(arch, grid)
    pg = build_planes(rr)
    N = rr.num_nodes
    rng = np.random.default_rng(seed)
    wires = np.where((rr.node_type == CHANX) | (rr.node_type == CHANY))[0]
    noc = np.asarray(pg.node_of_cell)
    seed_m = np.zeros((B, N), bool)
    for b in range(B):
        seed_m[b, rng.choice(wires, 2, replace=False)] = True
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    d0 = jnp.asarray(np.where(seed_m[:, noc], 0.0, np.inf)
                     .astype(np.float32))
    cc = jnp.asarray(cong[:, noc])
    crit = jnp.asarray(rng.uniform(0, 0.8, (B, 1, 1, 1))
                       .astype(np.float32))
    w0 = jnp.zeros((B, pg.ncells), jnp.float32)
    return rr, pg, d0, cc, crit, w0


def _assert_identical(a, b):
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype.kind == "f":
            # bit-identical: equal where finite, inf exactly matched
            assert np.array_equal(x, y, equal_nan=True), \
                np.abs(np.where(np.isfinite(x) & np.isfinite(y),
                                x - y, 0)).max()
        else:
            assert np.array_equal(x, y)


def _assert_nets_relax_alone(batch, relax_one, B):
    """``batch`` = (dist, pred, wenter, stats) of the whole batch;
    ``relax_one(sl)`` relaxes the nets of slice ``sl`` alone.  The
    per-net outputs must match bit for bit, and the batch's sweep count
    is the slowest member's (stats are per-dispatch maxima)."""
    trips = []
    for b in range(B):
        sl = slice(b, b + 1)
        alone = relax_one(sl)
        _assert_identical([np.asarray(t)[sl] for t in batch[:3]],
                          alone[:3])
        trips.append(int(alone[3][0]))
    assert np.isfinite(np.asarray(batch[0])).any()
    assert int(batch[3][0]) == max(trips)


@pytest.mark.parametrize("plane_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch,nx,ny,B,seed", [
    (minimal_arch(chan_width=6), 4, 4, 5, 0),
    (minimal_arch(chan_width=6), 5, 4, 4, 1),
    (unidir_arch(chan_width=6, length=2), 5, 4, 3, 3),   # directional
])
def test_relax_net_independent(arch, nx, ny, B, seed, plane_dtype):
    """Both storage dtypes: the bf16 loop requantizes per net too."""
    _, pg, d0, cc, crit, w0 = _instance(arch, nx, ny, B, seed)
    batch = planes_relax(pg, d0, cc, crit, w0, 12,
                         plane_dtype=plane_dtype)
    _assert_nets_relax_alone(
        batch,
        lambda sl: planes_relax(pg, d0[sl], cc[sl], crit[sl], w0[sl], 12,
                                plane_dtype=plane_dtype),
        B)


@pytest.mark.parametrize("cnx,cny", [(6, 6), (8, 5)])
def test_relax_cropped_net_independent(cnx, cny):
    """Two crop-ladder rungs (square + rectangular), each net's tile at
    its own origin."""
    arch = minimal_arch(chan_width=8)
    grid = DeviceGrid(12, 10, arch.io_capacity)
    rr = build_rr_graph(arch, grid)
    pg = build_planes(rr)
    N = rr.num_nodes
    B = 3
    rng = np.random.default_rng(7)
    noc = np.asarray(pg.node_of_cell)
    W, NX, NYp1 = pg.shape_x
    _, _, NY = pg.shape_y
    ox = rng.integers(0, NX - cnx, B).astype(np.int32)
    oy = rng.integers(0, NY - cny, B).astype(np.int32)
    Lm = pg.max_span
    inside = np.zeros((B, N), bool)
    for b in range(B):
        x0, y0 = int(ox[b]) + Lm, int(oy[b]) + Lm
        x1, y1 = int(ox[b]) + cnx - Lm, int(oy[b]) + cny - Lm
        inside[b] = ((rr.xlow >= x0) & (rr.xhigh <= x1)
                     & (rr.ylow >= y0) & (rr.yhigh <= y1)
                     & ((rr.node_type == CHANX)
                        | (rr.node_type == CHANY)))
        assert inside[b].any()
    cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
    cc_n = np.where(inside, cong, np.inf).astype(np.float32)
    cc = jnp.asarray(cc_n[:, noc])
    d0n = np.full((B, pg.ncells), np.inf, np.float32)
    for b in range(B):
        fin = np.where(np.isfinite(cc_n[b, noc]))[0]
        d0n[b, rng.choice(fin, 2, replace=False)] = 0.0
    d0 = jnp.asarray(d0n)
    crit = jnp.asarray(rng.uniform(0, 0.8, (B, 1, 1, 1))
                       .astype(np.float32))
    w0 = jnp.zeros((B, pg.ncells), jnp.float32)
    oxj, oyj = jnp.asarray(ox), jnp.asarray(oy)

    batch = planes_relax_cropped(pg, d0, cc, crit, w0, 24, oxj, oyj,
                                 cnx, cny)
    _assert_nets_relax_alone(
        batch,
        lambda sl: planes_relax_cropped(pg, d0[sl], cc[sl], crit[sl],
                                        w0[sl], 24, oxj[sl], oyj[sl],
                                        cnx, cny),
        B)


def test_block_planning_model():
    """auto_block_nets fits the budget, never exceeds the batch, and
    the packed layout's occupancy model beats the one-net layout at
    the bench canvas size (the whole point of the fold)."""
    shx, shy = (12, 12, 13), (12, 13, 12)
    lay = packed_layout(shx, shy, 8)
    G = auto_block_nets(shx, shy, 64, 8)
    assert G >= 8 and G & (G - 1) == 0
    assert lay.block_bytes(G) <= VMEM_BUDGET_BYTES
    assert auto_block_nets(shx, shy, 5, 8) <= 5
    assert lay.lane_occupancy(8) >= 0.5
    assert lay.lane_occupancy(8) > 4 * unpacked_lane_occupancy(shx, shy)
    # a rung too big for even one net still runs: G degrades to 1
    huge = (64, 512, 513)
    assert auto_block_nets(huge, (64, 513, 512), 64, 8) == 1


# --------------------------------------------------------------------
# Full-route checks.  Flows are cached at module scope.

_FLOWS: dict = {}
_GRAPHS = ["bench", "unidir", "random7"]


def _flow(name):
    from parallel_eda_tpu.flow import synth_flow
    if name not in _FLOWS:
        if name == "unidir":
            _FLOWS[name] = synth_flow(
                num_luts=12, num_inputs=5, num_outputs=5,
                chan_width=14, seed=5,
                arch=unidir_arch(chan_width=14, length=2))
        elif name == "random7":
            # a second generate_circuit draw: different seed, different
            # topology — guards against a result that only holds for
            # one routing instance
            _FLOWS[name] = synth_flow(
                num_luts=18, num_inputs=6, num_outputs=6,
                chan_width=10, seed=7)
        else:
            _FLOWS[name] = synth_flow(
                num_luts=15, num_inputs=6, num_outputs=6,
                chan_width=10, seed=3)
    return _FLOWS[name]


def _two_rung_flow(name):
    """``name``'s architecture and seed at 100 LUTs, placed, nets boxed
    tightly (bb_factor=1): on its 8 x 8 grid ``crop="7x7"`` hands the
    first window's narrow nets a cropped rung and the rest the full
    canvas (the 3 x 3 grids of ``_flow`` have no rung to populate)."""
    from parallel_eda_tpu.flow import run_place_native, synth_flow
    key = name + "_100"
    if key not in _FLOWS:
        kw = (dict(chan_width=14, seed=5,
                   arch=unidir_arch(chan_width=14, length=2))
              if name == "unidir" else dict(chan_width=12, seed=7))
        _FLOWS[key] = run_place_native(
            synth_flow(num_luts=100, bb_factor=1, **kw))
    return _FLOWS[key]


@pytest.mark.parametrize("pd", ["f32", "bf16"])
@pytest.mark.parametrize("name", ["unidir", "random7"])
def test_two_rung_window_escalates_once_and_threads_the_state(
        name, pd, monkeypatch):
    """A window that populates two rungs dispatches route_window_planes
    twice: ``acc_fac`` is the option's on the first and 0 on the
    second (the history cost escalates once a window), every rung gets
    the same ``pres0`` / ``it0``, and the second is handed the very
    arrays the first returned (they are donated on).  The route is the
    ``pipeline=False`` route node for node, legal, and recounts to its
    own wirelength."""
    import inspect

    from parallel_eda_tpu.route import (Router, RouterOpts, check_route,
                                        planes)
    f = _two_rung_flow(name)
    kw = dict(batch_size=32, crop="7x7", plane_dtype=pd)
    base = Router(f.rr, RouterOpts(pipeline=False, **kw)).route(f.term)
    assert base.success

    real = planes.route_window_planes
    sig = inspect.signature(real.__wrapped__)
    state = ("occ", "acc", "paths", "sink_delay", "all_reached", "bb",
             "crit_all")
    windows: dict = {}
    last = []

    def spy(*a, **k):
        arg = sig.bind(*a, **k).arguments
        rungs = windows.setdefault(int(arg["it0"]), [])
        if rungs:
            # a later rung of the same window: the state is what the
            # rung before it returned, not a copy
            for n in state:
                assert arg[n] is getattr(last[0], n), n
        rungs.append((float(arg["acc_fac"]), float(arg["pres0"]),
                      arg["crop_tile"]))
        out = real(*a, **k)
        assert isinstance(out, planes.WindowOut)
        last[:] = [out]
        return out

    monkeypatch.setattr(planes, "route_window_planes", spy)
    res = Router(f.rr, RouterOpts(**kw)).route(f.term)
    assert res.success
    two = [r for r in windows.values() if len(r) > 1]
    assert two, windows
    for rungs in windows.values():
        assert [r[0] for r in rungs] == [1.0] + [0.0] * (len(rungs) - 1)
        assert len({r[1] for r in rungs}) == 1
        # cropped rungs first, the full canvas last
        assert all(r[2] is not None for r in rungs[:-1])
    assert np.array_equal(base.paths, res.paths)
    assert np.array_equal(base.occ, res.occ)
    assert base.wirelength == res.wirelength
    judged = check_route(f.rr, f.term, res.paths, occ=res.occ)
    assert judged["wirelength"] == res.wirelength > 0


@pytest.mark.parametrize("name", _GRAPHS)
def test_bf16_route_commits_and_is_legal(name):
    """plane_dtype="bf16" is the dtype that is committed: the route
    converges, check_route (the independent legality oracle) accepts
    its trees against its own occupancy, and the oracle's wirelength
    recount equals the router's.  No parity with f32 is asked: bf16 is
    a different result, not a faster f32 one."""
    from parallel_eda_tpu.obs import (MetricsRegistry, get_metrics,
                                      set_metrics)
    from parallel_eda_tpu.route import Router, RouterOpts, check_route
    f = _flow(name)
    old = get_metrics()
    reg = set_metrics(MetricsRegistry())
    try:
        res = Router(f.rr, RouterOpts(
            batch_size=32, plane_dtype="bf16")).route(f.term)
        assert reg.gauge("route.kernel.plane_dtype").value == "bf16"
    finally:
        set_metrics(old)
    assert res.success
    judged = check_route(f.rr, f.term, res.paths, occ=res.occ)
    assert judged["wirelength"] == res.wirelength > 0


@pytest.mark.parametrize("kw,names", [
    (dict(program="planes_pallas"), ("'planes'", "'ell'")),
    (dict(dtype_guard="window"), ("dtype_guard", "'off'")),
    (dict(dtype_guard="route"), ("dtype_guard", "'off'")),
], ids=["planes_pallas", "guard_window", "guard_route"])
def test_deleted_option_values_are_refused(kw, names):
    """The values that selected the Pallas lowering and the shadow
    guards raise a ValueError that names what is accepted — at Router
    construction (program) or at route start (dtype_guard) — and never
    fall through to another program or to a silent f32 commit."""
    from parallel_eda_tpu.route import Router, RouterOpts
    f = _flow("bench")
    with pytest.raises(ValueError) as ei:
        Router(f.rr, RouterOpts(batch_size=32, **kw)).route(f.term)
    for n in names:
        assert n in str(ei.value)


def _deleted_field_cases():
    from parallel_eda_tpu.route import RouterOpts
    from parallel_eda_tpu.serve.daemon import DaemonOpts
    from parallel_eda_tpu.serve.fleet import FleetOpts
    from parallel_eda_tpu.serve.service import RouteService
    return {
        "RouterOpts": (lambda: RouterOpts(fused_dispatch=True),
                       "fused_dispatch"),
        "DaemonOpts": (lambda: DaemonOpts(fused=True), "fused"),
        "FleetOpts": (lambda: FleetOpts(fused=True), "fused"),
        "RouteService": (lambda: RouteService(None, fused=True), "fused"),
    }


@pytest.mark.parametrize(
    "who", ["RouterOpts", "DaemonOpts", "FleetOpts", "RouteService"])
def test_deleted_option_fields_are_refused(who):
    """The fields that selected the fused window program and the batch
    scheduler are gone, and nothing in the package refuses them in
    their name: the dataclass (the constructor) itself raises a
    TypeError that names the key, so a job script or a fleet
    configuration that still asks for the deleted scheduler fails
    loudly and never runs the interleaved one in its place."""
    build, key = _deleted_field_cases()[who]
    with pytest.raises(TypeError) as ei:
        build()
    assert repr(key) in str(ei.value)


@pytest.mark.parametrize("argv,flag", [
    (["serve", "--fused"], "--fused"),
    (["daemon", "run", "--inbox", "box", "--fused"], "--fused"),
    (["daemon", "fleet", "--inbox", "box", "--fused"], "--fused"),
    (["bench.py", "--cpu", "--fused_dispatch"], "--fused_dispatch"),
], ids=["serve", "daemon_run", "daemon_fleet", "bench"])
def test_deleted_cli_flags_exit_2(argv, flag, capsys):
    """``--fused`` at the three serving front ends and
    ``--fused_dispatch`` at bench.py are unknown to the parser: exit
    code 2, the flag named, nothing routed."""
    import os
    import subprocess
    import sys
    if argv[0] == "bench.py":
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        p = subprocess.run([sys.executable, os.path.join(repo, "bench.py")]
                           + argv[1:], capture_output=True, text=True,
                           timeout=300)
        code, err = p.returncode, p.stderr
        assert p.stdout.strip() == ""
    else:
        from parallel_eda_tpu.__main__ import main
        with pytest.raises(SystemExit) as ei:
            main(list(argv))
        code, err = ei.value.code, capsys.readouterr().err
    assert code == 2
    assert f"unrecognized arguments: {flag}" in err
