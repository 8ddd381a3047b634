"""Multi-chip halo-exchange sharding (route/planes_shard.py).

Three layers, mirroring tests/test_kernel_pack.py's parity discipline:

* kernel parity — planes_relax_sharded vs the single-device
  planes_relax on EXACT (power-of-two) congestion costs, where the
  min-plus sums are exact in f32 and the truncated per-shard scans
  must regroup without ulp drift: dist and wenter are asserted
  BIT-IDENTICAL for every transport impl x shard count x plane dtype.
  pred is deliberately not asserted cell-wise: on equal-cost ties a
  shard boundary can deliver one of two equally-short paths a sweep
  later, and the strict-< update keeps whichever arrived first — the
  router's per-(net,node) jitter makes shortest paths unique, which
  is why ROUTE-level parity below is exact.
* window parity — ONE window of the window program
  (route_window_planes) under a two-shard RowMesh and under the GSPMD `net` mesh
  must leave the one-device window's state and ledger, bit for bit;
  tier-1 runs these (the whole-route gates below are `slow`).
* route parity — a mesh-sharded Router run must produce bit-identical
  paths/occ/wirelength to the single-device baseline, a bf16 one must equal the single-device bf16 route, and
  the halo ledger must be populated.
* degradation — an injected backend.loss must land the resilience
  ladder's "mesh" dimension on the single_chip floor and still finish
  bit-identical.

The mesh layers need >= 4 visible devices
(XLA_FLAGS=--xla_force_host_platform_device_count=4, as the CI
mesh-smoke job sets); on a stock 1-device tier-1 host they skip.
The model/validation layers (make_mesh argument checking, the
dtype-aware halo byte model, the corpus n_shards field, flow_doctor's mesh-consistency rule) run
everywhere.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import minimal_arch, unidir_arch
from parallel_eda_tpu.flow import synth_flow
from parallel_eda_tpu.obs import MetricsRegistry, get_metrics, set_metrics
from parallel_eda_tpu.route import Router, RouterOpts, check_route
from parallel_eda_tpu.route.planes import (build_planes, plane_itemsize,
                                           planes_relax)
from parallel_eda_tpu.route.planes_shard import (halo_bytes_per_sweep,
                                                 make_row_mesh,
                                                 modeled_overlap_frac,
                                                 planes_relax_sharded,
                                                 row_block_cols)
from parallel_eda_tpu.rr.graph import CHANX, CHANY, build_rr_graph
from parallel_eda_tpu.rr.grid import DeviceGrid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=4 before jax init)")


# ---- fixtures ------------------------------------------------------

def _instance(arch, nx, ny, B, seed, exact=True):
    """A planes instance with random wire seeds; exact=True draws
    power-of-two congestion costs (f32-exact min-plus sums)."""
    rr = build_rr_graph(arch, DeviceGrid(nx, ny, arch.io_capacity))
    pg = build_planes(rr)
    N = rr.num_nodes
    rng = np.random.default_rng(seed)
    wires = np.where((rr.node_type == CHANX)
                     | (rr.node_type == CHANY))[0]
    noc = np.asarray(pg.node_of_cell)
    seed_m = np.zeros((B, N), bool)
    for b in range(B):
        seed_m[b, rng.choice(wires, 2, replace=False)] = True
    if exact:
        cong = (2.0 ** rng.integers(-6, 3, (B, N))).astype(np.float32)
        crit = jnp.zeros((B, 1, 1, 1), jnp.float32)
    else:
        cong = rng.uniform(0.5, 2.0, (B, N)).astype(np.float32) * 1e-10
        crit = jnp.asarray(rng.uniform(0, 0.8, (B, 1, 1, 1))
                           .astype(np.float32))
    d0 = jnp.asarray(np.where(seed_m[:, noc], 0.0, np.inf)
                     .astype(np.float32))
    cc = jnp.asarray(cong[:, noc])
    w0 = jnp.zeros((B, pg.ncells), jnp.float32)
    return pg, d0, cc, crit, w0


_FLOWS = {}
_BASE = {}


def _flow():
    if "bench" not in _FLOWS:
        _FLOWS["bench"] = synth_flow(num_luts=15, num_inputs=6,
                                     num_outputs=6, chan_width=10,
                                     seed=3)
    return _FLOWS["bench"]


def _baseline():
    if "bench" not in _BASE:
        f = _flow()
        _BASE["bench"] = Router(f.rr, RouterOpts(
            batch_size=32)).route(f.term)
        assert _BASE["bench"].success
    return _BASE["bench"]


def _small_pg():
    if "pg" not in _FLOWS:
        arch = minimal_arch(chan_width=6)
        rr = build_rr_graph(arch, DeviceGrid(6, 5, arch.io_capacity))
        _FLOWS["pg"] = build_planes(rr)
    return _FLOWS["pg"]


# ---- kernel parity (needs a mesh) ----------------------------------

@needs_mesh
@pytest.mark.slow
@pytest.mark.parametrize("impl,s,dtype", [
    ("ppermute", 4, "f32"),
    ("ppermute", 2, "f32"),
    ("ppermute", 3, "f32"),
    ("ppermute", 4, "bf16"),
    ("pallas_halo", 4, "f32"),
    ("pallas_halo", 3, "f32"),
    ("pallas_halo", 4, "bf16"),
])
def test_kernel_parity_exact_costs(impl, s, dtype):
    pg, d0, cc, crit, w0 = _instance(minimal_arch(chan_width=6),
                                     6, 5, 4, 0)
    ref = planes_relax(pg, d0, cc, crit, w0, 24, plane_dtype=dtype)
    out = planes_relax_sharded(pg, d0, cc, crit, w0, 24,
                               make_row_mesh(s, impl),
                               plane_dtype=dtype)
    # dist + wenter bit-identical; pred only up to equal-cost ties
    # (see module docstring)
    for name, a, b in (("dist", ref[0], out[0]),
                       ("wenter", ref[2], out[2])):
        assert np.array_equal(np.asarray(a), np.asarray(b),
                              equal_nan=True), (name, impl, s, dtype)
    # every pred cell must still name a real discovered cell: finite
    # dist iff pred was written identically in both programs
    fin_ref = np.isfinite(np.asarray(ref[0]))
    fin_out = np.isfinite(np.asarray(out[0]))
    assert np.array_equal(fin_ref, fin_out)


@needs_mesh
@pytest.mark.slow
def test_kernel_parity_unidir_arch():
    pg, d0, cc, crit, w0 = _instance(
        unidir_arch(chan_width=6, length=2), 7, 5, 3, 2)
    ref = planes_relax(pg, d0, cc, crit, w0, 24)
    out = planes_relax_sharded(pg, d0, cc, crit, w0, 24,
                               make_row_mesh(4, "ppermute"))
    assert np.array_equal(np.asarray(ref[0]), np.asarray(out[0]),
                          equal_nan=True)
    assert np.array_equal(np.asarray(ref[2]), np.asarray(out[2]),
                          equal_nan=True)


# ---- one window of the per-rung program under each mesh ------------

def _one_window(mesh=None, shard=None):
    """__graft_entry__'s tiny problem through ONE one-iteration window
    of route_window_planes (one forced group): the state 6-tuple and
    the packed scal ledger."""
    import __graft_entry__ as graft
    from parallel_eda_tpu.route.planes import route_window_planes

    p = graft.planes_step_problem()
    occ, acc, paths, sink_delay, all_reached, bb = p["state"]
    dev = p["dev"] if shard is None else shard(p["dev"])
    out = route_window_planes(
        p["pg"], dev, occ, acc, paths, sink_delay, all_reached, bb,
        *p["nets"], p["sel"][None], p["valid"][None], p["full_bb"],
        jnp.float32(0.5), jnp.float32(1.0), jnp.float32(1.0),
        jnp.float32(0.0), jnp.int32(0), jnp.int32(1),
        1, p["nsweeps"], p["max_len"], p["num_waves"], p["group"],
        True, topk=64, mesh=mesh)
    return out[:6] + (out[22],)


def _assert_window_equal(got, shards_batch=False):
    if "window" not in _BASE:
        _BASE["window"] = _one_window()
    want = _BASE["window"]
    from parallel_eda_tpu.route.planes import (SCAL_SINK_ROWS,
                                               SCAL_SINK_ROWS_DENSE,
                                               SCAL_WALK_BUDGET,
                                               SCAL_WALK_SLOTS)

    assert np.asarray(want[4]).any()          # nets were routed
    # under a mesh the sink pick is the dense one, statically (the live
    # list compacts across the batch axis, which a mesh shards): the
    # ledger says so, and nothing else differs
    scal, scal_one = np.array(got[6]), np.array(want[6])
    assert scal[SCAL_SINK_ROWS] == scal[SCAL_SINK_ROWS_DENSE] \
        == scal_one[SCAL_SINK_ROWS_DENSE] > scal_one[SCAL_SINK_ROWS] > 0
    scal[SCAL_SINK_ROWS] = scal_one[SCAL_SINK_ROWS]
    if shards_batch:
        # a GSPMD mesh's waves scatter every walk slot, the batch a
        # dimension of both scatters (planes.walk_scatters_dense)
        assert scal[SCAL_WALK_SLOTS] == scal[SCAL_WALK_BUDGET] \
            == scal_one[SCAL_WALK_BUDGET] > scal_one[SCAL_WALK_SLOTS] > 0
        scal[SCAL_WALK_SLOTS] = scal_one[SCAL_WALK_SLOTS]
    for name, a, b in zip(("occ", "acc", "paths", "sink_delay",
                           "all_reached", "bb", "scal"),
                          got[:6] + (scal,), want):
        assert np.array_equal(np.asarray(a), np.asarray(b),
                              equal_nan=True), name


@needs_mesh
def test_window_under_gspmd_net_mesh_equals_one_device():
    from parallel_eda_tpu.parallel.shard import make_mesh, shard_graph
    mesh = make_mesh(2, shape=(2, 1))
    _assert_window_equal(
        _one_window(mesh, lambda dev: shard_graph(dev, mesh)),
        shards_batch=True)


@needs_mesh
def test_window_under_two_shard_row_mesh_equals_one_device():
    _assert_window_equal(_one_window(make_row_mesh(2, "ppermute")))


# ---- route parity (needs a mesh) -----------------------------------

def _assert_route_parity(**kw):
    base = _baseline()
    f = _flow()
    res = Router(f.rr, RouterOpts(batch_size=32, **kw)).route(f.term)
    assert res.success, kw
    assert res.wirelength == base.wirelength, \
        (kw, res.wirelength, base.wirelength)
    assert np.array_equal(np.asarray(base.paths),
                          np.asarray(res.paths)), kw
    assert np.array_equal(np.asarray(base.occ), np.asarray(res.occ)), kw
    check_route(f.rr, f.term, res.paths, occ=res.occ)
    return res


@needs_mesh
@pytest.mark.slow
def test_route_parity_mesh4():
    old = set_metrics(MetricsRegistry())
    try:
        _assert_route_parity(mesh_shards=4)
        mv = get_metrics().values("route.mesh.")
        assert (mv.get("route.mesh.halo_bytes") or 0) > 0
        assert (mv.get("route.mesh.halo_exchanges") or 0) > 0
        assert mv.get("route.mesh.n_shards") == 4
        assert (mv.get("route.mesh.mesh_demotions") or 0) == 0
    finally:
        set_metrics(old)


@needs_mesh
@pytest.mark.slow
def test_route_parity_mesh2():
    _assert_route_parity(mesh_shards=2)


@needs_mesh
@pytest.mark.slow
def test_route_parity_mesh3_bf16():
    """bf16 commits bf16, so the reference is the single-device bf16
    route, not the f32 baseline."""
    f = _flow()
    ref = Router(f.rr, RouterOpts(batch_size=32,
                                  plane_dtype="bf16")).route(f.term)
    res = Router(f.rr, RouterOpts(batch_size=32, plane_dtype="bf16",
                                  mesh_shards=3)).route(f.term)
    assert ref.success and res.success
    assert res.wirelength == ref.wirelength
    assert np.array_equal(np.asarray(ref.paths), np.asarray(res.paths))
    assert np.array_equal(np.asarray(ref.occ), np.asarray(res.occ))
    check_route(f.rr, f.term, res.paths, occ=res.occ)


@needs_mesh
@pytest.mark.slow
def test_shard_loss_demotes_to_single_chip():
    from parallel_eda_tpu.resil import FaultPlan, Resilience, ResilOpts
    base = _baseline()
    f = _flow()
    old = set_metrics(MetricsRegistry())
    try:
        rt = Resilience(ResilOpts(
            fault_plan=FaultPlan(7, {"backend.loss": (1, 2)})))
        res = Router(f.rr, RouterOpts(batch_size=32, mesh_shards=4,
                                      resil=rt)).route(f.term)
        assert res.success
        assert res.wirelength == base.wirelength
        assert np.array_equal(np.asarray(base.paths),
                              np.asarray(res.paths))
        assert np.array_equal(np.asarray(base.occ),
                              np.asarray(res.occ))
        check_route(f.rr, f.term, res.paths, occ=res.occ)
        assert rt.ladder.name("mesh") == "single_chip", \
            rt.ladder.snapshot()
        assert "backend.loss" in rt.plan.fired_sites()
        mv = get_metrics().values("route.mesh.")
        assert (mv.get("route.mesh.mesh_demotions") or 0) >= 1
        assert mv.get("route.mesh.n_shards") == 1
    finally:
        set_metrics(old)


# ---- geometry / byte model (no mesh needed) ------------------------

def test_row_block_cols_covers_padded_extent():
    pg = _small_pg()
    _, NX, _ = pg.shape_x
    for s in (2, 3, 4, 5, 7):
        kx = row_block_cols(pg, s)
        assert kx >= 2                       # chany 2-col slab fits
        assert s * kx >= NX + 2              # padded extent covered


def test_halo_byte_model_dtype_aware():
    pg = _small_pg()
    W, NX, NYp1 = pg.shape_x
    _, NXp1, NY = pg.shape_y
    B = 4
    for s in (2, 4):
        f32 = halo_bytes_per_sweep(pg, B, s, "f32")
        bf16 = halo_bytes_per_sweep(pg, B, s, "bf16")
        assert f32 == (s - 1) * B * W * (2 * NYp1 + 3 * NY) * 4
        assert bf16 * 2 == f32               # bf16 = 0.5x f32, exactly
    assert plane_itemsize("bf16") * 2 == plane_itemsize("f32")


def test_modeled_overlap_frac():
    pg = _small_pg()
    assert modeled_overlap_frac(pg, 4, 4, "ppermute") == 0.0
    assert modeled_overlap_frac(pg, 4, 4, "single_chip") == 0.0
    ov = modeled_overlap_frac(pg, 4, 4, "pallas_halo")
    assert 0.0 < ov <= 1.0


def test_make_row_mesh_validation():
    with pytest.raises(ValueError, match=">= 2"):
        make_row_mesh(1)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        make_row_mesh(jax.device_count() + 1)
    if jax.device_count() >= 2:
        with pytest.raises(ValueError):
            make_row_mesh(2, impl="bogus")
    from parallel_eda_tpu.route.planes_shard import MESH_IMPLS
    assert "ppermute" in MESH_IMPLS and "pallas_halo" in MESH_IMPLS


def test_ladder_has_mesh_dimension():
    from parallel_eda_tpu.resil.ladder import DIMS, _LABEL_DIM
    assert DIMS["mesh"] == ("pallas_halo", "ppermute", "single_chip")
    for label in DIMS["mesh"]:
        assert _LABEL_DIM[label] == "mesh"


def test_router_rejects_mesh_with_legacy_mesh():
    from parallel_eda_tpu.parallel.shard import make_mesh
    f = _flow()
    legacy = make_mesh(1, shape=(1, 1))
    with pytest.raises(ValueError, match="mutually exclusive"):
        Router(f.rr, RouterOpts(batch_size=32, mesh_shards=2),
               mesh=legacy)


# ---- parallel.shard.make_mesh validation (satellite) ----------------

def test_make_mesh_rejects_1d_shape():
    from parallel_eda_tpu.parallel.shard import make_mesh
    # used to escape as IndexError on shape[1]
    with pytest.raises(ValueError, match="2-D"):
        make_mesh(shape=(4,))


def test_make_mesh_rejects_bad_axes():
    from parallel_eda_tpu.parallel.shard import make_mesh
    with pytest.raises(ValueError, match="positive"):
        make_mesh(shape=(0, 1))
    with pytest.raises(ValueError, match="2-D"):
        make_mesh(shape=(1, 1, 1))
    with pytest.raises(ValueError, match="devices"):
        make_mesh(n_devices=jax.device_count() + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_mesh(n_devices=0)


def test_make_mesh_product_mismatch_message():
    from parallel_eda_tpu.parallel.shard import make_mesh
    n = jax.device_count()
    with pytest.raises(ValueError, match="needs"):
        make_mesh(shape=(n + 1, 2))


def test_make_mesh_both_axis_orders():
    from parallel_eda_tpu.parallel.shard import NET, NODE, make_mesh
    n = jax.device_count()
    m = make_mesh(n, shape=(n, 1))
    assert m.shape[NET] == n and m.shape[NODE] == 1
    m = make_mesh(n, shape=(1, n))
    assert m.shape[NET] == 1 and m.shape[NODE] == n


# ---- corpus n_shards field (satellite) ------------------------------

def _runstore():
    spec = importlib.util.spec_from_file_location(
        "runstore_mesh_test",
        os.path.join(REPO, "parallel_eda_tpu", "obs", "runstore.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_runstore_n_shards_field():
    rs = _runstore()
    rec = rs.make_record("mesh_test", {"x": 1}, "nets_per_sec", 1.0,
                         "nets/s", "cpu", "host", n_shards=4,
                         rev="deadbeef")
    assert rec["n_shards"] == 4
    assert rs.validate_record(rec) == []
    # absent = single-device, still valid (v1/v2 compat)
    rec2 = rs.make_record("mesh_test", {"x": 1}, "nets_per_sec", 1.0,
                          "nets/s", "cpu", "host", rev="deadbeef")
    assert "n_shards" not in rec2
    assert rs.validate_record(rec2) == []
    # wrong types are rejected
    bad = dict(rec, n_shards="4")
    assert any("n_shards" in e for e in rs.validate_record(bad))
    bad = dict(rec, n_shards=True)
    assert any("n_shards" in e for e in rs.validate_record(bad))


# ---- flow_doctor mesh rules (satellite) -----------------------------

def _flow_doctor():
    spec = importlib.util.spec_from_file_location(
        "flow_doctor_mesh_test",
        os.path.join(REPO, "tools", "flow_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flow_doctor_halo_implies_shards():
    fd = _flow_doctor()
    # halo traffic on a single-device row: the ledger is lying
    errs, _ = fd.check_mesh_row(
        {"gauges": {"route.mesh.halo_bytes": 1024}})
    assert errs and "halo" in errs[0]
    errs, _ = fd.check_mesh_row(
        {"gauges": {"route.mesh.halo_bytes": 1024,
                    "route.mesh.n_shards": 1}})
    assert errs
    # consistent rows pass, via either the field or the gauge
    errs, notes = fd.check_mesh_row(
        {"n_shards": 4,
         "gauges": {"route.mesh.halo_bytes": 1024}})
    assert not errs and notes
    errs, _ = fd.check_mesh_row(
        {"gauges": {"route.mesh.halo_bytes": 1024,
                    "route.mesh.n_shards": 2}})
    assert not errs
    # no halo traffic: nothing to say
    errs, notes = fd.check_mesh_row({"gauges": {}})
    assert not errs and not notes


def test_flow_doctor_mesh_demotion_is_a_cause():
    fd = _flow_doctor()
    doc = {"resil": {"metrics": {
        "route.resil.quarantined_variants": 1,
        "route.resil.degradation_steps": 1,
        "route.mesh.mesh_demotions": 1,
    }}, "jobs": []}
    errs, _ = fd.check_resil(doc)
    assert not errs, errs
    # without the demotion counter the same doc is a lying ladder
    doc["resil"]["metrics"].pop("route.mesh.mesh_demotions")
    errs, _ = fd.check_resil(doc)
    assert errs
