"""Ask the chip's compiler (on-chip-measurement guide, section 2.3).

The TPU compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED, not attached: these tests lower the main path's
programs for a ``v5e:2x2`` topology and keep the compiler's answers.
A compile that passes is not a chip run — nothing executes, so nothing
here says anything about results or times.

All in ONE file (one xdist worker loads the TPU library and keeps its
lock), the topology described inside a module-scoped fixture (never at
import), compiled in the test's own process, compilation cache off
around the compiles.
"""

import functools
import unittest.mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from parallel_eda_tpu.obs.devprof import DevProfiler, _avatarize

# the chip_smoke `route` phase's real canvas: synth_flow(1200, W=20)
# sizes a 25 x 25 grid and routes with the default batch of 64; its
# crop ladder uses the 16 x 16 tile and the full canvas (the dispatch
# variants of that route, PR 22 rehearsal)
ROUTE_NX, ROUTE_W, ROUTE_B, ROUTE_TILE, ROUTE_SWEEPS = 25, 20, 64, 16, 16
HBM_BYTES = 16 * 1024 ** 3          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def row_mesh(topo):
    from parallel_eda_tpu.route.planes_shard import ROW_AXIS
    return Mesh(np.array(topo.devices[:4]), (ROW_AXIS,))


def _on(tree, sharding):
    """Give every shape avatar of ``tree`` the described sharding."""
    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        return x
    return jax.tree_util.tree_map(leaf, tree)


@functools.lru_cache(maxsize=None)
def _planes(nx: int, W: int):
    """PlanesGraph of an nx x nx device, from shapes alone (no route)."""
    from parallel_eda_tpu.arch.builtin import minimal_arch
    from parallel_eda_tpu.route.planes import build_planes
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import DeviceGrid

    arch = minimal_arch(chan_width=W)
    return build_planes(
        build_rr_graph(arch, DeviceGrid(nx, nx, arch.io_capacity)))


def _relax_avatars(pg, B, sharding):
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    args = (S((B, pg.ncells), f32), S((B, pg.ncells), f32),
            S((B, 1, 1, 1), f32), S((B, pg.ncells), f32))
    return _on((_avatarize(pg),) + args, sharding)


def _fits_hbm(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes + ma.generated_code_size_in_bytes)
    assert total < HBM_BYTES, f"{total} B does not fit one v5e chip"
    return ma.temp_size_in_bytes


# ---- the default path's kernels at the route phase's real canvas ---

def test_planes_relax_compiles_at_route_canvas(one_chip):
    from parallel_eda_tpu.route.planes import planes_relax

    pg = _planes(ROUTE_NX, ROUTE_W)
    fn = jax.jit(planes_relax, static_argnames=("nsweeps",))
    compiled = fn.lower(*_relax_avatars(pg, ROUTE_B, one_chip),
                        nsweeps=ROUTE_SWEEPS).compile()
    _fits_hbm(compiled)


def test_planes_relax_cropped_compiles_at_route_tile(one_chip):
    from parallel_eda_tpu.route.planes import planes_relax_cropped

    pg = _planes(ROUTE_NX, ROUTE_W)
    origin = jax.ShapeDtypeStruct((ROUTE_B,), jnp.int32, sharding=one_chip)
    fn = jax.jit(planes_relax_cropped,
                 static_argnames=("nsweeps", "cnx", "cny"))
    compiled = fn.lower(*_relax_avatars(pg, ROUTE_B, one_chip),
                        nsweeps=ROUTE_SWEEPS, ox=origin, oy=origin,
                        cnx=ROUTE_TILE, cny=ROUTE_TILE).compile()
    _fits_hbm(compiled)


def _while_loops(compiled) -> int:
    """The ``while`` instructions of a compiled program's text."""
    import re

    return len(re.findall(r"^\s*(?:ROOT )?%\S+ = .* while\(",
                          compiled.as_text(), re.M))


def test_the_cropped_relaxation_loops_over_its_sweeps_alone(one_chip):
    """The v5e compiler's program of planes_relax_cropped at the route
    tile holds ONE ``while``, the relaxation's: no cut and no write-back
    is a loop over the batch.  The per-net dynamic slices it replaced
    (tests/crop_refs.py) compile to one loop a cut and a put, 27 more:
    the guard that keeps them from coming back unnoticed, and the proof
    that it would see them."""
    from crop_refs import planes_relax_cropped_vmap
    from parallel_eda_tpu.route.planes import planes_relax_cropped

    pg = _planes(ROUTE_NX, ROUTE_W)
    origin = jax.ShapeDtypeStruct((ROUTE_B,), jnp.int32, sharding=one_chip)

    def loops(relax):
        fn = jax.jit(relax, static_argnames=("nsweeps", "cnx", "cny"))
        return _while_loops(fn.lower(
            *_relax_avatars(pg, ROUTE_B, one_chip), nsweeps=ROUTE_SWEEPS,
            ox=origin, oy=origin, cnx=ROUTE_TILE, cny=ROUTE_TILE).compile())

    assert loops(planes_relax_cropped) == 1
    assert loops(planes_relax_cropped_vmap) == 1 + 15 + 6 + 6


def _unfused_scan_pads(compiled) -> int:
    """The ``pad`` instructions of a compiled program that stand outside
    every fusion (an instruction of their own: a pass over a canvas each)
    and whose ``op_name`` lies under ``route.dev.relax.scan``."""
    import re

    pads = 0
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()",
                         compiled.as_text()):
        head, _, body = comp.partition("\n")
        if "fused_computation" in head.split("(")[0]:
            continue
        pads += len(re.findall(
            r"^\s*(?:ROOT )?%\S+ = \S+ pad\(.*"
            r'op_name="[^"]*route\.dev\.relax\.scan', body, re.M))
    return pads


def test_the_relaxations_scans_interleave_by_no_pad(one_chip):
    """The v5e compiler's program of planes_relax at the route canvas
    holds NO unfused ``pad`` under ``route.dev.relax.scan``: the scan's
    odd-even tree runs on slabs and joins them once.  The whole-array
    form it replaced (tests/scan_refs.py) interleaves a level's halves
    by two interior pads and an add, none of which the compiler fuses:
    the guard that keeps them from coming back unnoticed, and the proof
    that it would see them."""
    from parallel_eda_tpu.route.planes import planes_relax
    from scan_refs import minplus_scan_assoc, scan_form

    pg = _planes(ROUTE_NX, ROUTE_W)

    def pads():
        # a function object a call: jit keeps a trace by its function
        fn = jax.jit(lambda *a, nsweeps: planes_relax(*a, nsweeps=nsweeps),
                     static_argnames=("nsweeps",))
        return _unfused_scan_pads(fn.lower(
            *_relax_avatars(pg, ROUTE_B, one_chip),
            nsweeps=ROUTE_SWEEPS).compile())

    assert pads() == 0
    with scan_form(minplus_scan_assoc) as traced:
        assert pads() == 28     # 24 at 22 x 22: a level more at 25 / 26
    assert len(traced) == 4         # one sweep body, four scans


def test_the_walk_scatters_are_one_loop_and_no_copy_of_themselves(one_chip):
    """The v5e compiler's program of planes.walk_scatters at
    route_relaxed's wave (B 64, G 8, Kw 188, 20,240 cells) holds ONE
    ``while`` more than one scatter each over the whole budget
    (tests/walk_refs.py) and the same number of scatters: the trips are
    a loop, not a ladder of copies."""
    import re

    from parallel_eda_tpu.route.planes import walk_scatters
    from walk_refs import walk_scatters_dense

    B, G, Kw, ncells = 64, 8, 188, 20240

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (a((B, ncells + 1), jnp.float32), a((B, G, Kw + 4), jnp.int32),
            a((B, G, Kw), jnp.int32), a((B, G, Kw), jnp.float32),
            a((B, G, Kw), jnp.int32), a((B, G, Kw), jnp.bool_),
            a((B, G, Kw), jnp.int32))

    def counts(form):
        compiled = jax.jit(form).lower(*args).compile()
        _fits_hbm(compiled)
        return (_while_loops(compiled),
                len(re.findall(r" scatter\(", compiled.as_text())))

    loops, scatters = counts(walk_scatters)
    loops_dense, scatters_dense = counts(walk_scatters_dense)
    assert loops == loops_dense + 1
    assert scatters == scatters_dense == 2


def test_the_mesh_form_of_the_walk_scatters_holds_no_collective(topo):
    """Under a GSPMD mesh whose 'net' axis shards the batch, the wave
    runs planes.walk_scatters_dense: B stays a batch dimension of both
    scatters and the v5e partitioner adds no collective (the flat stores
    of walk_scatters cost it all-gathers and all-reduces there)."""
    import re

    from parallel_eda_tpu.route.planes import walk_scatters_dense

    B, G, Kw, ncells = 64, 8, 188, 20240
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("net", "node"))

    def by_net(ndim):
        return NamedSharding(mesh, P("net", *(None,) * (ndim - 1)))

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=by_net(len(shape)))

    def form(*args):
        buf, seg, slots = walk_scatters_dense(*args)
        return (jax.lax.with_sharding_constraint(buf, by_net(2)),
                jax.lax.with_sharding_constraint(seg, by_net(3)), slots)

    text = jax.jit(form).lower(
        a((B, ncells + 1), jnp.float32), a((B, G, Kw + 4), jnp.int32),
        a((B, G, Kw), jnp.int32), a((B, G, Kw), jnp.float32),
        a((B, G, Kw), jnp.int32), a((B, G, Kw), jnp.bool_),
        a((B, G, Kw), jnp.int32)).compile().as_text()
    assert not re.findall(
        r" (?:all-gather|all-reduce|all-to-all|collective-permute)"
        r"(?:-start)?\(", text)
    assert len(re.findall(r" scatter\(", text)) == 2


@pytest.mark.parametrize("arch_fn, n, W, tile, guard", [
    ("k6_n10_40nm_arch", 11, 64, 8, False),     # route_k6n10_relaxed
    # route_scale: the one populated rung, 16 x 16
    ("k6_n10_40nm_arch", 19, 88, 16, False),
    # route_hetero: typed columns and tall blocks under the same wires
    ("k6_frac_n10_mem32k_40nm_arch", 25, 64, 16, False),
    # route_scale_6k: 123,552 cells a net, as its route starts and with
    # the scans guarded, as its last windows run
    ("k6_n10_40nm_arch", 26, 88, 16, False),
    ("k6_n10_40nm_arch", 26, 88, 16, True),
])
def test_directional_planes_relax_compiles_at_k6n10_canvas(
        one_chip, arch_fn, n, W, tile, guard):
    """The directional relaxation (unidir graphs: group-min turns) at
    the canvases of the cells ``route_k6n10_relaxed`` (11 x 11, W = 64),
    ``route_scale`` (19 x 19, W = 88), ``route_hetero`` (25 x 25,
    W = 64, on the heterogeneous device) and ``route_scale_6k``
    (26 x 26, W = 88; plain and with ``scan_guard``, the roll and the
    two compares a scan) of length-4 single-driver wires, 64 nets --
    whole and cropped."""
    import warnings

    from parallel_eda_tpu.arch import builtin
    from parallel_eda_tpu.route.planes import (build_planes, planes_relax,
                                               planes_relax_cropped)
    from parallel_eda_tpu.rr.graph import build_rr_graph
    from parallel_eda_tpu.rr.grid import make_grid

    arch = getattr(builtin, arch_fn)(chan_width=W)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the file asks for Wilton
        pg = build_planes(build_rr_graph(arch, make_grid(arch, n, n)))
    assert pg.shape_x[:2] == (W, n)
    assert pg.directional and pg.group_tracks == 8 and pg.max_span == 4
    pg = pg.replace(scan_guard=guard)
    fn = jax.jit(planes_relax, static_argnames=("nsweeps",))
    _fits_hbm(fn.lower(*_relax_avatars(pg, ROUTE_B, one_chip),
                       nsweeps=ROUTE_SWEEPS).compile())
    origin = jax.ShapeDtypeStruct((ROUTE_B,), jnp.int32, sharding=one_chip)
    fn = jax.jit(planes_relax_cropped,
                 static_argnames=("nsweeps", "cnx", "cny"))
    _fits_hbm(fn.lower(*_relax_avatars(pg, ROUTE_B, one_chip),
                       nsweeps=ROUTE_SWEEPS, ox=origin, oy=origin,
                       cnx=tile, cny=tile).compile())


# ---- the conflict colouring's two forms under their cond -----------

def _hlo_computations(text):
    """{name: its lines} of a compiled module's text, and each
    computation's callees."""
    import re

    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    calls = {n: set(re.findall(r"%([\w.\-]+)", " ".join(
        re.findall(r"(?:calls|to_apply|body|condition|"
                   r"branch_computations|true_computation|"
                   r"false_computation)=\{?([^}\s,]*(?:, [^}\s,]*)*)",
                   " ".join(lines))))) & set(comps)
             for n, lines in comps.items()}
    return comps, calls


def test_the_short_colouring_reads_the_store_by_compares_alone(one_chip):
    """`planes.window_colours` at `route_relaxed`'s path store (962 x 12
    x 128 slots, 29,656 nodes, topk 4,096) before the v5e compiler:
    ONE conditional on the rung's flag around ONE on the count of
    overused nodes; the short branch holds a loop, no sort, no scatter
    and no array of store x width elements (378 M at the width 256); the
    full branch holds the scatter and the sort of as many indices as the
    store has slots (the guard sees the form it guards against)."""
    import re
    import types

    from parallel_eda_tpu.route import planes

    R, S, L, N, topk = 962, 12, 128, 29656, 4096
    a = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def colours(cap, occ, paths, reached, read):
        dev = types.SimpleNamespace(num_nodes=N, capacity=cap)
        return planes.window_colours(dev, occ, paths, reached, topk, 5, read)

    compiled = jax.jit(colours).lower(
        a((N,), jnp.int32), a((N,), jnp.int32), a((R, S, L), jnp.int32),
        a((R,), jnp.bool_), a((), jnp.bool_)).compile()
    comps, calls = _hlo_computations(compiled.as_text())

    def reach(name):
        seen, todo = set(), [name]
        while todo:
            n = todo.pop()
            if n not in seen:
                seen.add(n)
                todo.extend(calls[n])
        return "\n".join(line for n in sorted(seen) for line in comps[n])

    conds = [line for lines in comps.values() for line in lines
             if " conditional(" in line]
    assert len(conds) == 2
    branches = [b for line in conds for b in re.findall(
        r"%([\w.\-]+)", re.search(
            r"(?:branch_computations=\{([^}]*)\}|"
            r"true_computation=(\S+), false_computation=(\S+))",
            line).group(0))]
    texts = [reach(b) for b in branches if b in comps]
    # three branches hold no conditional of their own: the skipped
    # rung's zeros, and the inner cond's two
    Ks = planes.mis_short_width(topk)
    leaves = [t for t in texts if " conditional(" not in t]
    assert len(leaves) == 3
    (short,) = [t for t in leaves if f"pred[{Ks},{R}]" in t]
    (full,) = [t for t in leaves if " sort(" in t]
    assert " while(" in short
    assert " sort(" not in short and " scatter(" not in short
    assert " scatter(" in full
    assert f"[{R * S * L}]" in full.replace(",", "")
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"\w+\[([\d,]+)\]", short)]
    assert max(sizes) == R * S * L < R * S * L * Ks
    assert f"pred[{Ks},{R}]" in short
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


# ---- the whole window program once, small --------------------------

def test_route_window_program_compiles_for_every_bench_variant(one_chip):
    """The code AROUND the kernels (fused STA, _mis_colors, traceback,
    the while_loop exit): every dispatch variant of the 60-LUT bench
    route, from the shape avatars obs/devprof keeps of a CPU route."""
    from parallel_eda_tpu.flow import run_route, synth_flow
    from parallel_eda_tpu.obs import get_devprof, set_devprof
    from parallel_eda_tpu.route.router import RouterOpts

    prev = get_devprof()
    prof = set_devprof(DevProfiler(enabled=True))
    try:
        # bench.py's default config (bench.build)
        flow = synth_flow(num_luts=60, num_inputs=12, num_outputs=12,
                          chan_width=12, seed=11)
        run_route(flow, RouterOpts(batch_size=64), timing_driven=True)
    finally:
        set_devprof(prev)
    assert flow.route.success
    pending = prof._pending
    assert pending, "the route noted no dispatch variant"
    for key, _meta, fn, args, kwargs in pending:
        args, kwargs = _on((args, kwargs), one_chip)
        compiled = fn.lower(*args, **kwargs).compile()
        _fits_hbm(compiled)


def test_fused_sta_compiles_with_route_dsps_wide_junctions(one_chip):
    """``sta_crit`` at ``route_dsp``'s full-size timing graph (6,989
    nodes, 12 levels, 18 junctions whose 30 in-edges past the table's
    six columns lie in the flat list): the scatter-max fold under
    ``route.dev.sta.wide_fold`` before the v5e compiler, plain and in
    SDC mode, and named in the compiled program."""
    import os
    import sys
    import warnings

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import harness, problem
    from parallel_eda_tpu.timing.graph import build_timing_graph
    from parallel_eda_tpu.timing.sta import sta_crit, to_device

    cell = harness.load_cell(harness.load_manifest(repo), repo,
                             "route_dsp")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    tg = build_timing_graph(f.nl, f.pnl, f.term)
    assert tg.in_src.shape[1] == 6 and len(tg.in_overflow[0]) == 540
    dev = _on(_avatarize(to_device(tg)), one_chip)
    S = jax.ShapeDtypeStruct
    flat = S((tg.num_route_slots + 1,), jnp.float32, sharding=one_chip)
    seed = S((tg.num_tnodes,), jnp.float32, sharding=one_chip)
    for use_sdc in (False, True):
        fn = jax.jit(functools.partial(
            sta_crit, depth=tg.depth, use_sdc=use_sdc))
        compiled = fn.lower(dev, flat, req_seed=seed).compile()
        _fits_hbm(compiled)
        assert "route.dev.sta.wide_fold" in compiled.as_text()


# ---- the paths that exist only across chips ------------------------

def test_remote_slab_permute_compiles_on_four_chip_mesh(row_mesh):
    from parallel_eda_tpu.route.planes_shard import (ROW_AXIS,
                                                     remote_slab_permute)

    n = row_mesh.devices.size
    pg = _planes(ROUTE_NX, ROUTE_W)
    W, _, NYp1 = pg.shape_x
    # one dx halo slab per shard: [B, W, 1, NY+1]
    slab = jax.ShapeDtypeStruct(
        (n * ROUTE_B, W, 1, NYp1), jnp.float32,
        sharding=NamedSharding(row_mesh, P(ROW_AXIS)))
    for fwd in (True, False):
        fn = jax.jit(jax.shard_map(
            functools.partial(remote_slab_permute, axis_name=ROW_AXIS,
                              n_shards=n, fwd=fwd),
            mesh=row_mesh, in_specs=P(ROW_AXIS), out_specs=P(ROW_AXIS),
            check_vma=False))
        compiled = fn.lower(slab).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _sharded_relax(row_mesh, impl):
    from parallel_eda_tpu.route.planes_shard import (RowMesh,
                                                     planes_relax_sharded)

    pg = _planes(ROUTE_NX, ROUTE_W)
    rm = RowMesh(row_mesh, row_mesh.devices.size, impl)
    fn = jax.jit(planes_relax_sharded,
                 static_argnames=("nsweeps", "rmesh"))
    replicated = NamedSharding(row_mesh, P())
    return fn.lower(*_relax_avatars(pg, ROUTE_B, replicated),
                    nsweeps=ROUTE_SWEEPS, rmesh=rm).compile()


def test_planes_relax_sharded_spreads_over_four_chips(one_chip, row_mesh):
    """ppermute transport: the collectives are in the program and the
    per-device working set is a fraction of the one-device program's —
    nothing was put whole on the first device."""
    from parallel_eda_tpu.route.planes import planes_relax

    compiled = _sharded_relax(row_mesh, "ppermute")
    text = compiled.as_text()
    assert "collective-permute" in text      # the halo exchange
    assert "all-reduce" in text              # the global fixpoint vote
    sharded_temp = _fits_hbm(compiled)
    pg = _planes(ROUTE_NX, ROUTE_W)
    single = jax.jit(planes_relax, static_argnames=("nsweeps",)).lower(
        *_relax_avatars(pg, ROUTE_B, one_chip),
        nsweeps=ROUTE_SWEEPS).compile()
    single_temp = single.memory_analysis().temp_size_in_bytes
    assert sharded_temp < 0.5 * single_temp, (sharded_temp, single_temp)


def test_planes_relax_sharded_with_pallas_halo_transport(row_mesh):
    """The transport the router picks FIRST on a TPU backend
    (router._active_row_mesh): the whole sharded relaxation with the
    remote-DMA kernel in it.  planes_shard asks jax.default_backend(),
    which sees the CPU here, so the test steers it."""
    with unittest.mock.patch.object(jax, "default_backend",
                                    lambda: "tpu"):
        compiled = _sharded_relax(row_mesh, "pallas_halo")
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)
