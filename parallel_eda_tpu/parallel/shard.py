"""Multi-chip routing: net- and node-parallel sharding over a device Mesh.

TPU-native replacement for the reference's entire distributed stack
(SURVEY §2.8).  Two mesh axes map its two distribution strategies:

- "net" axis = the MPI flagship's net partitioning
  (mpi_route_load_balanced_nonblocking_send_recv_encoded.cxx:402): the
  batch of nets is split across devices; instead of broadcasting
  bit-packed rip-up/add path packets via nonblocking sends, the per-net
  usage masks are summed into a global occupancy delta by one
  deterministic psum over ICI.
- "node" axis = the rr-graph spatial partitioning
  (rr_graph_partitioner.h:840, mpi_spatial_route*.cxx): the graph's ELL
  arrays, congestion state, and the [B, N] search state are sharded over
  rr-nodes.  Where the reference maintains boundary nodes and pseudo
  sources/sinks (route.h:330-365) with explicit messaging, here the
  sharding annotations let XLA/GSPMD insert the halo communication for
  the pull-relaxation's cross-shard gathers (the scaling-book recipe:
  pick a mesh, annotate, let the compiler place collectives; a hand-tuned
  ppermute halo-exchange pallas kernel is a later optimization).

The full negotiation loop runs sharded: ``route.Router(rr, opts, mesh=m)``
keeps every whole-circuit array (occ/acc/paths/bbs) on the mesh across
iterations and dispatches the fused rip-up/route/commit/scatter step
(search.route_batch_resident, which constrains each batch's rows to the
"net" axis) per batch — the reference's complete iterating MPI router
(load rebalance, plateau shrink) maps to the Router's existing schedule +
re-jit on a smaller mesh.  Determinism is inherent: fixed mesh, fixed reduction order, and
every cross-shard reduction is an integer sum or an elementwise min —
sharded results are bit-identical to single-device (tested).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import get_metrics, span
from ..route.device_graph import DeviceRRGraph
from ..route.search import route_and_commit

NET, NODE = "net", "node"


def make_mesh(n_devices: Optional[int] = None,
              shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """2-D (net, node) mesh over the first devices.  shape=None puts all
    devices on the net axis (pure net parallelism).

    Multi-slice placement (the reference's MPI-over-cluster analogue,
    SURVEY §5.8): jax.devices() orders devices slice-major, so with
    shape=(num_slices * k, node_per_slice) the NODE axis (the
    bandwidth-hungry spatial canvas shard + its scan prefix exchanges)
    lands INSIDE each slice on ICI, while the NET axis — whose only
    cross-shard traffic is the one int32 occupancy psum per window —
    spans slices over DCN.  That is exactly the traffic split the
    reference engineered by hand with per-rank rr-graph partitions and
    packetized congestion broadcasts
    (mpi_route_load_balanced_nonblocking_send_recv_encoded.cxx); here it
    is an axis-ordering convention.  (Single-slice environments exercise the same
    code on a virtual CPU mesh; see tests/test_parallel.py.)"""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        if n_devices > len(devs):
            raise ValueError(f"asked for {n_devices} devices but only "
                             f"{len(devs)} are visible (on CPU hosts "
                             f"set XLA_FLAGS=--xla_force_host_platform"
                             f"_device_count={n_devices} before jax "
                             f"initialises)")
        devs = devs[:n_devices]
    n = len(devs)
    if shape is None:
        shape = (n, 1)
    shape = tuple(shape)
    # validate BEFORE any shape[i] access: a 1-tuple like (4,) used to
    # escape as an IndexError on shape[1] instead of a usable message
    if len(shape) != 2:
        raise ValueError(f"mesh shape must be 2-D (net, node), got "
                         f"{shape!r} with {len(shape)} axis(es)")
    if not all(isinstance(s, (int, np.integer)) and s >= 1
               for s in shape):
        raise ValueError(f"mesh shape axes must be positive ints, got "
                         f"{shape!r}")
    if shape[0] * shape[1] != n:
        raise ValueError(f"mesh shape {shape} needs "
                         f"{shape[0] * shape[1]} devices, have {n} "
                         f"(net axis {shape[0]} x node axis {shape[1]})")
    return Mesh(np.array(devs).reshape(shape), (NET, NODE))


def make_multislice_mesh(num_slices: int, chips_per_slice: int,
                         node_per_slice: int = 1) -> Mesh:
    """Explicit multi-slice (net, node) mesh (SURVEY §5.8, the MPI
    flagship's cluster deployment): `jax.devices()` orders devices
    slice-major, so reshaping (slices, net_per_slice, node) and folding
    the first two axes puts every NODE-axis group (the spatial canvas
    shard + scan prefix exchanges — the bandwidth-hungry traffic)
    INSIDE one slice on ICI, while the NET axis (one int32 occupancy
    psum per window) is the only axis that crosses slices over DCN —
    the traffic split the reference engineered with per-rank rr-graph
    partitions + packetized congestion broadcasts
    (mpi_route_load_balanced_nonblocking_send_recv_encoded.cxx:402).

    Works identically on a virtual CPU mesh (tests) and real
    multi-slice topologies; sharded == single-device stays bit-exact
    because the mesh only changes WHERE the same deterministic
    reductions run."""
    if num_slices < 1 or chips_per_slice < 1 or node_per_slice < 1:
        raise ValueError("num_slices, chips_per_slice, node_per_slice "
                         "must all be >= 1")
    if chips_per_slice % node_per_slice:
        raise ValueError(f"chips_per_slice {chips_per_slice} not "
                         f"divisible by node_per_slice {node_per_slice}")
    total = num_slices * chips_per_slice
    devs = jax.devices()
    if len(devs) < total:
        raise ValueError(f"need {total} devices, have {len(devs)}")
    # validate the guarantee itself against the devices' REAL slice
    # membership where the backend exposes it (multi-slice TPU
    # runtimes set slice_index; virtual CPU meshes don't — there the
    # layout is a pure convention and nothing can cross a real DCN):
    # every NODE-axis row of the grid must live on one slice
    slice_ids = [getattr(d, "slice_index", None) for d in devs[:total]]
    if all(s is not None for s in slice_ids):
        for r in range(total // node_per_slice):
            row = slice_ids[r * node_per_slice:(r + 1) * node_per_slice]
            if len(set(row)) > 1:
                raise ValueError(
                    f"node-axis row {r} spans slices {sorted(set(row))}"
                    f": the canvas-shard traffic would cross DCN; "
                    f"check num_slices/chips_per_slice against the "
                    f"real topology")
    return make_mesh(total, shape=(total // node_per_slice,
                                   node_per_slice))


def shard_graph(dev: DeviceRRGraph, mesh: Mesh) -> DeviceRRGraph:
    """Place the rr-graph on the mesh: ELL tables + node properties are
    sharded over the "node" axis (the rr_graph_partitioner.h:840 spatial
    partition, minus the boundary-node bookkeeping GSPMD makes moot)."""
    s_node = NamedSharding(mesh, P(NODE))
    s_node_ell = NamedSharding(mesh, P(NODE, None))
    put = jax.device_put
    return DeviceRRGraph(
        ell_src=put(dev.ell_src, s_node_ell),
        ell_delay=put(dev.ell_delay, s_node_ell),
        ell_valid=put(dev.ell_valid, s_node_ell),
        cong_base=put(dev.cong_base, s_node),
        capacity=put(dev.capacity, s_node),
        xlow=put(dev.xlow, s_node),
        xhigh=put(dev.xhigh, s_node),
        ylow=put(dev.ylow, s_node),
        yhigh=put(dev.yhigh, s_node),
        is_wire=put(dev.is_wire, s_node),
        la_axis=put(dev.la_axis, s_node),
        la_len_same=put(dev.la_len_same, s_node),
        la_len_ortho=put(dev.la_len_ortho, s_node),
        la_tlin_same=put(dev.la_tlin_same, s_node),
        la_tlin_ortho=put(dev.la_tlin_ortho, s_node),
    )


class ShardedRouter:
    """Binds a (net, node) mesh to the fused single-step route kernel
    (search.route_and_commit) via input shardings; GSPMD propagates them
    through the jitted program.  For the complete negotiation loop use
    route.Router(..., mesh=mesh), which runs the device-resident variant
    (search.route_batch_resident) under the same mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.s_batch = NamedSharding(mesh, P(NET))          # [B, ...]
        self.s_node = NamedSharding(mesh, P(NODE))          # [N]

    def shard_graph(self, dev: DeviceRRGraph) -> DeviceRRGraph:
        return shard_graph(dev, self.mesh)

    def route_step(self, dev: DeviceRRGraph, occ, acc, pres_fac,
                   prev_paths, source, sinks, bb, crit, net_key, valid,
                   max_steps: int, max_len: int, num_waves: int,
                   group: int = 1):
        """Batch size must be divisible by the mesh's net-axis size."""
        B = source.shape[0]
        n_net = self.mesh.shape[NET]
        if B % n_net:
            raise ValueError(f"batch {B} not divisible by net axis "
                             f"{n_net}")
        # per-device-step telemetry: the span covers shard placement +
        # dispatch (the device work itself is async; a following fetch
        # shows as the caller's sync time), the gauges record the mesh
        # decomposition every step ran under
        reg = get_metrics()
        reg.counter("shard.route_steps").inc()
        reg.gauge("shard.batch_per_device").set(B // n_net)
        reg.gauge("shard.mesh_net").set(int(n_net))
        reg.gauge("shard.mesh_node").set(int(self.mesh.shape[NODE]))
        with span("shard.route_step", cat="parallel", batch=int(B),
                  net_axis=int(n_net),
                  node_axis=int(self.mesh.shape[NODE])):
            put = jax.device_put
            prev_paths = put(prev_paths, self.s_batch)
            source = put(source, self.s_batch)
            sinks = put(sinks, self.s_batch)
            bb = put(bb, self.s_batch)
            crit = put(crit, self.s_batch)
            net_key = put(net_key, self.s_batch)
            valid = put(valid, self.s_batch)
            occ = put(occ, self.s_node)
            acc = put(acc, self.s_node)
            return route_and_commit(
                dev, occ, acc, pres_fac, prev_paths, source, sinks, bb,
                crit, net_key, valid, max_steps, max_len, num_waves,
                group)
