"""Static timing analysis on the device.

Replaces the reference's recursive/levelized CPU sweeps
(vpr/SRC/timing/path_delay.c:1994 do_timing_analysis_new, :3791
get_critical_path_delay) with max-plus / min-plus ELL relaxations: ``depth``
dense sweeps over the in-/out-edge tables converge exactly on a DAG of that
depth, and every sweep is one [T, D] gather + reduce — the same shape the
router's relaxation uses, so XLA fuses it well.

Per-connection criticality  crit = (1 - slack/Dmax) ** exp  (semantics of
vpr/SRC/route/route_timing.c:225-268 and timing_place.c:81
load_criticalities) is scattered back to the router's [R, Smax] layout with
a max-reduce, closing the analyze_timing -> update_sink_criticalities loop
(parallel_route/router.cxx:28,42).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..obs.trace import device_scope
from .graph import TimingGraph

NEG = -jnp.inf


@struct.dataclass
class DeviceTimingGraph:
    """``TimingGraph``'s arrays on the device, a pytree the window
    program takes as an argument.  T tnodes; D_in / D_out the ELLs'
    widths.

    in_src [T, D_in] int32, in_const [T, D_in] f32 (seconds),
    in_ridx [T, D_in] int32 (routed-delay slot, -1 = none: the delay
    vector's trailing zero), in_valid [T, D_in] bool: the in-edges, read
    by the forward (arrival) sweep and by the per-connection slacks.
    out_dst / out_const / out_ridx / out_valid [T, D_out]: the same
    edges by source, read by the backward (required) sweep.
    arrival0 [T] f32: the startpoints' seeds, -inf elsewhere.
    is_endpoint [T] bool.
    out_overflow / in_overflow: the edges the two ELLs do not hold, as
    flat lists; None is NO pytree leaf, so a graph without them has the
    structure, and the compiled programs, of one built before they
    existed."""
    in_src: jnp.ndarray
    in_const: jnp.ndarray
    in_ridx: jnp.ndarray
    in_valid: jnp.ndarray
    out_dst: jnp.ndarray
    out_const: jnp.ndarray
    out_ridx: jnp.ndarray
    out_valid: jnp.ndarray
    arrival0: jnp.ndarray
    is_endpoint: jnp.ndarray
    # TimingGraph.out_overflow: the out-edges past the ELL's width,
    # flat (src, dst, const, ridx); None (no leaf) where there are none
    out_overflow: Optional[Tuple[jnp.ndarray, ...]] = None
    # TimingGraph.in_overflow: the in-edges of combinational hard
    # blocks' junctions past the in-edge ELL's width, flat (dst, src,
    # const, ridx); None (no leaf) where there are none
    in_overflow: Optional[Tuple[jnp.ndarray, ...]] = None


def _on_device(edges) -> Optional[Tuple[jnp.ndarray, ...]]:
    return None if edges is None else tuple(jnp.asarray(a) for a in edges)


def to_device(tg: TimingGraph) -> DeviceTimingGraph:
    return DeviceTimingGraph(
        in_src=jnp.asarray(tg.in_src), in_const=jnp.asarray(tg.in_const),
        in_ridx=jnp.asarray(tg.in_ridx), in_valid=jnp.asarray(tg.in_valid),
        out_dst=jnp.asarray(tg.out_dst), out_const=jnp.asarray(tg.out_const),
        out_ridx=jnp.asarray(tg.out_ridx),
        out_valid=jnp.asarray(tg.out_valid),
        arrival0=jnp.asarray(tg.arrival0),
        is_endpoint=jnp.asarray(tg.is_endpoint),
        out_overflow=_on_device(tg.out_overflow),
        in_overflow=_on_device(tg.in_overflow),
    )


def sta_crit(dev: DeviceTimingGraph, route_delay: jnp.ndarray,
             depth: int, crit_exp: float = 1.0, max_crit: float = 0.99,
             req_seed: jnp.ndarray = None, use_sdc: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                        jnp.ndarray]:
    """Traceable STA core (jit-wrapped below as sta_sweep; also inlined
    into the router's fused window program, route/planes.py
    route_window_planes, so timing-driven negotiation needs no host
    round trip per iteration — the analyze_timing-every-iteration loop
    of the reference, path_delay.c:1994 via parallel_route/router.cxx:28,
    with the analysis running on device between PathFinder iterations).

    route_delay: flat [R*Smax + 1] routed per-connection delays with a
    trailing 0.0 slot so ridx == -1 gathers a zero.

    Single-clock mode (use_sdc=False, path_delay.c default): endpoint
    required time = the critical-path delay itself.  SDC mode: req_seed
    [T] carries each endpoint's clock-domain period (read_sdc.c
    constraint application); slacks may go negative and criticality
    saturates at max_crit.

    Returns (crit_flat [R*Smax], Dmax, worst_slack, arrival [T])."""
    rd = jnp.where(jnp.isfinite(route_delay), route_delay, 0.0)

    d_in = dev.in_const + rd[dev.in_ridx]          # [T, D] (-1 -> last slot)
    d_out = dev.out_const + rd[dev.out_ridx]
    if dev.out_overflow is not None:
        # the widest tnodes' out-edges past the ELL: one more candidate
        # a level each, folded in by a scatter-min (the same min, so the
        # required times are the full table's)
        o_src, o_dst, o_const, o_ridx = dev.out_overflow
        d_ovf = o_const + rd[o_ridx]

    if dev.in_overflow is not None:
        # the junctions' in-edges past the ELL: one more candidate a
        # level each, folded in by a scatter-max (the same max, so the
        # arrivals are the full table's)
        i_dst, i_src, i_const, i_ridx = dev.in_overflow
        d_iovf = i_const + rd[i_ridx]

    def fwd(_, arr):
        cand = arr[dev.in_src] + d_in
        cand = jnp.where(dev.in_valid, cand, NEG)
        new = jnp.maximum(dev.arrival0, cand.max(axis=1))
        if dev.in_overflow is not None:
            with device_scope("route.dev.sta.wide_fold"):
                new = new.at[i_dst].max(arr[i_src] + d_iovf)
        return new

    arr = jax.lax.fori_loop(0, depth, fwd, dev.arrival0)

    dmax = jnp.max(jnp.where(dev.is_endpoint, arr, NEG))
    dmax = jnp.where(jnp.isfinite(dmax), dmax, 0.0)

    if use_sdc:
        req0 = jnp.where(dev.is_endpoint, req_seed, jnp.inf)
        # each tnode's slack is normalised by the period of the DOMAIN
        # whose endpoint dominates its required time (per-constraint
        # analysis, read_sdc.c application): a fast clock's 95%-margin
        # connection must not saturate just because a slow clock exists
        per0 = jnp.where(dev.is_endpoint & jnp.isfinite(req_seed),
                         req_seed, 0.0)

        def bwd(_, st):
            req, per = st
            cand = jnp.where(dev.out_valid, req[dev.out_dst] - d_out,
                             jnp.inf)
            cper = jnp.where(dev.out_valid, per[dev.out_dst], 0.0)
            cand_all = jnp.concatenate([cand, req0[:, None]], axis=1)
            per_all = jnp.concatenate([cper, per0[:, None]], axis=1)
            j = jnp.argmin(cand_all, axis=1)
            new = jnp.take_along_axis(cand_all, j[:, None], axis=1)[:, 0]
            nper = jnp.take_along_axis(per_all, j[:, None], axis=1)[:, 0]
            if dev.out_overflow is not None:
                # an overflow edge that beats the ELL's best brings its
                # domain's period (the widest among equals)
                c_o = req[o_dst] - d_ovf
                best = new.at[o_src].min(c_o)
                wins = (c_o == best[o_src]) & (c_o < new[o_src])
                nper = jnp.where(best < new, jnp.zeros_like(per).at[
                    jnp.where(wins, o_src, per.shape[0])].max(
                    per[o_dst], mode="drop"), nper)
                new = best
            return new, nper

        req, per = jax.lax.fori_loop(0, depth, bwd, (req0, per0))
        denom = jnp.where(per > 0, per, jnp.maximum(dmax, 1e-30))[:, None]
    else:
        req0 = jnp.where(dev.is_endpoint, dmax, jnp.inf)

        def bwd(_, req):
            cand = req[dev.out_dst] - d_out
            cand = jnp.where(dev.out_valid, cand, jnp.inf)
            new = jnp.minimum(req0, cand.min(axis=1))
            if dev.out_overflow is not None:
                new = new.at[o_src].min(req[o_dst] - d_ovf)
            return new

        req = jax.lax.fori_loop(0, depth, bwd, req0)
        denom = jnp.maximum(dmax, 1e-30)

    worst = jnp.min(jnp.where(dev.is_endpoint & jnp.isfinite(req0),
                              req0 - arr, jnp.inf))
    worst = jnp.where(jnp.isfinite(worst), worst, 0.0)

    # per in-edge slack -> criticality, scattered to (net, sink) slots
    # max_crit clamp (VPR --max_criticality 0.99 default): a criticality of
    # exactly 1 would zero the congestion term and livelock negotiation
    slack = req[:, None] - arr[dev.in_src] - d_in          # [T, D]
    crit = jnp.clip(1.0 - slack / denom, 0.0, max_crit)
    if crit_exp != 1.0:
        crit = crit ** crit_exp
    ok = dev.in_valid & (dev.in_ridx >= 0) & jnp.isfinite(slack)
    RS = route_delay.shape[0] - 1
    idx = jnp.where(ok, dev.in_ridx, RS)
    crit_flat = jnp.zeros(RS + 1, jnp.float32).at[idx.ravel()].max(
        jnp.where(ok, crit, 0.0).ravel())
    if dev.in_overflow is not None:
        with device_scope("route.dev.sta.wide_fold"):
            slack_o = req[i_dst] - arr[i_src] - d_iovf
            crit_o = jnp.clip(1.0 - slack_o / (
                denom[i_dst, 0] if use_sdc else denom), 0.0, max_crit)
            if crit_exp != 1.0:
                crit_o = crit_o ** crit_exp
            ok_o = (i_ridx >= 0) & jnp.isfinite(slack_o)
            crit_flat = crit_flat.at[jnp.where(ok_o, i_ridx, RS)].max(
                jnp.where(ok_o, crit_o, 0.0))
    return crit_flat[:RS], dmax, worst, arr


sta_sweep = functools.partial(jax.jit, static_argnames=(
    "depth", "crit_exp", "max_crit", "use_sdc"))(sta_crit)


class TimingAnalyzer:
    """Host wrapper: owns the device graph, exposes the router callback.

    ``sdc``: optional timing.sdc.SdcConstraints — switches the analysis
    to constrained mode (per-clock-domain required times, read_sdc.c
    application semantics); without it a single ideal clock normalised to
    the critical path is assumed (stock path_delay.c behavior)."""

    def __init__(self, tg: TimingGraph, crit_exp: float = 1.0,
                 max_crit: float = 0.99, sdc=None):
        self.tg = tg
        self.dev = to_device(tg)
        self.crit_exp = crit_exp
        self.max_crit = max_crit
        self.crit_path_delay = float("nan")
        self.worst_slack = float("nan")
        self.sdc = sdc
        self._req_seed = None
        self._last = None       # (flat delays, arrivals) of analyze()
        if sdc is not None:
            # a typo'd -clock reference must error, not silently fall
            # back to the default period (same contract as port names)
            declared = set(sdc.clock_periods) | set(sdc.virtual_clocks)
            for port, (clk, _d) in list(sdc.input_delays.items()) + \
                    list(sdc.output_delays.items()):
                if clk is not None and clk not in declared:
                    raise ValueError(
                        f"I/O delay on {port!r} references undeclared "
                        f"clock {clk!r}")
            req = np.full(tg.num_tnodes, np.inf, dtype=np.float32)
            default = sdc.default_period or np.inf
            for t in np.where(tg.is_endpoint)[0]:
                d = int(tg.endpoint_domain[t])
                cname = tg.domains[d] if d >= 0 else None
                p = sdc.period_of(cname) if d >= 0 else default
                p = p if p is not None else np.inf
                # set_multicycle_path -setup: the matching constraint
                # relaxes to N periods (read_sdc.c:50 application)
                if np.isfinite(p):
                    p = p * sdc.multicycle_for(cname)
                req[t] = p
            # set_output_delay (read_sdc.c:46): the external path eats
            # into the period — required time = N*period - delay
            for port, (clk, dly) in sdc.output_delays.items():
                t = (tg.outpad_tnode or {}).get(port)
                if t is None:
                    raise ValueError(
                        f"set_output_delay: unknown output port {port!r}")
                p = sdc.period_of(clk)
                p = p if p is not None else np.inf
                if np.isfinite(p):
                    req[t] = p * sdc.multicycle_for(clk) - dly
            self._req_seed = jnp.asarray(req)
            # set_input_delay (read_sdc.c:44): the input pad launches
            # after the external delay — arrival seed = delay
            if sdc.input_delays:
                arr0 = np.array(tg.arrival0, copy=True)
                for port, (clk, dly) in sdc.input_delays.items():
                    t = (tg.inpad_tnode or {}).get(port)
                    if t is None:
                        raise ValueError(
                            f"set_input_delay: unknown input port "
                            f"{port!r}")
                    arr0[t] = dly
                self.dev = self.dev.replace(arrival0=jnp.asarray(arr0))

    def analyze(self, sink_delay: np.ndarray) -> np.ndarray:
        """sink_delay [R, Smax] from the router -> criticalities [R, Smax];
        also records crit_path_delay and (SDC mode) worst_slack, both in
        seconds."""
        R, Smax = sink_delay.shape
        slots = self.tg.route_slots
        if slots is None:
            flat = np.append(sink_delay.ravel().astype(np.float32), 0.0)
        else:
            # several fanout classes: the classes' tables end to end
            flat = np.zeros(self.tg.num_route_slots + 1, np.float32)
            flat[slots[slots >= 0]] = sink_delay[slots >= 0]
        crit, dmax, worst, arr = sta_sweep(
            self.dev, jnp.asarray(flat), self.tg.depth, self.crit_exp,
            self.max_crit, req_seed=self._req_seed,
            use_sdc=self._req_seed is not None)
        self._last = (flat, arr)
        self.crit_path_delay = float(dmax)
        self.worst_slack = float(worst)
        if slots is None:
            return np.asarray(crit).reshape(R, Smax)
        return np.where(slots >= 0, np.asarray(crit)[slots], 0.0)

    def critical_path(self) -> list:
        """The tnodes of the last ``analyze``'s longest path, endpoint
        first: from the latest endpoint back along the in-edge (the
        ELL's or the overflow list's) that set each arrival, to a
        startpoint."""
        tg = self.tg
        flat, arr = self._last
        flat = np.where(np.isfinite(flat), flat, 0.0)
        arr = np.asarray(arr)
        ends = np.flatnonzero(tg.is_endpoint & np.isfinite(arr))
        if not len(ends):
            return []
        v = int(ends[np.argmax(arr[ends])])
        path = [v]
        for _ in range(tg.depth):
            src = tg.in_src[v][tg.in_valid[v]]
            d = (tg.in_const[v] + flat[tg.in_ridx[v]])[tg.in_valid[v]]
            if tg.in_overflow is not None:
                o_dst, o_src, o_const, o_ridx = tg.in_overflow
                m = o_dst == v
                src = np.concatenate([src, o_src[m]])
                d = np.concatenate([d, o_const[m] + flat[o_ridx[m]]])
            cand = arr[src] + d
            if not len(cand) or cand.max() <= tg.arrival0[v]:
                break               # the node's own seed set it
            v = int(src[np.argmax(cand)])
            path.append(v)
        return path

    def crit_path_hard_arcs(self) -> int:
        """Pin-to-pin arcs of combinational hard blocks on the last
        ``analyze``'s critical path (an edge OUT of a block's junction
        node); 0, and no walk, on a graph without such a block."""
        if self.tg.comb_junction is None:
            return 0
        return int(np.isin(self.critical_path()[1:],
                           self.tg.comb_junction).sum())

    def timing_cb(self, result) -> np.ndarray:
        """Router timing_cb hook (router.py Router.route); stamps the
        iteration's crit-path delay into its stats row (the analyze_timing
        -> iter_stats crit_path column, …cxx:6302-6318)."""
        crit = self.analyze(result.sink_delay)
        if result.stats:
            result.stats[-1].crit_path_delay = self.crit_path_delay
        return crit
