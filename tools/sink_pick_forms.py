"""Time the wave's sink pick ALONE, form by form, at the benchmark
cells' shapes (ROADMAP Queue 1 "How to price an item": a form timed
alone on the chip x a count of its calls).

    python3 tools/sink_pick_forms.py [--shapes route_hetero,...]
        [--reps 30] [--out chiprun_out/sink_pick_forms.json] [--allow-cpu]
    python3 tools/sink_pick_forms.py --occupancy route_hetero[,...]

For each shape (B, S, P, C, ncells): seeded tables and distances, then
the microseconds a call of

    dense      planes.sink_pick, every slot of the batch
    live@M     planes.sink_pick_live at each rung M of
               planes.live_pick_rungs(B, S), M slots live
    wave@p     planes.sink_pick_wave (the switch the window program
               runs) with a share p of the slots live, p in --shares

each as one jitted loop of ``--reps`` dependent calls under the host's
clock (the loop carries the distances, so no call is hoisted), the best
of three.  ``live@M``'s ratio to ``dense`` is what a rung must beat
(1.5x, ISSUE 38) to stay on the ladder.  Prints one JSON line a shape
and writes them all to ``--out``.  Refuses to run off the TPU (exit 2,
chip_smoke.py's rule) unless ``--allow-cpu`` asks for a rehearsal, whose
lines say ``"device": "cpu"`` and are no device numbers.

``--occupancy`` times nothing: it routes each named benchmark cell once,
with ``planes.sink_pick_wave`` wrapped in a host callback, and prints
how many waves of each batch shape sat on each rung of the ladder (the
last index is the dense pick).  Waves by rung x the form's microseconds
is what a rung buys in a route; program COUNTS, so any platform will do
(the line names it: a CPU route's trajectory can differ from the
chip's).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# (B, S, P, C, ncells): the six cells' narrow batches and route_fanout's
# wide class at two of its batch widths (ISSUE 38)
SHAPES = {
    "route_relaxed": (64, 8, 10, 80, 20240),
    "route_k6n10_relaxed": (64, 7, 33, 256, 16896),
    "route_tight": (64, 8, 10, 64, 16192),
    "route_scale": (64, 9, 33, 352, 66880),
    "route_hetero": (64, 13, 40, 256, 83200),
    "route_fanout": (64, 15, 33, 224, 47040),
    "route_fanout.wide16": (16, 204, 33, 224, 47040),
    "route_fanout.wide32": (32, 204, 33, 224, 47040),
}


def seeded_inputs(shape, seed: int):
    """(dist, pin_congj, crit_w, cw, sink_tabs) of one batch: a fifth of
    the (pin, cell) hops real (ranks a permutation of them), a tenth of
    the cells unreached."""
    import jax.numpy as jnp

    from parallel_eda_tpu.route.planes import RANK_PAD

    B, S, P, C, ncells = shape
    rng = np.random.default_rng(seed)
    dist = rng.uniform(1e-10, 1e-8, (B, ncells)).astype(np.float32)
    dist[rng.random((B, ncells)) < 0.1] = np.inf
    ucell = rng.integers(0, ncells, (B, S, C)).astype(np.int32)
    ucell[:, :, -(C // 5):] = ncells
    upin = rng.integers(0, 1000, (B, S, P)).astype(np.int32)
    pcdel = rng.uniform(1e-11, 1e-10, (B, S, P, C)).astype(np.float32)
    real = rng.random((B, S, P, C)) < 0.2
    pcrank = np.where(
        real, rng.permuted(np.broadcast_to(
            np.arange(P * C, dtype=np.int32), (B, S, P * C)),
            axis=2).reshape(B, S, P, C), RANK_PAD).astype(np.int32)
    pin_congj = rng.uniform(0.5, 2.0, (B, S, P)).astype(np.float32)
    crit_w = rng.uniform(0.0, 0.99, B).astype(np.float32)
    tabs = tuple(jnp.asarray(a) for a in (ucell, upin, pcdel, pcrank))
    return (jnp.asarray(dist), jnp.asarray(pin_congj),
            jnp.asarray(crit_w), jnp.asarray(1.0 - crit_w), tabs)


def live_mask(shape, count: int, seed: int):
    """``count`` live slots of the batch's B x S, seeded."""
    B, S = shape[:2]
    m = np.zeros(B * S, bool)
    m[np.random.default_rng(seed).permutation(B * S)[:count]] = True
    return m.reshape(B, S)


def timed_loop(form, reps: int):
    """``form(dist, *rest)`` -> (sink_dist, ent_cell, ent_ipin,
    ent_wdel) as a jitted loop of ``reps`` calls, each reading
    distances the one before it touched."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(dist, *rest):
        def body(_, d):
            sd, cell, ipin, wdel = form(d, *rest)
            # carry a dependence on every slot of every output, which
            # the compiler can neither fold away nor narrow
            used = (jnp.isfinite(sd).sum() + cell.sum() + ipin.sum()
                    + (wdel > 0).sum())
            return d.at[0, 0].add(jnp.where(used > 0, 1e-20, 0.0))
        return lax.fori_loop(0, reps, body, dist)

    return loop


def us_per_call(loop, args, reps: int) -> float:
    """Microseconds a call inside ``loop`` (timed_loop): the best of
    three timed runs after one that compiles."""
    loop(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        loop(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / reps


def time_shape(name: str, shares, reps: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from parallel_eda_tpu.route.planes import (live_pick_rungs, sink_pick,
                                               sink_pick_live,
                                               sink_pick_wave)

    shape = SHAPES[name]
    B, S, P, C, ncells = shape
    args = seeded_inputs(shape, seed)
    rungs = live_pick_rungs(B, S)
    row = {"shape": name, "B": B, "S": S, "P": P, "C": C,
           "ncells": ncells, "rungs": list(rungs),
           "device": jax.devices()[0].platform,
           "dense_us": us_per_call(timed_loop(sink_pick, reps), args,
                                   reps)}
    for M in rungs:
        rem = jnp.asarray(live_mask(shape, M, seed + 1))
        row[f"live@{M}_us"] = us_per_call(
            timed_loop(functools.partial(sink_pick_live, M=M), reps),
            args + (rem,), reps)
    wave = timed_loop(
        lambda *a: sink_pick_wave(*a, rungs=rungs)[:4], reps)
    for p in shares:
        rem = jnp.asarray(live_mask(shape, int(round(p * B * S)), seed + 2))
        row[f"wave@{p:g}_us"] = us_per_call(wave, args + (rem,), reps)
    return row


def rung_occupancy(workload: str) -> dict:
    """One route of the cell ``workload`` with the wave's pick wrapped:
    waves by (B, S, rung index), beside the route's own counters."""
    import collections

    import jax
    import jax.numpy as jnp

    from benchmark import harness, problem
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.route import planes

    waves = collections.Counter()
    built = planes.sink_pick_wave

    def wrapped(dist, pin_congj, crit_w, cw, sink_tabs, remaining, rungs):
        if rungs:
            # sink_pick_wave's own index, taken again beside it
            idx = jnp.sum(remaining.sum(dtype=jnp.int32)
                          > jnp.array(rungs), dtype=jnp.int32)
            key = remaining.shape + (tuple(rungs),)
            jax.debug.callback(
                lambda i: waves.update([key + (int(i),)]), idx)
        return built(dist, pin_congj, crit_w, cw, sink_tabs, remaining,
                     rungs)

    cell = harness.load_cell(harness.load_manifest(REPO), REPO, workload)
    f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    # a jitted program holds what it traced: none from before the patch
    # may run, and none with the patch in may outlive it
    jax.clear_caches()
    planes.sink_pick_wave = wrapped
    try:
        F.run_route(f, problem.router_opts(cell.config, {}),
                    timing_driven=bool(cell.config["router"]
                                       ["timing_driven"]), verify=False)
        jax.effects_barrier()
    finally:
        planes.sink_pick_wave = built
        jax.clear_caches()
    r = f.route
    return {"workload": workload, "device": jax.devices()[0].platform,
            "iterations": int(r.iterations), "windows": len(r.stats),
            "sweeps": int(r.total_relax_steps),
            "waves": int(r.total_waves),
            "sink_reads": int(r.total_sink_reads),
            "sink_reads_dense": int(r.total_sink_reads_dense),
            "by_rung": [{"B": B, "S": S, "rungs": list(rungs),
                         "rung": i, "waves": n}
                        for (B, S, rungs, i), n in sorted(waves.items())]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--shares", default="0.1,0.25,0.5,1.0")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "sink_pick_forms.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the TPU; the times mean nothing")
    ap.add_argument("--occupancy", default="",
                    help="cells to route once for their waves by rung")
    a = ap.parse_args(argv)
    if a.occupancy:
        for name in a.occupancy.split(","):
            print(json.dumps(rung_occupancy(name)), flush=True)
        return 0
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.allow_cpu:
        print(f"sink_pick_forms: the device is {platform!r}, not a TPU; "
              "a time from it is no device number (--allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    shares = [float(p) for p in a.shares.split(",")]
    rows = []
    for name in a.shapes.split(","):
        rows.append(time_shape(name, shares, a.reps, a.seed))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
