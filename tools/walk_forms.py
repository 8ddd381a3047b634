"""Time a wave's two walk scatters ALONE, form by form, at the benchmark
cells' shapes (ROADMAP Queue 1 "How to price an item": a form timed
alone on the chip x a count of its calls; the calls are
`RouteResult.total_waves`).

    python3 tools/walk_forms.py [--shapes route_relaxed,...] [--reps 20]
        [--chunks 8,16,32] [--out chiprun_out/walk_forms.json]
        [--allow-cpu]
    python3 tools/walk_forms.py --occupancy route_relaxed[,...]

A wave of `planes._step_core` walks back from its G picked sinks a net,
at most Kw steps each, and then scatters the walks' records twice: the
walked cells' delays into the tree buffer f32[B, ncells + 1] by `min`,
the walked nodes into the path rows s32[B, G, max_len]
(`planes.walk_scatters`).  For each shape (B, G, Kw, ncells, the cell's
mean steps a wave) and seeded walks (tests/walk_refs.py `seeded_walks`:
every walk a length drawn up to the wave's steps, the fill behind it,
the longest one kept), the microseconds a call of

    dense      ONE scatter each over all B x G x Kw records, the fill
               to the dump column / dropped (tests/walk_refs.py: the
               program until PR 42)
    loop@C     `planes.walk_scatters` with `planes.WALK_CHUNK` = C: a
               loop of ceil(steps / C) trips, each the two scatters
               on a dynamic slice of C slots
    ladder     a `lax.switch` on the steps over static prefixes of
               Kw / 8, / 4, / 2 slots (whole sublanes of 8) and the
               dense form (`live_pick_rungs`' idiom: every rung a copy
               of both scatters in the compiled wave)
    floor      no scatter: what the harness itself costs (the fill of
               the tree buffer and the read of both outputs, which the
               wave does around its scatters too)

each at ``steps`` = 0, the cell's mean, twice and four times it, and Kw
(a walk that overran), as one jitted loop of ``--reps`` dependent calls
under the host's clock (the loop carries the delays, so no call is
hoisted), the best of three.  Prints
one JSON line a shape and writes them all to ``--out``.  Refuses to run
off the TPU (exit 2, chip_smoke.py's rule) unless ``--allow-cpu`` asks
for a rehearsal, whose lines say ``"device": "cpu"`` and are no device
numbers.

``--occupancy`` times nothing: it routes each named benchmark cell once,
with ``planes.walk_scatters`` wrapped in a host callback, and prints
the waves by batch shape (B, G, Kw) and by the steps their longest KEPT
walk ran, with the share of the budget a chunk C would read.  Waves by steps
x the form's microseconds is what a form costs a route; program COUNTS,
so any platform will do (the line names it: a CPU route's trajectory can
differ from the chip's).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# (B, G, Kw, ncells, mean steps a wave): the op shapes of ISSUE 42's
# table, route_scale's from --occupancy, the mean from the ledger's
# `window.walk_step_share` (PR 41) x Kw
SHAPES = {
    "route_relaxed": (64, 8, 188, 20240, 8),
    "route_tight": (64, 8, 188, 16192, 9),
    "route_k6n10_relaxed": (64, 7, 148, 16896, 7),
    "route_scale": (64, 9, 188, 66880, 23),
    "route_hetero": (64, 13, 260, 83200, 73),
    "route_dsp": (64, 11, 252, 76800, 58),
    "route_fanout": (64, 16, 220, 47040, 12),
    "route_fanout.wide16": (16, 204, 220, 47040, 12),
    # a finishing pass or a rebuild picks ONE sink a wave: half of
    # route_hetero's waves (--occupancy)
    "route_hetero.one_pick": (64, 1, 260, 83200, 90),
}
NODES = 30000       # the node sentinel: no form's cost depends on it


def ladder_rungs(Kw: int):
    """Static prefix lengths of the ladder form: an eighth, a quarter,
    a half of the budget in whole sublanes of 8."""
    return tuple(sorted({min(Kw, -(-Kw // d // 8) * 8) for d in (8, 4, 2)}))


def walk_scatters_ladder(buf, seg, walk_cells, walk_tdel, nodes_w, keep,
                         posn):
    """The narrowest static prefix that holds the longest kept walk, by
    one switch; past the widest rung the dense form."""
    import jax.numpy as jnp
    from jax import lax

    from walk_refs import walk_scatters_dense

    Kw = nodes_w.shape[2]
    rungs = ladder_rungs(Kw)
    last = jnp.max(jnp.where(walk_cells < buf.shape[1] - 1,
                             jnp.arange(1, Kw + 1, dtype=jnp.int32), 0))

    def prefix(M, buf, seg, *recs):
        return walk_scatters_dense(buf, seg, *(a[:, :, :M] for a in recs))

    return lax.switch(
        jnp.sum(last > jnp.array(rungs), dtype=jnp.int32),
        [functools.partial(prefix, M) for M in rungs]
        + [functools.partial(prefix, None)],
        buf, seg, walk_cells, walk_tdel, nodes_w, keep, posn)


def floor_form(buf, seg, *_):
    return buf, seg, 0


def loop_at(C: int):
    """`planes.walk_scatters` traced with `planes.WALK_CHUNK` = C."""
    from unittest import mock

    from parallel_eda_tpu.route import planes

    def form(*args):
        with mock.patch.object(planes, "WALK_CHUNK", C):
            return planes.walk_scatters(*args)
    return form


def forms_of(chunks):
    from walk_refs import walk_scatters_dense

    forms = {"dense": walk_scatters_dense}
    for C in chunks:
        forms[f"loop@{C}"] = loop_at(C)
    forms["ladder"] = walk_scatters_ladder
    forms["floor"] = floor_form
    return forms


def timed_loop(form, ncells: int, reps: int):
    """``form(buf, seg, walk_cells, walk_tdel, nodes_w, keep, posn)``
    -> (buf, seg, slots) as a jitted loop of ``reps`` calls.  Each call
    starts from a fresh tree buffer, as a wave does, and hands the next
    its delays moved on a count the compiler cannot know to be
    impossible; the count reads every cell of the tree and every slot
    of the rows (the wave reads them too)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(seg, walk_cells, walk_tdel, nodes_w, keep, posn):
        B = seg.shape[0]
        N = seg.max()

        def body(_, tdel):
            buf, out, _ = form(
                jnp.full((B, ncells + 1), jnp.inf, jnp.float32), seg,
                walk_cells, tdel, nodes_w, keep, posn)
            used = (jnp.isfinite(buf[:, :ncells]).sum(dtype=jnp.int32)
                    + jnp.sum(out != N, dtype=jnp.int32))
            return tdel.at[0, 0, 0].add(jnp.where(used == -7, 1.0, 0.0))
        return lax.fori_loop(0, reps, body, walk_tdel)

    return loop


def seeded_args(shape, steps: int, seed: int):
    """A wave's records with its longest walk ``steps`` long and kept,
    the others drawn below it (a tenth of the picks direct), on the
    device: seg and the five record arrays."""
    import jax.numpy as jnp

    from walk_refs import seeded_walks

    B, G, Kw, ncells, _ = shape
    rng = np.random.default_rng(seed)
    lengths = rng.integers(0, steps + 1, (B, G))
    lengths[0, 0] = steps
    args = seeded_walks(B, G, Kw, ncells, NODES, lengths, seed + 1,
                        direct=0.1, kept=[(0, 0)])
    return tuple(jnp.asarray(a) for a in args[1:-1])


def step_points(shape):
    Kw, mean = shape[2], shape[4]
    return sorted({0, mean, min(Kw, 2 * mean), min(Kw, 4 * mean), Kw})


def time_shape(name: str, chunks, reps: int, seed: int) -> dict:
    import jax

    from crop_forms import us_per_call    # tools/ is the script's path

    shape = SHAPES[name]
    B, G, Kw, ncells, mean = shape
    row = {"shape": name, "B": B, "G": G, "Kw": Kw, "ncells": ncells,
           "mean_steps": mean, "ladder_rungs": list(ladder_rungs(Kw)),
           "device": jax.devices()[0].platform}
    inputs = {s: seeded_args(shape, s, seed) for s in step_points(shape)}
    for fname, form in forms_of(chunks).items():
        loop = timed_loop(form, ncells, reps)
        # the dense form and the floor read no step count: one point
        points = [mean] if fname in ("dense", "floor") else list(inputs)
        for s in points:
            row[f"{fname}.us@{s}"] = round(
                us_per_call(loop, inputs[s], reps), 2)
    return row


def forms_agree(name: str, chunks, seed: int) -> bool:
    """Every form's tree cells and path rows equal the dense form's,
    element for element, at each of the shape's step points; what
    differs is said on stderr."""
    import jax

    shape = SHAPES[name]
    ncells = shape[3]
    forms = {f: jax.jit(form) for f, form in forms_of(chunks).items()
             if f != "floor"}
    agree = True
    for s in step_points(shape):
        seg, *recs = seeded_args(shape, s, seed)
        buf = np.full((shape[0], ncells + 1), np.inf, np.float32)
        outs = {f: tuple(np.asarray(o) for o in form(buf, seg, *recs)[:2])
                for f, form in forms.items()}
        want_b, want_g = outs["dense"][0][:, :ncells], outs["dense"][1]
        for f, (b, g) in outs.items():
            for what, got, want in (("tree", b[:, :ncells], want_b),
                                    ("rows", g, want_g)):
                at = np.argwhere(got != want)
                if len(at):
                    agree = False
                    print(f"walk_forms: {name} {f} steps={s}: {len(at)} "
                          f"{what} elements differ from dense, first "
                          + "; ".join(f"{tuple(i)}: {got[tuple(i)]} != "
                                      f"{want[tuple(i)]}" for i in at[:4]),
                          file=sys.stderr)
    return agree


def read_share(hist: dict, Kw: int, C: int) -> float:
    """Percent of the budget the loop form reads at chunk C over waves
    counted by their steps."""
    C = min(C, Kw)
    waves = sum(hist.values())
    read = sum(n * min(Kw, -(-s // C) * C) for s, n in hist.items())
    return 100.0 * read / (waves * Kw) if waves else 0.0


def trips_hist(hist: dict, Kw: int, C: int) -> dict:
    """Waves by the trips the loop form runs at chunk C."""
    out: dict = {}
    for s, n in hist.items():
        t = -(-s // min(C, Kw))
        out[t] = out.get(t, 0) + n
    return dict(sorted(out.items()))


def walk_occupancy(workload: str, chunks) -> dict:
    """One route of the cell ``workload`` with the walk scatters
    wrapped: waves by (B, G, Kw) and by the steps their longest kept
    walk ran, beside the route's own counters."""
    import collections

    import jax
    import jax.numpy as jnp

    from benchmark import harness, problem
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.route import planes

    waves = collections.Counter()
    built = planes.walk_scatters

    def wrapped(buf, seg, walk_cells, *recs):
        key, Kw = walk_cells.shape, walk_cells.shape[2]
        last = jnp.max(jnp.where(walk_cells < buf.shape[1] - 1,
                                 jnp.arange(1, Kw + 1, dtype=jnp.int32), 0))
        jax.debug.callback(lambda s: waves.update([key + (int(s),)]), last)
        return built(buf, seg, walk_cells, *recs)

    cell = harness.load_cell(harness.load_manifest(REPO), REPO, workload)
    f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    # a jitted program holds what it traced: none from before the patch
    # may run, and none with the patch in may outlive it
    jax.clear_caches()
    planes.walk_scatters = wrapped
    try:
        F.run_route(f, problem.router_opts(cell.config, {}),
                    timing_driven=bool(cell.config["router"]
                                       ["timing_driven"]), verify=False)
        jax.effects_barrier()
    finally:
        planes.walk_scatters = built
        jax.clear_caches()
    r = f.route
    shapes = []
    for key in sorted({k[:3] for k in waves}):
        hist = {s: n for (*k, s), n in waves.items() if tuple(k) == key}
        n = sum(hist.values())
        steps = sorted(hist.items())
        shapes.append({
            "B": key[0], "G": key[1], "Kw": key[2], "waves": n,
            "mean_steps": round(sum(s * c for s, c in steps) / n, 2),
            "steps_pct": {str(q): int(np.percentile(
                np.repeat([s for s, _ in steps], [c for _, c in steps]), q))
                for q in (50, 90, 99, 100)},
            "read_share": {str(C): round(read_share(hist, key[2], C), 2)
                           for C in chunks},
            "trips": {str(C): trips_hist(hist, key[2], C)
                      for C in chunks},
            "steps": dict(steps)})
    return {"workload": workload, "device": jax.devices()[0].platform,
            "iterations": int(r.iterations), "windows": len(r.stats),
            "sweeps": int(r.total_relax_steps),
            "waves": int(r.total_waves),
            "walk_steps": int(r.total_walk_steps),
            "walk_budget": int(r.total_walk_budget),
            "walk_slots_read": int(r.total_walk_slots_read),
            "by_shape": shapes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--chunks", default="8,16,32")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "walk_forms.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the TPU; the times mean nothing")
    ap.add_argument("--check-only", action="store_true",
                    help="compare the forms' outputs and time nothing")
    ap.add_argument("--occupancy", default="",
                    help="cells to route once for their waves by steps")
    a = ap.parse_args(argv)
    chunks = [int(c) for c in a.chunks.split(",")]
    if a.occupancy:
        for name in a.occupancy.split(","):
            print(json.dumps(walk_occupancy(name, chunks)), flush=True)
        return 0
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.allow_cpu:
        print(f"walk_forms: the device is {platform!r}, not a TPU; a "
              "time from it is no device number (--allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    rows = []
    for name in a.shapes.split(","):
        if not forms_agree(name, chunks, a.seed):
            return 1
        if a.check_only:
            continue
        rows.append(time_shape(name, chunks, a.reps, a.seed))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
