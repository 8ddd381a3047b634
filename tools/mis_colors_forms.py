"""Time the window program's conflict colouring ALONE, form by form, at
the benchmark cells' path-store shapes (ROADMAP Queue 1 "How to price an
item": a form timed alone on the chip x a count of its calls; the calls
are `route.mis_colors.short_total` / `.full_total` / `.skipped_total`).

    python3 tools/mis_colors_forms.py [--shapes route_relaxed,...]
        [--n-over 0,1,8,32,64,128,256,700,1024] [--reps 5]
        [--forms full,short64,short128,short256,short1024,chunk8,auto,skip]
        [--out chiprun_out/mis_colors_forms.json] [--allow-cpu]
    python3 tools/mis_colors_forms.py --occupancy route_relaxed[,...]

With ``--occupancy`` it routes a cell once instead and prints the counts
a price is multiplied by: window programs dispatched, colourings read,
the programs by form, and the overused nodes each window ended with
(counts, so the CPU will do: 1 to 15 minutes a cell).

For each shape (a cell's path store as the router allocates it, R nets
x S sink slots x L path slots a fanout class, `route_scale_6k.grown`
the store once a widened net grew L; N its rr graph's nodes; topk
4,096) a seeded store (two fifths of a net's slots hold a node, the
tails the sentinel N) and, for each count of overused nodes, an ``occ``
that puts that many nodes ON the paths over capacity.  The forms:

    full       `planes._mis_colors_full`: the node-indexed table, the
               gather of R x S x L slots, the scatter into [R, topk + 1]
    short<K>   `planes._mis_colors_short` at width K: one dense compare
               of the store an overused node, U [R, K]; only where
               n_over <= K
    chunk8     the rival: eight ids a trip of the loop (eight compares
               a read of the store), width `planes.MIS_SHORT_K`
    auto       `planes._mis_colors`: the two under their `lax.cond`
    skip       `planes.window_colours(read=False)`: a rung that is not
               its window's last

Columns of a row: ``<form>.us`` {n_over: microseconds a call}, one
jitted loop of ``--reps`` dependent calls (the loop carries ``occ``,
which each call's answer could move and never does) under the host's
clock, the best of three; ``store_layouts``, the layouts the compiler
gave the s32 [R, S, L] store in each form's loop, to be compared with
the window program's op table before a price is multiplied by calls
(PERF.md section 6 PR 45, finding (b)).

Before a form is timed at a count, its rrm and colors are compared with
`full`'s ON THE DEVICE: a difference is said on stderr and exits 1 (name
`full` first in ``--forms``).  Prints one JSON line a shape and writes
them all to ``--out``.  Refuses to run off the TPU (exit 2,
chip_smoke.py's rule) unless ``--allow-cpu`` asks for a rehearsal, whose
lines say ``"device": "cpu"`` and are no device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import types

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOPK = 4096
N_COLORS = 5
# cell -> (N, the path store's (R, S, L) a fanout class): the ledger's
# op tables at PR 45 (`s32[R*S*L]` read out of `s32[N + 1]`)
SHAPES = {
    "route_relaxed": (29656, [(962, 12, 128)]),
    "route_k6n10_relaxed": (13560, [(1017, 7, 152)]),
    "route_tight": (25608, [(962, 12, 128)]),
    "route_scale": (42008, [(2985, 9, 216)]),
    "route_hetero": (75005, [(2461, 13, 264)]),
    "route_fanout": (38484, [(2142, 15, 224), (16, 204, 224)]),
    "route_dsp": (69576, [(2428, 11, 256)]),
    "route_scale_6k": (75636, [(4997, 12, 192)]),
    "route_scale_6k.grown": (75636, [(4997, 12, 272)]),
}
FORMS = ("full", "short64", "short128", "short256", "short1024", "chunk8",
         "auto", "skip")
N_OVER = (0, 1, 8, 32, 64, 128, 256, 700, 1024)


# ---- the rival form ----

def mis_colors_short_chunked(dev, occ, paths, all_reached, K: int,
                             n_colors: int, fan=None, chunk: int = 8):
    """`planes._mis_colors_short` with ``chunk`` ids a trip: one read of
    the store serves ``chunk`` compares."""
    import jax.numpy as jnp
    from jax import lax

    from parallel_eda_tpu.route import planes

    over = jnp.maximum(occ - dev.capacity, 0)
    n_over = (over > 0).sum(dtype=jnp.int32)
    _, ids = lax.top_k(over, K)
    flats = [store.reshape(store.shape[0], -1)
             for store in ((paths,) if fan is None else paths)]
    prio, reached = planes._mis_rows(all_reached, fan)

    def columns(t, Ut):
        hit = jnp.stack([
            jnp.concatenate([(flat == ids[t * chunk + c]).any(axis=1)
                             for flat in flats])
            for c in range(chunk)])
        return lax.dynamic_update_slice(Ut, hit, (t * chunk, 0))

    trips = (jnp.minimum(n_over, K) + chunk - 1) // chunk
    Ut = lax.fori_loop(0, trips, columns,
                       jnp.zeros((K, prio.shape[0]), bool))
    # a trip's columns past n_over compared against clean nodes' ids
    U = Ut.T & (jnp.arange(K) < n_over)[None, :]
    rrm = U.any(axis=1) | ~reached
    return planes._mis_by_net(
        rrm, planes._mis_rounds(U, rrm, prio, n_colors), prio, fan)


def form_of(form: str):
    """``form`` as f(dev, occ, paths, all_reached, fan) -> (rrm,
    colors)."""
    import jax.numpy as jnp

    from parallel_eda_tpu.route import planes

    if form == "full":
        return lambda dev, occ, paths, reached, fan: (
            planes._mis_colors_full(dev, occ, paths, reached, TOPK,
                                    N_COLORS, fan))
    if form.startswith("short"):
        K = int(form[len("short"):])
        return lambda dev, occ, paths, reached, fan: (
            planes._mis_colors_short(dev, occ, paths, reached, K,
                                     N_COLORS, fan))
    if form.startswith("chunk"):
        chunk = int(form[len("chunk"):])
        return lambda dev, occ, paths, reached, fan: (
            mis_colors_short_chunked(dev, occ, paths, reached,
                                     planes.MIS_SHORT_K, N_COLORS, fan,
                                     chunk))
    if form == "auto":
        return lambda dev, occ, paths, reached, fan: (
            planes._mis_colors(dev, occ, paths, reached, TOPK, N_COLORS,
                               **({} if fan is None else {"fan": fan})))
    if form == "skip":
        return lambda dev, occ, paths, reached, fan: (
            planes.window_colours(dev, occ, paths, reached, TOPK,
                                  N_COLORS, jnp.bool_(False), fan)[:2])
    raise ValueError(f"no form {form!r} (have {FORMS})")


def width_of(form: str):
    """The most overused nodes ``form`` takes (None: any)."""
    from parallel_eda_tpu.route import planes

    if form.startswith("short"):
        return int(form[len("short"):])
    if form.startswith("chunk"):
        return planes.MIS_SHORT_K
    return None


# ---- a shape's inputs, the programs over them ----

def seeded_store(N: int, classes, seed: int):
    """(paths, fan, reached): a store a class (a tuple of them and
    ``fan`` = (local, members) with more than one class), two fifths of
    a net's slots a node and the tails the sentinel N, two nets with a
    sink unreached."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    stores, members, r0 = [], [], 0
    for R, S, L in classes:
        st = rng.integers(0, N, (R, S, L))
        ln = rng.integers(1, max(2, (4 * L) // 5), (R, S))
        st[np.arange(L) >= ln[:, :, None]] = N
        stores.append(jnp.asarray(st, jnp.int32))
        members.append(jnp.arange(r0, r0 + R, dtype=jnp.int32))
        r0 += R
    reached = np.ones(r0, bool)
    reached[[1, r0 - 1]] = False
    if len(classes) == 1:
        return stores[0], None, jnp.asarray(reached)
    local = jnp.concatenate([jnp.arange(R, dtype=jnp.int32)
                             for R, _, _ in classes])
    return tuple(stores), (local, tuple(members)), jnp.asarray(reached)


def seeded_occ(N: int, paths, n_over: int, seed: int):
    """An occupancy (capacity 1 a node) with ``n_over`` nodes that lie
    ON paths over capacity, by 1 to n_over."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + 7 * n_over)
    first = np.asarray(paths if not isinstance(paths, tuple) else paths[0])
    on_paths = np.unique(first[first < N])
    hot = rng.choice(on_paths, n_over, replace=False)
    occ = rng.integers(0, 2, N)
    occ[hot] = 2 + rng.permutation(n_over)
    return jnp.asarray(occ, jnp.int32)


def colour_loop(form, dev, fan, reps: int):
    """``reps`` colourings, each of the occupancy the one before it
    left (the same: a colouring's sums are never negative, which the
    compiler cannot know)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(occ, paths, reached):
        def body(_, st):
            occ, _, _ = st
            rrm, colors = form(dev, occ, paths, reached, fan)
            moved = rrm.sum() + colors.sum() < 0
            return jnp.where(moved, occ + 1, occ), rrm, colors
        R = reached.shape[0]
        return lax.fori_loop(
            0, reps, body,
            (occ, jnp.zeros(R, bool), jnp.zeros(R, jnp.int32)))
    return loop


def store_layouts(compiled, classes):
    """The layouts a compiled loop holds an s32 [R, S, L] store in (its
    own text for them)."""
    text = compiled.as_text()
    found = set()
    for R, S, L in classes:
        found |= set(re.findall(rf"s32\[{R},{S},{L}\]\{{[^}}]*\}}", text))
        found |= set(re.findall(rf"s32\[{R},{S * L}\]\{{[^}}]*\}}", text))
        found |= set(re.findall(rf"s32\[{R * S * L}\]\{{[^}}]*\}}", text))
    return sorted(found)


def run_shape(name: str, forms, n_overs, reps: int, seed: int):
    """(row, agree) of one shape."""
    import jax
    import jax.numpy as jnp

    from crop_forms import us_per_call

    N, classes = SHAPES[name]
    paths, fan, reached = seeded_store(N, classes, seed)
    dev = types.SimpleNamespace(num_nodes=N,
                                capacity=jnp.ones(N, jnp.int32))
    occs = {n: seeded_occ(N, paths, n, seed) for n in n_overs}
    row = {"shape": name, "N": N, "classes": classes, "topk": TOPK,
           "slots": int(sum(R * S * L for R, S, L in classes)),
           "reps": reps, "device": jax.devices()[0].platform,
           "store_layouts": {}}
    want, agree = {}, True
    for fname in forms:
        # compiled ONCE: every count's occupancy has one shape
        loop = colour_loop(form_of(fname), dev, fan, reps).lower(
            occs[n_overs[0]], paths, reached).compile()
        K = width_of(fname)
        row["store_layouts"][fname] = store_layouts(loop, classes)
        times = {}
        for n in n_overs:
            if K is not None and n > K:
                continue
            operands = (occs[n], paths, reached)
            _, rrm, colors = loop(*operands)
            got = (np.asarray(rrm), np.asarray(colors))
            if fname == "full":
                want[n] = got
            elif fname != "skip" and n in want:
                for what, a, b in zip(("rrm", "colors"), got, want[n]):
                    if not np.array_equal(a, b):
                        agree = False
                        print(f"mis_colors_forms: {name} {fname} n_over "
                              f"{n}: {what} differs from full in "
                              f"{int((a != b).sum())} of {a.size} nets",
                              file=sys.stderr)
            elif fname == "skip" and (got[0].any() or got[1].any()):
                agree = False
                print(f"mis_colors_forms: {name} skip: not zeros",
                      file=sys.stderr)
            times[str(n)] = round(us_per_call(loop, operands, reps), 1)
        row[f"{fname}.us"] = times
    return row, agree


def colouring_occupancy(workload: str) -> dict:
    """One route of the cell ``workload``: the `route.mis_colors.*`
    counters it moved, and the overused nodes at each window's end."""
    import jax

    from benchmark import harness, problem
    from parallel_eda_tpu import flow as F
    from parallel_eda_tpu.obs import get_metrics
    from parallel_eda_tpu.route import planes

    def counters():
        v = get_metrics().values("route.mis_colors.")
        return {k.split(".")[-1]: v.get(k, 0) for k in (
            "route.mis_colors.calls_total", "route.mis_colors.read_total",
            "route.mis_colors.skipped_total",
            "route.mis_colors.short_total", "route.mis_colors.full_total")}

    cell = harness.load_cell(harness.load_manifest(REPO), REPO, workload)
    f = problem.build_placed(cell, int(cell.traffic["chan_width"]))
    before = counters()
    F.run_route(f, problem.router_opts(cell.config, {}),
                timing_driven=bool(cell.config["router"]["timing_driven"]),
                verify=False)
    r = f.route
    row = {k: v - before[k] for k, v in counters().items()}
    row.update(workload=workload, device=jax.devices()[0].platform,
               short_width=planes.MIS_SHORT_K,
               iterations=int(r.iterations), windows=len(r.stats),
               sweeps=int(r.total_relax_steps),
               over_at_window_end=[int(s.overused_nodes) for s in r.stats],
               window_kinds=[s.kind for s in r.stats])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--n-over", default=",".join(map(str, N_OVER)))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "mis_colors_forms.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the TPU; the times mean nothing")
    ap.add_argument("--occupancy", default="",
                    help="route these cells once and count the forms")
    a = ap.parse_args(argv)
    if a.occupancy:
        for workload in a.occupancy.split(","):
            print(json.dumps(colouring_occupancy(workload)), flush=True)
        return 0
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.allow_cpu:
        print(f"mis_colors_forms: the device is {platform!r}, not a TPU; "
              "a time from it is no device number (--allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    forms = a.forms.split(",")
    n_overs = [int(n) for n in a.n_over.split(",")]
    rows = []
    for name in a.shapes.split(","):
        row, agree = run_shape(name, forms, n_overs, a.reps, a.seed)
        if not agree:
            return 1
        rows.append(row)
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
