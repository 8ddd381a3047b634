"""Time the relaxation's min-plus scan ALONE, form by form, at the
benchmark cells' shapes (ROADMAP Queue 1 "How to price an item": a form
timed alone on the chip x a count of its calls; a sweep scans four
times, the sweeps are `RouteResult.total_relax_steps`).

    python3 tools/scan_forms.py [--shapes route_relaxed,...] [--reps 20]
        [--forms assoc,slab,seq]
        [--out chiprun_out/scan_forms.json] [--allow-cpu]

A sweep of the relaxation (`planes._sweep_once`) runs
s[x] = min(d[x], s[x-1] + c[x]) along x and along y, forward and
reverse, over the batch's canvases [B, W, X, Y+1] and [B, W, X+1, Y].
For each shape (the cell's routing architecture at its grid and channel
width: real span breaks, so real zero steps and, on single-driver wires,
real INF steps; B nets of seeded distances, 30% of the cells INF) the
microseconds a call of each FORM:

    assoc    the parent's (tests/scan_refs.py): `lax.associative_scan`
             over the whole canvases, its odd-even tree put back
             together by interior pads and an add of zeros a level,
             `jnp.flip` around a reverse scan
    slab     `planes._minplus_scan`: the same tree on the axis's n
             per-position slabs, static slices in, one concatenate out,
             a reverse scan the list reversed
    seq      the sequential recurrence on slabs, n - 1 combines: NOT the
             same bits (it sums a path in one order; an ulp apart from
             the tree in 0.5 to 16% of cells, by the data), timed for
             ROADMAP Queue 1 item 2 only

Columns of a row (all microseconds a call, one jitted loop of ``--reps``
dependent calls under the host's clock, the best of three):

    <form>.x_fwd_us / .x_rev_us / .y_fwd_us / .y_rev_us
                     ONE scan along x (axis 2 of the x canvas) / y (axis
                     3 of the y canvas); the loop carries the distances
    <form>.sweep_us  one whole `planes._sweep_once` (four scans with
                     their selects, two turns) with the form swapped in

Before a loop is timed its result (the scanned distances, the six
planes ``--reps`` sweeps leave) is compared with `assoc`'s ON THE DEVICE,
uint32 view against uint32 view, for every form of `assoc`'s tree (all
but `seq`): a difference is said on stderr and exits 1 (name `assoc`
first in ``--forms``).  Prints one JSON line a shape and writes them all
to ``--out``.  Refuses to run off the TPU (exit 2, chip_smoke.py's rule)
unless ``--allow-cpu`` asks for a rehearsal, whose lines say
``"device": "cpu"`` and are no device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tests"),
           os.path.join(REPO, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# cell -> (architecture builder, its arguments, grid, W, B): the eight
# cells, each at its full canvas
_K4N4 = ("minimal_arch", {"K": 4, "N": 4, "I": 10, "io_capacity": 2})
_K6N10 = ("k6_n10_40nm_arch", {})
_K6FRAC = "k6_frac_n10_mem32k_40nm_arch"
SHAPES = {
    "route_relaxed": _K4N4 + (22, 20, 64),
    "route_k6n10_relaxed": _K6N10 + (11, 64, 64),
    "route_tight": _K4N4 + (22, 16, 64),
    "route_scale": _K6N10 + (19, 88, 64),
    "route_hetero": (_K6FRAC, {}, 25, 64, 64),
    "route_fanout": _K6N10 + (20, 56, 64),
    "route_dsp": (_K6FRAC, {"mult_combinational": True}, 24, 64, 64),
    "route_scale_6k": _K6N10 + (26, 88, 64),
}
FORMS = ("assoc", "slab", "seq")
BIT_EQUAL = ("assoc", "slab")       # one tree: one set of bits
SCANS = {"x_fwd": (0, 2, False), "x_rev": (0, 2, True),
         "y_fwd": (1, 3, False), "y_rev": (1, 3, True)}


# ---- the rival form (the parent's is tests/scan_refs.py, the chosen
# one planes._minplus_scan; how the slabs are cut and joined is no form
# of its own: squeezed slabs and a stack, the axis moved to the front,
# the join behind a barrier all compile to the v5e program of `slab`,
# PERF.md section 6 PR 45) ----

def minplus_scan_seq(d0, c, axis, reverse=False):
    """The recurrence as written, slab after slab: another order of
    sums, so other bits."""
    import jax.numpy as jnp
    from jax import lax

    n = d0.shape[axis]
    order = range(n - 1, -1, -1) if reverse else range(n)
    s, out = None, [None] * n
    for i in order:
        m = lax.slice_in_dim(d0, i, i + 1, axis=axis)
        if s is not None:
            m = jnp.minimum(s + lax.slice_in_dim(c, i, i + 1, axis=axis), m)
        s = out[i] = m
    return lax.concatenate(out, axis)


def form_of(form: str):
    """A form under `planes._minplus_scan`'s own signature."""
    if form == "assoc":
        from scan_refs import minplus_scan_assoc
        return minplus_scan_assoc
    if form == "slab":
        from parallel_eda_tpu.route import planes
        return planes._minplus_scan
    if form == "seq":
        return minplus_scan_seq
    raise ValueError(f"no form {form!r} (have {FORMS})")


# ---- a shape's inputs, the programs over them ----

def seeded_inputs(pg, B: int, seed: int):
    """(gm, state, crit_c, cc_x, cc_y, costs): the full geometry, a
    sweep's six planes (30% of the distances INF), seeded criticalities
    and congestion fields, and the step costs `_sweep_costs` forms from
    them: zero inside a span, INF against a single-driver wire."""
    import jax.numpy as jnp

    from parallel_eda_tpu.route import planes

    rng = np.random.default_rng(seed)
    sx, sy = (B,) + pg.shape_x, (B,) + pg.shape_y

    def field(shape, inf=0.0):
        a = rng.uniform(1e-10, 1e-8, shape).astype(np.float32)
        if inf:
            a[rng.random(shape) < inf] = np.inf
        return jnp.asarray(a)

    gm = planes.geom_full(pg)
    crit_c = jnp.asarray(rng.uniform(0, 0.9, (B, 1, 1, 1))
                         .astype(np.float32))
    cc_x, cc_y = field(sx), field(sy)
    state = (field(sx, 0.3), field(sy, 0.3),
             jnp.broadcast_to(gm.idxx, sx), jnp.broadcast_to(gm.idxy, sy),
             field(sx), field(sy))
    return (gm, state, crit_c, cc_x, cc_y,
            planes._sweep_costs(gm, crit_c, cc_x, cc_y))


def scan_loop(form, axis: int, reverse: bool, reps: int):
    """``reps`` scans, each of the distances the one before it left."""
    import jax
    from jax import lax

    @jax.jit
    def loop(d, c):
        return lax.fori_loop(
            0, reps, lambda _, d: form(d, c, axis, reverse), d)
    return loop


def sweep_loop(form, inputs, reps: int):
    """``reps`` sweeps of `planes._sweep_once` traced with ``form`` in
    `planes._minplus_scan`'s place."""
    from unittest import mock

    import jax
    from jax import lax

    from parallel_eda_tpu.route import planes

    gm, _, crit_c, cc_x, cc_y, costs = inputs

    @jax.jit
    def loop(s):
        with mock.patch.object(planes, "_minplus_scan", form):
            return lax.fori_loop(
                0, reps, lambda _, s: planes._sweep_once(
                    gm, s, crit_c, cc_x, cc_y, costs), s)
    return loop


def scan_operands(inputs, which: str):
    """(d, c, axis, reverse) of one of a sweep's four scans."""
    plane, axis, reverse = SCANS[which]
    state, costs = inputs[1], inputs[5]
    # costs: cfx, cbx, cfy, cby: a reverse scan pays the `after` breaks
    return state[plane], costs[2 * plane + int(reverse)], axis, reverse


def run_shape(name: str, forms, reps: int, seed: int):
    """(row, agree) of one shape: each form's five loops compiled once,
    their first results compared with `assoc`'s (forms of its tree
    only; what differs is said on stderr), then timed."""
    import jax

    from crop_forms import build_planes_of, us_per_call

    builder, args, n, W, B = SHAPES[name]
    pg = build_planes_of(builder, args, n, W)
    assert pg.shape_x[:2] == (W, n), (pg.shape_x, W, n)
    inputs = seeded_inputs(pg, B, seed)
    row = {"shape": name, "grid": n, "W": W, "B": B, "reps": reps,
           "directional": bool(pg.directional),
           "device": jax.devices()[0].platform}
    want, agree = None, True
    for fname in forms:
        form = form_of(fname)
        loops = {"sweep": (sweep_loop(form, inputs, reps), (inputs[1],))}
        for which in SCANS:
            d, c, axis, reverse = scan_operands(inputs, which)
            loops[which] = (scan_loop(form, axis, reverse, reps), (d, c))
        got = {}
        for what, (loop, operands) in loops.items():
            if fname in BIT_EQUAL:
                outs = jax.tree_util.tree_leaves(loop(*operands))
                got.update({f"{what}[{i}]": np.asarray(a).view(np.uint32)
                            for i, a in enumerate(outs)})
            row[f"{fname}.{what}_us"] = round(
                us_per_call(loop, operands, reps), 2)
        if fname == "assoc":
            want = got
        for what, a in got.items() if want is not None else ():
            at = np.argwhere(a != want[what])
            if len(at):
                agree = False
                print(f"scan_forms: {name} {fname} {what}: {len(at)} of "
                      f"{a.size} elements differ from assoc after {reps} "
                      f"calls, first at {tuple(at[0])}", file=sys.stderr)
    return row, agree


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "scan_forms.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the TPU; the times mean nothing")
    a = ap.parse_args(argv)
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.allow_cpu:
        print(f"scan_forms: the device is {platform!r}, not a TPU; a "
              "time from it is no device number (--allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    forms = a.forms.split(",")
    rows = []
    for name in a.shapes.split(","):
        row, agree = run_shape(name, forms, a.reps, a.seed)
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
