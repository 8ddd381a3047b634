"""The device-scope vocabulary (obs.trace.DEVICE_SCOPES) against the
compiled window programs: every declared name occurs in an op_name of
``compile().as_text()``, and nothing that JAX traced inside the
programs' loops lies outside every ``route.dev.*`` scope.

Instructions a compiler pass made carry no op_name at all (XLA:CPU's
reduce-window rewrite of a cumsum, for one); they are what a device
trace reports as ``unscoped``, and are only held to a minority here.
"""

import re

import pytest

from parallel_eda_tpu.flow import run_place, run_route, synth_flow
from parallel_eda_tpu.obs import DevProfiler, get_devprof, set_devprof
from parallel_eda_tpu.obs.trace import DEVICE_SCOPES, device_scope
from parallel_eda_tpu.route.router import RouterOpts

# opcodes that move or name values and compute nothing
PLUMBING = {"parameter", "tuple", "get-tuple-element", "constant",
            "bitcast", "copy", "while", "conditional", "call",
            "broadcast", "iota", "reshape", "after-all"}
_INSTR = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<shape>\(.*?\)|\S+) "
    r"(?P<op>[\w\-]+)\(")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def parse_hlo(text):
    """{computation: [(name, opcode, scalar, op_name, called)]}, entry."""
    comps, entry, cur = {}, None, None
    for line in text.split("\n"):
        if line and not line[0].isspace():
            m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
            cur = m.group(2) if m else None
            if m:
                comps[cur] = []
                entry = cur if m.group(1) else entry
            continue
        m = _INSTR.match(line) if cur else None
        if not m:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        comps[cur].append((m.group("name"), m.group("op"),
                           m.group("shape").endswith("[]"),
                           op_name.group(1) if op_name else "", called))
    return comps, entry


def scopes_of(op_name):
    return [p for p in op_name.split("/") if p.startswith("route.dev.")]


def audit(text):
    """(names seen anywhere, instructions of the entry's loops that JAX
    traced outside every scope, compiler-made ones with no op_name, how
    many were judged).  Judged: what computes a non-scalar value in a
    computation reached from the entry through a ``while``; a fusion is
    its fusion instruction (a fusion's scope is its root's)."""
    comps, entry = parse_hlo(text)
    seen = {s for body in comps.values() for i in body
            for s in scopes_of(i[3])}

    def computes(op, called):
        if op != "fusion":
            return op not in PLUMBING
        return any(o not in PLUMBING for k in called
                   for _, o, _, _, _ in comps.get(k, []))

    rows, done = [], set()

    def walk(comp, in_loop):
        if (comp, in_loop) in done:
            return
        done.add((comp, in_loop))
        for name, op, scalar, op_name, called in comps.get(comp, []):
            if in_loop and not scalar and computes(op, called):
                rows.append((comp, name, op, op_name))
            if op == "while":
                for k in called:
                    walk(k, True)
            elif op in ("conditional", "call"):
                for k in called:
                    walk(k, in_loop)

    walk(entry, False)
    made = [r for r in rows if not r[3]]
    outside = [r for r in rows if r[3] and not scopes_of(r[3])]
    return seen, outside, made, len(rows)


@pytest.fixture(scope="module")
def placed():
    # 6x6 grid with tight boxes: a forced 5x5 tile then gives both a
    # cropped and a full-canvas rung (the auto ladder starts at 8)
    return run_place(synth_flow(num_luts=60, chan_width=12, seed=3,
                                bb_factor=1))


def test_vocabulary_covers_the_window_program(placed):
    """Route a tiny placed problem, then lower every dispatched variant
    again from its recorded avatars and audit the compiled text."""
    old = get_devprof()
    dp = set_devprof(DevProfiler(enabled=True))
    try:
        opts = RouterOpts(program="planes", batch_size=16, crop="5x5")
        f = run_route(placed, opts, timing_driven=True, verify=False)
        assert f.route.success
        pending = list(dp._pending)
    finally:
        set_devprof(old)
    assert pending
    seen = set()
    for key, _, fn, args, kwargs in pending:
        assert fn.__name__ == "route_window_planes"
        names, outside, made, judged = audit(
            fn.lower(*args, **kwargs).compile().as_text())
        seen |= names
        assert not outside, (key, outside[:5])
        assert len(made) * 4 < judged, (key, len(made), judged)
    # the wide fold exists only where a combinational hard block's
    # junction is wider than the STA's in-edge table: a whole route on
    # such a circuit looks for it (tests/test_timing_comb_hard.py)
    assert seen == set(DEVICE_SCOPES) - {"route.dev.sta.wide_fold"}, (
        sorted(set(DEVICE_SCOPES) - seen), sorted(seen - set(DEVICE_SCOPES)))


def test_only_declared_names_open_a_device_scope():
    with pytest.raises(KeyError):
        device_scope("route.dev.not_declared")
    tops = [s for s in DEVICE_SCOPES if s.count(".") == 2]
    for s in DEVICE_SCOPES:
        assert s.startswith("route.dev.")
        # nested names only under a declared top-level one
        assert ".".join(s.split(".")[:3]) in tops
