"""The relaxation's min-plus scan as `planes._minplus_scan` ran it until
PR 45 (`lax.associative_scan` over the whole canvases: its odd-even
tree put back together by interior pads and an add of zeros a level,
`jnp.flip` around a reverse scan) as the REFERENCE the slab form is
held to, bit for bit, alone, in one relaxation and before the v5e
compiler (not a test file: imported by tests/test_scan_forms.py,
tests/test_chip_compile.py and tools/scan_forms.py)."""

import contextlib

import jax.numpy as jnp
from jax import lax

from parallel_eda_tpu.route import planes


def minplus_scan_assoc(d0, c, axis, reverse=False):
    """s[x] = min(d0[x], s[x-1] + c[x]) along axis (reverse: x+1 side)
    via associative_scan on pairs: combine((c1, m1), (c2, m2)) =
    (c1 + c2, min(m1 + c2, m2))."""
    def comb(a, b):
        ca, ma = a
        cb, mb = b
        return ca + cb, jnp.minimum(ma + cb, mb)

    if reverse:
        d0 = jnp.flip(d0, axis)
        c = jnp.flip(c, axis)
    _, s = lax.associative_scan(comb, (c, d0), axis=axis)
    if reverse:
        s = jnp.flip(s, axis)
    return s


@contextlib.contextmanager
def scan_form(form):
    """Inside: every relaxation traced scans by ``form`` (the signature
    of `planes._minplus_scan`).  Yields a list that holds an entry a
    scan traced that way.  The jitted programs that hold a traced
    relaxation are dropped on the way in and out; a caller's own jit
    traces anew only under a function object of its own."""
    def drop_programs():
        for prog in (planes.route_window_planes,
                     planes.route_batch_resident_planes):
            prog.clear_cache()

    traced = []

    def counted(*a, **kw):
        traced.append(1)
        return form(*a, **kw)

    built = planes._minplus_scan
    drop_programs()
    planes._minplus_scan = counted
    try:
        yield traced
    finally:
        planes._minplus_scan = built
        drop_programs()
