"""Does this device's compiler keep `lax.dynamic_slice`'s clamp?
(PERF.md section 7 (29), PR 42)

    python3 tools/dynamic_slice_clamp.py

`lax.dynamic_slice(x, start, C)` with start > dim - C reads from
dim - C (documented: the start is clamped), so the slice's first
element is x[dim - C] and its last x[dim - 1].  Two forms, each a slice
of C = 16 slots at 176 of 188 whose value IS the slot:

    plain    a 1-D operand, the slice's two ends picked out, alone and
             in a `fori_loop`: the TPU v5e keeps the clamp (172, 187)
    planes   an int32 [B, G, Kw] operand sliced on its last axis in a
             `fori_loop` with a traced trip count, the slice's first and
             last PLANE reduced by a max (what a consumer that reads
             elements out of a slice does): the v5e's program of
             PR 42's day gives (176, 187): the first plane from the
             UNCLAMPED start, the last from the end of the array, as if
             each plane were a slice of its own at start + j clamped
             alone.  An f32 operand converted after the slice reads
             (172, 187).  XLA:CPU keeps the clamp in every form.

`planes.walk_scatters` therefore never hands dynamic_slice a start past
the bound.  Prints one JSON line; exit 1 where a form loses the clamp.
"""

import json
import sys

import jax
import jax.numpy as jnp
from jax import lax

B, G, N, C = 4, 2, 188, 16
TRIPS = -(-N // C)


def plain_ends(x, start):
    s = lax.dynamic_slice(x, (start,), (C,))
    return jnp.stack([s[0], s[C - 1]])


def plane_ends(a, start):
    s = lax.dynamic_slice_in_dim(a, start, C, axis=2)
    return jnp.stack([s[:, :, 0].max(), s[:, :, C - 1].max()])


def in_a_loop(ends, x):
    return jax.jit(lambda x, n: lax.fori_loop(
        0, n, lambda k, out: out.at[k].set(ends(x, k * C)),
        jnp.zeros((TRIPS, 2), jnp.int32)))(x, jnp.int32(TRIPS))[-1]


def main() -> int:
    x = jnp.arange(N, dtype=jnp.int32)
    a = jnp.broadcast_to(x, (B, G, N))
    want = [N - C, N - 1]
    got = {"plain": jax.jit(plain_ends)(
               x, jnp.int32((TRIPS - 1) * C)).tolist(),
           "plain_in_a_loop": in_a_loop(plain_ends, x).tolist(),
           "planes_in_a_loop": in_a_loop(plane_ends, a).tolist()}
    kept = all(v == want for v in got.values())
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "start": (TRIPS - 1) * C, "want": want, **got,
                      "clamp_kept": kept}))
    return 0 if kept else 1


if __name__ == "__main__":
    sys.exit(main())
