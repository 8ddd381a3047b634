"""Device-truth cost profiling: XLA's own cost model per dispatch
variant.

The PR-3 roofline ledger and the route.kernel.* gauges quote HOST-SIDE
MODELED numbers — bytes_per_sweep is a formula, not a measurement.  The
reference grounded its observability in measured per-thread perf_t
counters; the TPU-flow analogue of "measured" here is the compiler's
own cost analysis: every canonicalized route_window_planes dispatch
variant (the same (tile, K, nsw, L, waves, grp) signatures
_note_dispatch_variant tracks) is re-lowered AOT from shape avatars and
its ``Compiled.cost_analysis()`` / ``memory_analysis()`` captured —
FLOPs, bytes accessed, peak temp allocation, generated-code size.

Two structural constraints shape the design:

- the window program DONATES its state arrays, so the profiler cannot
  lower from the real arguments after the dispatch returns.  At note
  time (before the call) every array leaf is replaced with a
  jax.ShapeDtypeStruct avatar; static args (ints, bools, tuples, the
  mesh) pass through untouched, so ``fn.lower(*avatars)`` retraces the
  exact variant without touching device memory.
- capture is deferred: note_variant() only stores avatars (cheap);
  capture_all() pays the lower+compile (about half a cold compile per
  variant — the AOT path misses jit's weak-type cache entry but hits
  XLA's) OUTSIDE any measured region, at end-of-route / end-of-bench.

The measured-vs-modeled delta compares ``bytes accessed`` against the
planner's modeled bytes_per_sweep for the same dispatch.  HLO cost
analysis counts a while/scan body ONCE (not times the trip count), so
the measured number approximates ONE relaxation sweep plus the window's
fixed overhead — the declared sanity band is therefore wide
(|log10(measured/modeled)| <= DELTA_BAND_LOG10), a drift tripwire, not
a tight roofline.  Backends without cost analysis degrade gracefully:
the record carries ``unavailable`` with the reason.
"""

from __future__ import annotations

import json
from typing import Optional

from .metrics import get_metrics

# sanity band for the measured-vs-modeled bytes ratio (see module
# docstring for why it is wide); tools/ledger_report.py and
# tools/flow_doctor.py mirror this value
DELTA_BAND_LOG10 = 2.0


# THE peak table (one place; bench.py --sweep_only and
# tools/kernel_bench.py read it): peak HBM bandwidth in bytes/s keyed
# by jax's ``device_kind``.  Source: Google Cloud documentation, "TPU
# v5e" — 16 GB of HBM at 819 GB/s per chip.  A device that is not in
# the table is an error, not a default.
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def peak_hbm_bytes_per_s(device) -> Optional[float]:
    """Published peak HBM bandwidth of ``device``; None on a CPU (a CPU
    run reports no roofline share); KeyError for an accelerator the
    table does not know."""
    if device.platform == "cpu":
        return None
    try:
        return PEAK_HBM_BYTES_PER_S[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak HBM bandwidth for device_kind "
            f"{device.device_kind!r}: add it, with its source, to "
            f"obs/devprof.PEAK_HBM_BYTES_PER_S") from None


def _jsonable(x):
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return repr(x)


def _avatarize(tree):
    """Replace every array leaf of a (args, kwargs) tree with a
    jax.ShapeDtypeStruct; everything else (static ints/bools/tuples,
    None, the mesh, registered-pytree containers) passes through, so
    the avatar call hits the same jit variant as the real dispatch."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype") \
                and not isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


class DevProfiler:
    """Deferred AOT capture of XLA cost/memory analysis per dispatch
    variant.  Disabled by default; a driver (bench.py, the router when
    a stats_dir sink is configured) flips ``enabled``.  Keeps its OWN
    seen-set — independent of _note_dispatch_variant's process-wide
    one, so a profiler enabled mid-process (warm jit cache) still
    captures every variant the run dispatches."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._seen = set()
        self._pending = []     # (key, meta, fn, avatar_args, avatar_kw)
        self.records = []

    def note_variant(self, key, meta: dict, fn, args, kwargs) -> bool:
        """Register one dispatch variant for later capture.  ``key`` is
        the canonical signature tuple, ``meta`` the planner's modeled
        row (variant/bytes_per_sweep/nets/...), ``fn`` the jitted
        callable and args/kwargs the REAL call arguments — avatarized
        here, BEFORE the dispatch donates them.  Returns True when the
        variant is new to this profiler."""
        if not self.enabled or key in self._seen:
            return False
        self._seen.add(key)
        av_args, av_kwargs = _avatarize((tuple(args), dict(kwargs)))
        self._pending.append((key, dict(meta), fn, av_args, av_kwargs))
        return True

    def _capture(self, key, meta, fn, args, kwargs) -> dict:
        rec = {"key": _jsonable(key), "meta": _jsonable(meta)}
        try:
            compiled = fn.lower(*args, **kwargs).compile()
        except Exception as e:
            rec["unavailable"] = (f"lower/compile failed: "
                                  f"{type(e).__name__}: {e}")
            return rec
        reasons = []
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict) and ca:
                if "flops" in ca:
                    rec["flops"] = float(ca["flops"])
                if "bytes accessed" in ca:
                    rec["bytes_accessed"] = float(ca["bytes accessed"])
            else:
                reasons.append("cost_analysis returned no properties")
        except Exception as e:
            reasons.append(f"cost_analysis: {type(e).__name__}: {e}")
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                for field, attr in (
                        ("temp_bytes", "temp_size_in_bytes"),
                        ("generated_code_bytes",
                         "generated_code_size_in_bytes"),
                        ("argument_bytes", "argument_size_in_bytes"),
                        ("output_bytes", "output_size_in_bytes")):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        rec[field] = int(v)
            else:
                reasons.append("memory_analysis returned None")
        except Exception as e:
            reasons.append(f"memory_analysis: {type(e).__name__}: {e}")
        if "bytes_accessed" not in rec and "temp_bytes" not in rec:
            rec["unavailable"] = ("backend exposes no analysis: "
                                  + "; ".join(reasons))
            return rec
        # measured-vs-modeled delta against the planner's HBM-traffic
        # model for this same dispatch (see module docstring for the
        # one-sweep-vs-loop-body semantics behind the wide band)
        modeled = meta.get("bytes_per_sweep")
        measured = rec.get("bytes_accessed")
        if modeled and measured and modeled > 0 and measured > 0:
            import math
            delta = measured / modeled
            rec["bytes_delta"] = round(delta, 6)
            rec["delta_in_band"] = (
                abs(math.log10(delta)) <= DELTA_BAND_LOG10)
        return rec

    def capture_all(self) -> list:
        """Capture every pending variant (lower+compile+analyze) and
        publish the route.devcost.* gauges.  Call this OUTSIDE measured
        regions; idempotent between notes."""
        pending, self._pending = self._pending, []
        for key, meta, fn, args, kwargs in pending:
            self.records.append(self._capture(key, meta, fn, args,
                                              kwargs))
        if self.records:
            self._publish_gauges()
        return self.records

    def _dominant(self) -> Optional[dict]:
        """The measured record covering the most nets (the same
        dominant-window rule the route.kernel.* gauges use)."""
        measured = [r for r in self.records if "unavailable" not in r]
        if not measured:
            return None
        return max(measured,
                   key=lambda r: r.get("meta", {}).get("nets", 0))

    def _publish_gauges(self) -> None:
        reg = get_metrics()
        reg.gauge("route.devcost.variants").set(len(self.records))
        dom = self._dominant()
        if dom is None:
            return
        g = {}
        for k in ("flops", "bytes_accessed", "bytes_delta"):
            if k in dom:
                g["route.devcost." + k] = dom[k]
        if "temp_bytes" in dom:
            g["route.devcost.peak_temp_bytes"] = dom["temp_bytes"]
        if "generated_code_bytes" in dom:
            g["route.devcost.generated_code_bytes"] = \
                dom["generated_code_bytes"]
        reg.set_gauges(g)

    def summary(self) -> dict:
        """The bench-row rider (detail.devcost): the dominant variant's
        measured numbers + the delta, or unavailable with reason."""
        if not self.records:
            return {"unavailable": "no dispatch variants captured"}
        dom = self._dominant()
        if dom is None:
            return {"unavailable": self.records[0].get(
                "unavailable", "no measured variants"),
                "variants": len(self.records)}
        out = {"variants": len(self.records),
               "measured_variants": len(
                   [r for r in self.records if "unavailable" not in r]),
               "delta_band_log10": DELTA_BAND_LOG10}
        for k in ("flops", "bytes_accessed", "temp_bytes",
                  "generated_code_bytes", "bytes_delta",
                  "delta_in_band"):
            if k in dom:
                out[k] = dom[k]
        modeled = dom.get("meta", {}).get("bytes_per_sweep")
        if modeled is not None:
            out["modeled_bytes_per_sweep"] = modeled
        # the modeled row is dtype-aware (router._plan_block_nets byte
        # formulas scale with the plane storage itemsize); carry the
        # dtype so a bytes_delta is never compared across dtypes
        pd = dom.get("meta", {}).get("plane_dtype")
        if pd is not None:
            out["plane_dtype"] = pd
        return out

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"delta_band_log10": DELTA_BAND_LOG10,
                       "records": self.records,
                       "summary": self.summary()}, f, indent=1)

    def reset(self) -> None:
        self._seen.clear()
        self._pending.clear()
        self.records.clear()


# process-wide profiler, same enablement pattern as the registry: note
# sites call it unconditionally (a disabled note is one attribute read);
# drivers flip .enabled
_profiler = DevProfiler()


def get_devprof() -> DevProfiler:
    return _profiler


def set_devprof(p: DevProfiler) -> DevProfiler:
    global _profiler
    _profiler = p
    return _profiler
