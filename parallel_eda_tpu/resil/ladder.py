"""The graceful-degradation ladder.

Three dimensions, each an ordered list of execution levels, fastest
first, all bit-identical in QoR:

  pipeline: pipelined -> sync
  program:  aot -> jit
  mesh:     pallas_halo -> ppermute -> single_chip   (multi-chip
            halo-exchange relaxation, route/planes_shard.py: the
            overlapped remote-DMA transport, the on-critical-path
            ppermute transport, and the one-device floor a lost mesh
            member lands on — router._mesh_demote).  pallas_halo only
            engages on TPU backends; elsewhere ppermute is the top
            working rung.  Inert unless RouterOpts.mesh_shards > 1.

"program" descends *per dispatch-variant* inside ``DispatchGuard``
(quarantine picks the rung); the ladder records every such step.
"pipeline" and a floor override for "program" are *global*: the
service steps them when a whole job attempt is poisoned, and the
router consults ``level()`` when building a dispatch chain.
Every step is observable — the ``route.resil.degradation_steps``
counter, per-dimension ``route.resil.level.<dim>`` gauges, and a
trace instant.
"""

from typing import Dict, List, Optional

from ..obs.metrics import get_metrics
from ..obs.trace import get_tracer

DIMS: Dict[str, tuple] = {
    "pipeline": ("pipelined", "sync"),
    "program": ("aot", "jit"),
    "mesh": ("pallas_halo", "ppermute", "single_chip"),
}

# Rung labels (watchdog chain) -> ladder dimension, for step records.
_LABEL_DIM = {
    "aot": "program",
    "jit": "program",
    "pallas_halo": "mesh",
    "ppermute": "mesh",
    "single_chip": "mesh",
}


class DegradationLadder:
    def __init__(self):
        self._level = {dim: 0 for dim in DIMS}
        m = get_metrics()
        for dim, lvl in self._level.items():
            m.gauge(f"route.resil.level.{dim}").set(lvl)

    def level(self, dim: str) -> int:
        return self._level[dim]

    def name(self, dim: str) -> str:
        return DIMS[dim][min(self._level[dim], len(DIMS[dim]) - 1)]

    def record(self, from_label: str, reason: str) -> None:
        """Log one per-variant step-down (quarantine of ``from_label``)
        without moving the global level."""
        m = get_metrics()
        m.counter("route.resil.degradation_steps").inc()
        tr = get_tracer()
        if tr is not None:
            tr.instant("route.resil.degrade", cat="resil",
                       dim=_LABEL_DIM.get(from_label, "?"),
                       rung=from_label, reason=reason[:200])

    def step(self, dim: str, reason: str = "") -> bool:
        """Move a global dimension one level down; False at the floor."""
        names = DIMS[dim]
        if self._level[dim] >= len(names) - 1:
            return False
        self._level[dim] += 1
        m = get_metrics()
        m.counter("route.resil.degradation_steps").inc()
        m.gauge(f"route.resil.level.{dim}").set(self._level[dim])
        tr = get_tracer()
        if tr is not None:
            tr.instant("route.resil.degrade", cat="resil", dim=dim,
                       to=self.name(dim), reason=reason[:200])
        return True

    def snapshot(self) -> Dict[str, str]:
        return {dim: self.name(dim) for dim in DIMS}
