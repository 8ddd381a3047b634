"""Resilience layer: seeded fault injection, durable checkpoints,
dispatch watchdog/quarantine, and the graceful-degradation ladder.

The contract every component here enforces is the repo's bit-identical
discipline: a recovery action may change *timing* (retries, backoff,
slower fallback programs) but never *QoR*.  Each rung of the
degradation ladder is one of the already-proven bit-identical
alternates (AOT library vs live jit, the mesh transports,
pipelined vs --sync, checkpoint-resume vs straight-through), so a run
that weathers injected faults must finish with wirelength identical to
the fault-free run — the chaos CI gate asserts exactly that.
"""

from dataclasses import dataclass, field
from typing import Optional

import time

from .faults import (
    SITES,
    Fault,
    FaultInjected,
    BackendLostError,
    FaultPlan,
)
from .checkpoint import CheckpointStore
from .journal import Heartbeat, JournalStore, LeaseStore
from .watchdog import DispatchGuard, DispatchPoisonedError, Rung
from .ladder import DegradationLadder


@dataclass
class ResilOpts:
    """User-facing resilience configuration (see serve/cli.py flags)."""

    fault_plan: Optional[FaultPlan] = None
    checkpoint_dir: Optional[str] = None
    diag_dir: Optional[str] = None
    watchdog_s: float = 120.0
    dispatch_attempts: int = 2
    backoff_s: float = 0.05
    backoff_mult: float = 2.0
    backoff_max_s: float = 2.0


class Resilience:
    """Runtime bundle threaded through RouterOpts.resil.

    Owns the fault plan, the per-dispatch guard, the global
    degradation ladder, and (when a checkpoint_dir is configured) the
    durable checkpoint store.  One instance per RouteService; the
    router only duck-types against ``.plan``, ``.guard`` and
    ``.ladder``.
    """

    def __init__(self, opts: ResilOpts, clock=time.monotonic,
                 sleep=time.sleep):
        self.opts = opts
        self.plan = opts.fault_plan
        self.ladder = DegradationLadder()
        self.guard = DispatchGuard(
            max_attempts=opts.dispatch_attempts,
            timeout_s=opts.watchdog_s,
            backoff_s=opts.backoff_s,
            backoff_mult=opts.backoff_mult,
            backoff_max_s=opts.backoff_max_s,
            plan=self.plan,
            ladder=self.ladder,
            clock=clock,
            sleep=sleep,
        )
        self.store = (CheckpointStore(opts.checkpoint_dir, plan=self.plan)
                      if opts.checkpoint_dir else None)


__all__ = [
    "SITES",
    "Fault",
    "FaultInjected",
    "BackendLostError",
    "FaultPlan",
    "CheckpointStore",
    "Heartbeat",
    "JournalStore",
    "LeaseStore",
    "DispatchGuard",
    "DispatchPoisonedError",
    "Rung",
    "DegradationLadder",
    "ResilOpts",
    "Resilience",
]
