"""The one general traffic generator for served cells.

A copy of ``tools/traffic_gen.py``'s ``make_stream`` idea -- the whole
submission plan is fixed before anything is delivered -- rebuilt so
that a mix is nothing but the parameters in its traffic file:

* a POOL of job specs: ``pool_circuit_seeds`` circuits, each as a full
  job and as a tiny one (a seeded subset of its nets, ``small_frac``
  scaled by a factor in [0.6, 1.4) drawn once from ``plan_seed``), so
  the farm re-routes designs it has seen and set-up can warm every one;
* a FIXED amount of work: ``round(rate x seconds)`` jobs, every
  ``heavy_every``-th full-size and the rest tiny, circuits and
  priorities dealt round robin, and as many exponential gaps drawn from
  ``plan_seed`` and scaled to fill the window exactly;
* ``--seed`` only ORDERS that work: the jobs and the gaps are each
  shuffled from the seed.  Every seed offers the same jobs and the same
  gaps, so runs differ by which jobs collide and by the daemon's own
  timing, never by how much was asked.  The order matters: on the chip
  the median latency of 100 jobs moved by 30% between orders where two
  runs of one order differed by 1 to 7% (my chip runs, PR 23), so a
  median over one window is the latency of that window's collisions
  and a cell has to pool more than one order or judge a steadier
  statistic (PERF.md, Open questions).

``burst_size`` > 1 sends that many jobs at each arrival instant, with
gaps that much longer: the same mean rate in bursts.  Tenants are dealt
round robin in arrival order over ``tenants`` names.
"""

from __future__ import annotations

import random
from typing import Dict, List


def pool_specs(config: dict, traffic: dict) -> List[dict]:
    """Every distinct spec the mix can send: per pool circuit one full
    job and one tiny one."""
    rng = random.Random(int(traffic["plan_seed"]))
    luts, width = int(config["luts"]), int(config["chan_width"])
    out = []
    for cs in traffic["pool_circuit_seeds"]:
        base = {"luts": luts, "chan_width": width, "seed": int(cs),
                "name": f"l{luts}_s{cs}"}
        tiny = dict(base, name=base["name"] + "_tiny",
                    net_frac=round(float(traffic["small_frac"])
                                   * rng.uniform(0.6, 1.4), 4),
                    net_seed=rng.randrange(1, 10_000))
        out.append({"heavy": True, "spec": base})
        out.append({"heavy": False, "spec": tiny})
    return out


def _tenant(i: int, traffic: dict) -> str:
    return f"t{i % int(traffic['tenants'])}"


def warmup_plan(config: dict, traffic: dict) -> List[dict]:
    """Each pool spec once, all due at once."""
    return [{"job_id": f"warm-{i:03d}", "tenant": _tenant(i, traffic),
             "priority": 0, "due_s": 0.0, "heavy": p["heavy"],
             "spec": p["spec"]}
            for i, p in enumerate(pool_specs(config, traffic))]


def window_plan(config: dict, traffic: dict, seed: int,
                seconds: float) -> List[dict]:
    """The window's submissions, in arrival order: ``job_id``,
    ``tenant``, ``priority``, ``due_s`` (from the window's start),
    ``heavy`` and ``spec``."""
    pool = pool_specs(config, traffic)
    heavies = [p for p in pool if p["heavy"]]
    tinies = [p for p in pool if not p["heavy"]]
    rate = float(traffic["rate_jobs_per_s"])
    n = max(1, int(round(rate * seconds)))
    every = max(1, int(traffic["heavy_every"]))
    burst = max(1, int(traffic.get("burst_size", 1)))
    priorities = list(traffic["priorities"])

    plan_rng = random.Random(int(traffic["plan_seed"]) + 1)
    shapes: List[Dict] = []
    n_heavy = n_tiny = 0
    for i in range(n):
        if i % every == every - 1:
            p, n_heavy = heavies[n_heavy % len(heavies)], n_heavy + 1
        else:
            p, n_tiny = tinies[n_tiny % len(tinies)], n_tiny + 1
        shapes.append({"heavy": p["heavy"], "spec": p["spec"],
                       "priority": priorities[i % len(priorities)]})
    n_arrivals = -(-n // burst)
    gaps = [plan_rng.expovariate(1.0) for _ in range(n_arrivals)]
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]

    order = random.Random(int(seed))
    order.shuffle(shapes)
    order.shuffle(gaps)
    out, t = [], 0.0
    for a in range(n_arrivals):
        # the first arrival is at the window's start, the last gap
        # closes the window: all n are due inside it
        for i in range(a * burst, min(n, (a + 1) * burst)):
            out.append(dict(shapes[i], job_id=f"w{seed}-{i:04d}",
                            tenant=_tenant(i, traffic), due_s=t))
        t += gaps[a]
    return out
