"""Throw-away cells for the CPU tests, made from temp files only: the
real configuration and traffic files at tiny sizes, a manifest that
names them, and nothing else.  That ``run_cell`` serves them without an
edit to any file under ``benchmark/`` is the proof that a later PR can
add a cell by adding files."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# fingerprint of the tiny route problem (30 LUTs, W=12)
TINY_ROUTE_SHA256 = ("ad625175c06c0385575ef0f9584a7a20"
                     "05674267b0fd7ba535b106ffa1620cfb")


def load(rel):
    with open(os.path.join(REPO, rel)) as fh:
        return json.load(fh)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


# the served cell is not in BENCHMARK.json (PERF.md, Open questions,
# row 1): the metrics its driver and readers report, as the manifest
# would list them
SERVE_METRICS = {
    "end_to_end": [
        {"name": "job_p50_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock"}],
    "per_layer": [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": "job_p50_s"}
        for name, unit, source, layer in (
            ("serve.queue_wait_share", "%", "program_span", "serving"),
            ("serve.job_p90_s", "s", "host_clock", "serving"),
            ("serve.gen_late_max_s", "s", "host_clock", "serving"),
            ("device.idle_share.serve", "%", "device_trace", "device"))],
}


def write_cell(root, kind, **traffic_changes):
    """A one-cell benchmark under ``root``; ``kind`` is ``route`` (30
    LUTs on the real cell's architecture, W=12) or ``serve`` (15-LUT
    grid, W=12, 2 pool circuits).  Returns the workload's name."""
    real = load("BENCHMARK.json")
    if kind == "route":
        metrics, cfg_name, mix = real, "tiny_k4n4", "tiny_w12"
        cfg = load("benchmark/configs/mcnc_tseng_like_k4n4.json")
        cfg["circuit"].update(num_luts=30, num_inputs=8, num_outputs=8)
        traffic = load("benchmark/traffic/route_w20.json")
        traffic.update(chan_width=12, relax_sample_nets=3,
                       trace_offset_s=0, trace_seconds=0.5,
                       problem_sha256=TINY_ROUTE_SHA256)
    else:
        metrics, cfg_name, mix = SERVE_METRICS, "tiny_daemon", "tiny_open"
        cfg = load("benchmark/configs/daemon_l60_w16.json")
        cfg.update(luts=15, chan_width=12, slice_iters=2)
        traffic = load("benchmark/traffic/small_heavy_open.json")
        traffic.update(pool_circuit_seeds=[1, 2], rate_jobs_per_s=2.0,
                       trace_offset_s=0, trace_seconds=1, drain_s=120)
    traffic.update(traffic_changes)
    name = f"tiny_{kind}"
    _dump(cfg, os.path.join(root, "cells", "configs", cfg_name + ".json"))
    _dump(traffic, os.path.join(root, "cells", "traffic", mix + ".json"))

    def mine(group):
        return [dict(m, workloads=[name]) for m in metrics[group]]

    _dump({
        "command": real["command"], "paths": ["cells"],
        "run_seconds": real["run_seconds"],
        "configs": [{"name": cfg_name, "source": cfg["source"],
                     "file": f"cells/configs/{cfg_name}.json",
                     "reduced": [], "why": "test size"}],
        "workloads": [{"name": name, "config": cfg_name, "traffic": mix,
                       "chips": 1, "why": "test size"}],
        "end_to_end": mine("end_to_end"),
        "per_layer": mine("per_layer"),
    }, os.path.join(root, "BENCHMARK.json"))
    return name


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def assert_cpu_result(result):
    """The contract's keys, the CPU named, and no metric at all: a
    number from a CPU run is never written under a metric's name."""
    assert RESULT_KEYS <= set(result)
    json.dumps(result)
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
