"""BENCHMARK.json against the rules that refuse it before any run, and
the command itself off the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench_cells
from benchmark import harness

REPO = bench_cells.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(REPO)


def _line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_shape_names_units_and_lengths(manifest):
    assert set(manifest) == KEYS["top"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) for p in manifest["paths"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
        for e in manifest[group]:
            assert KEYS[group] <= set(e) <= KEYS[group] | (
                {"workloads"} if group in ("end_to_end", "per_layer")
                else set()), e
            assert NAME.match(e["name"]), e["name"]
    for c in manifest["configs"]:
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert _line(m["layer"])


def test_every_named_file_exists_under_paths(manifest):
    paths = manifest["paths"]
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in paths)
        assert os.path.isfile(os.path.join(REPO, f))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in manifest["workloads"]:
        cell = harness.load_cell(manifest, REPO, w["name"])
        assert os.path.isfile(cell.find(
            "drivers", cell.traffic["driver"], ".py"))
        # what a configuration file says it cut is what the manifest lists
        entry = next(c for c in manifest["configs"]
                     if c["name"] == w["config"])
        assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])
        assert cell.config["source"] == entry["source"]
    for m in manifest["per_layer"]:
        assert os.path.isfile(harness.find_reader(
            harness.search_dirs(manifest, REPO), m["name"]))
    for p in paths:
        for base, _, names in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in base:
                continue
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), REPO)
                assert PATH.match(rel), rel


def test_every_cell_reports_what_its_metrics_move(manifest):
    e2e = [m["name"] for m in manifest["end_to_end"]]
    assert "setup_s" in e2e
    # ISSUE 23: at most four end-to-end metrics besides the set-up time
    assert 1 <= len(e2e) - 1 <= 4
    # a per-layer metric always lists its cells
    assert all(m.get("workloads") for m in manifest["per_layer"])
    setup = next(m for m in manifest["end_to_end"]
                 if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25
    cells = [w["name"] for w in manifest["workloads"]]
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    for cell in cells:
        mine = {m["name"] for m in
                harness.metrics_of(manifest, "end_to_end", cell)}
        assert "setup_s" in mine and len(mine) >= 2
        layer = harness.metrics_of(manifest, "per_layer", cell)
        assert layer
        for m in layer:
            assert m["moves"] in mine, (cell, m["name"], m["moves"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells


def test_one_reader_serves_a_quantity_split_by_what_it_moves(tmp_path):
    """``device.idle_share.route`` and ``device.idle_share.serve`` are
    one quantity under two manifest names: both find the reader
    ``device.idle_share.py``; a reader of the full name wins; a name no
    file answers to is an error."""
    search = [os.path.join(REPO, "benchmark")]
    one = harness.find_reader(search, "device.idle_share.route")
    assert one == harness.find_reader(search, "device.idle_share.serve")
    assert os.path.basename(one) == "device.idle_share.py"
    own = tmp_path / "layer_metrics" / "device.idle_share.route.py"
    own.parent.mkdir()
    own.write_text("def read(ctx):\n    return 1.0\n")
    assert harness.find_reader([str(tmp_path)] + search,
                               "device.idle_share.route") == str(own)
    with pytest.raises(FileNotFoundError):
        harness.find_reader(search, "no.such.metric")


@pytest.mark.parametrize("change, error", [
    ({"problem": "no_such_builder"}, FileNotFoundError),
    ({"arch": {"builder": "no_such_arch", "args": {}}}, AttributeError),
    ({"placement": {"placer": "no_such_placer", "args": {}}},
     AttributeError),
])
def test_a_configuration_names_how_its_problem_is_built(tmp_path, change,
                                                        error):
    """The builder module, the architecture's builder and the placer
    are names in the configuration file; a name nothing answers to is
    an error, never a silent default."""
    from benchmark import problem

    name = bench_cells.write_cell(str(tmp_path), "route")
    cfg_path = tmp_path / "cells" / "configs" / "tiny_k4n4.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(change)
    cfg_path.write_text(json.dumps(cfg))
    cell = harness.load_cell(harness.load_manifest(str(tmp_path)),
                             str(tmp_path), name)
    with pytest.raises(error):
        problem.build_placed(cell, 12)


def test_another_builtin_architecture_needs_no_code(tmp_path):
    """A configuration on another of the program's architectures
    (single-driver unidirectional wires) is data alone."""
    from benchmark import problem

    name = bench_cells.write_cell(str(tmp_path), "route")
    cfg_path = tmp_path / "cells" / "configs" / "tiny_k4n4.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["arch"] = {"builder": "unidir_arch",
                   "args": {"K": 4, "N": 2, "I": 6, "length": 2}}
    cfg_path.write_text(json.dumps(cfg))
    cell = harness.load_cell(harness.load_manifest(str(tmp_path)),
                             str(tmp_path), name)
    f = problem.build_placed(cell, 12)
    assert f.rr.num_nodes > 0 and f.term.num_nets > 0
    assert problem.fingerprint(f) != bench_cells.TINY_ROUTE_SHA256


def _run_py(cwd, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", "route_relaxed", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300)


def _no_result_line(stdout):
    for ln in stdout.splitlines():
        try:
            doc = json.loads(ln)
        except ValueError:
            continue
        assert not (isinstance(doc, dict) and "correct" in doc), ln


def test_command_exits_non_zero_off_the_chip():
    """conftest pins this process tree to the CPU: the command must
    refuse it, say why, and print no result."""
    r = _run_py(REPO)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    _no_result_line(r.stdout)


def test_command_refuses_a_directory_without_the_program(tmp_path,
                                                         manifest):
    """Only BENCHMARK.json and the files under ``paths``: nothing to
    measure, so no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(str(tmp_path))
    assert r.returncode != 0
    assert "parallel_eda_tpu" in r.stderr
    _no_result_line(r.stdout)
