"""``window.walk_slot_read_share``: the reader alone on results with and
without the walk scatters' field, the manifest's entry, and a CPU
rehearsal of the tiny route cell that has to end with the metric named
(off the chip a share is withheld, like every number that is not a
count)."""

from types import SimpleNamespace

import pytest

import bench_cells
from benchmark import harness

NAME = "window.walk_slot_read_share"
READER = harness.load_module(harness.find_reader(
    [bench_cells.REPO + "/benchmark"], NAME))


@pytest.mark.parametrize("ctx", [
    # the parent's RouteResult budgets and counts steps, reads nothing
    {"routes": [SimpleNamespace(total_walk_steps=189,
                                total_walk_budget=4788)]},
    # the field alone
    {"routes": [SimpleNamespace(total_walk_slots_read=5)]},
    # a program that ran no windowed wave budgeted nothing
    {"routes": [SimpleNamespace(total_walk_slots_read=0,
                                total_walk_budget=0)]},
    # no route, no number
    {"routes": []}, {}], ids=["parent", "half", "idle", "empty", "bare"])
def test_reader_returns_none_without_the_fields(ctx):
    assert READER.read(ctx) is None


def test_reader_returns_the_share_of_the_first_route():
    first = SimpleNamespace(total_walk_steps=3544, total_walk_budget=87420,
                            total_walk_slots_read=14880)
    later = SimpleNamespace(total_walk_slots_read=1, total_walk_budget=1)
    assert READER.read({"routes": [first, later]}) == 100.0 * 14880 / 87420
    # a program that scatters every slot reads 100%
    dense = SimpleNamespace(total_walk_slots_read=7, total_walk_budget=7)
    assert READER.read({"routes": [dense]}) == 100.0


SEVEN_CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
               "route_scale", "route_hetero", "route_fanout", "route_dsp"]


def test_the_manifest_lists_the_metric_for_the_seven_route_cells():
    """One-way checks only: a later cell or metric appended to the
    manifest needs no edit here."""
    manifest = bench_cells.load("BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "window program",
        "moves": "route_s"}
    assert set(SEVEN_CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in manifest["workloads"]}
    # the step share it is read beside reports in the same cells
    floor = next(m for m in manifest["per_layer"]
                 if m["name"] == "window.walk_step_share")
    assert set(floor.get("workloads", entry["workloads"])) \
        >= set(entry["workloads"])


def test_rehearsal_names_the_metric(tmp_path):
    root = str(tmp_path / "cell")
    name = bench_cells.write_cell(root, "route")
    result = harness.run_cell(root, name, seed=2**31 + 42, seconds=0.5,
                              work_dir=str(tmp_path / "work"), trace=True)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True
    assert NAME in result["rehearsal"]["withheld"]
