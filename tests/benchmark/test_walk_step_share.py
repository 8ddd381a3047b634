"""``window.walk_step_share``: the reader alone on results with and
without the walk's two fields, and a CPU rehearsal of the tiny route
cell that has to end with the metric named (off the chip a share is
withheld, like every number that is not a count)."""

from types import SimpleNamespace

import bench_cells
from benchmark import harness

READER = harness.load_module(harness.find_reader(
    [bench_cells.REPO + "/benchmark"], "window.walk_step_share"))


def test_reader_returns_none_without_the_fields():
    # the parent's RouteResult has no such fields; no route, no number
    parent = SimpleNamespace(total_relax_steps=10)
    assert READER.read({"routes": [parent]}) is None
    assert READER.read({"routes": []}) is None
    assert READER.read({}) is None
    # a program that ran no windowed wave budgeted nothing
    idle = SimpleNamespace(total_walk_steps=0, total_walk_budget=0)
    assert READER.read({"routes": [idle]}) is None


def test_reader_returns_the_share_of_the_first_route():
    first = SimpleNamespace(total_walk_steps=189, total_walk_budget=4788)
    later = SimpleNamespace(total_walk_steps=1, total_walk_budget=1)
    assert READER.read({"routes": [first, later]}) == 100.0 * 189 / 4788


def test_rehearsal_names_the_metric(tmp_path):
    root = str(tmp_path / "cell")
    name = bench_cells.write_cell(root, "route")
    result = harness.run_cell(root, name, seed=2**31 + 25, seconds=0.5,
                              work_dir=str(tmp_path / "work"), trace=True)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True
    assert "window.walk_step_share" in result["rehearsal"]["withheld"]
    assert result["rehearsal"]["counts"]["window.sweeps"] >= 1
