"""``window.sink_pick_read_share``: the reader alone on results with and
without the sink pick's two fields, the manifest's entry, and a CPU
rehearsal of the tiny route cell that has to end with the metric named
(off the chip a share is withheld, like every number that is not a
count)."""

from types import SimpleNamespace

import pytest

import bench_cells
from benchmark import harness

NAME = "window.sink_pick_read_share"
READER = harness.load_module(harness.find_reader(
    [bench_cells.REPO + "/benchmark"], NAME))


@pytest.mark.parametrize("ctx", [
    # the parent's RouteResult has no such fields
    {"routes": [SimpleNamespace(total_relax_steps=10)]},
    # one of the two alone
    {"routes": [SimpleNamespace(total_sink_reads=5)]},
    # a program that ran no windowed wave read nothing
    {"routes": [SimpleNamespace(total_sink_reads=0,
                                total_sink_reads_dense=0)]},
    # no route, no number
    {"routes": []}, {}], ids=["parent", "half", "idle", "empty", "bare"])
def test_reader_returns_none_without_the_fields(ctx):
    assert READER.read(ctx) is None


def test_reader_returns_the_share_of_the_first_route():
    first = SimpleNamespace(total_sink_reads=26_624,
                            total_sink_reads_dense=212_992)
    later = SimpleNamespace(total_sink_reads=1, total_sink_reads_dense=1)
    assert READER.read({"routes": [first, later]}) == 12.5
    # a program that compacts nothing reads 100%
    dense = SimpleNamespace(total_sink_reads=7, total_sink_reads_dense=7)
    assert READER.read({"routes": [dense]}) == 100.0


SIX_CELLS = ["route_relaxed", "route_k6n10_relaxed", "route_tight",
             "route_scale", "route_hetero", "route_fanout"]


def test_the_manifest_lists_the_metric_for_the_six_route_cells():
    """One-way checks only: a later cell or metric appended to the
    manifest needs no edit here."""
    manifest = bench_cells.load("BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "window program",
        "moves": "route_s"}
    assert set(SIX_CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in manifest["workloads"]}


def test_rehearsal_names_the_metric(tmp_path):
    root = str(tmp_path / "cell")
    name = bench_cells.write_cell(root, "route")
    result = harness.run_cell(root, name, seed=2**31 + 38, seconds=0.5,
                              work_dir=str(tmp_path / "work"), trace=True)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True
    assert NAME in result["rehearsal"]["withheld"]
