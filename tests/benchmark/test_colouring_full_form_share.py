"""``window.colouring_full_form_share``: the reader alone on registries
with and without the colouring's form counters, the manifest's entry,
and a CPU rehearsal of the tiny route cell that has to end with the
metric named (off the chip a share is withheld, like every number that
is not a count)."""

import pytest

import bench_cells
from benchmark import harness

NAME = "window.colouring_full_form_share"
READER = harness.load_module(harness.find_reader(
    [bench_cells.REPO + "/benchmark"], NAME))


@pytest.mark.parametrize("ctx", [
    # the parent's registry counts programs and reads, no forms
    {"registry": {"route.mis_colors.calls_total": 12,
                  "route.mis_colors.read_total": 7}},
    # forms and no dispatch
    {"registry": {"route.mis_colors.calls_total": 0,
                  "route.mis_colors.full_total": 0}},
    {"registry": {"route.mis_colors.full_total": 3}},
    # no registry, no number
    {"registry": {}}, {}], ids=["parent", "idle", "half", "empty", "bare"])
def test_reader_returns_none_without_the_counters(ctx):
    assert READER.read(ctx) is None


@pytest.mark.parametrize("forms, want", [
    # route_scale as ISSUE 46 reckons it: 12 programs, 7 read
    ({"skipped": 5, "short": 4, "full": 3}, 25.0),
    # a counter nothing ever incremented is absent, not zero
    ({"skipped": 5, "short": 7}, 0.0),
    ({"full": 12}, 100.0)], ids=["mixed", "none_full", "all_full"])
def test_reader_returns_the_full_forms_share_of_the_programs(forms, want):
    reg = {f"route.mis_colors.{k}_total": v for k, v in forms.items()}
    reg["route.mis_colors.calls_total"] = 12
    reg["route.mis_colors.read_total"] = 7
    assert READER.read({"registry": reg}) == want


def test_the_manifest_lists_the_metric_for_the_eight_route_cells():
    """One-way checks only: a later cell or metric appended to the
    manifest needs no edit here."""
    manifest = bench_cells.load("BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "window program",
        "moves": "route_s"}
    assert {"route_relaxed", "route_k6n10_relaxed", "route_tight",
            "route_scale", "route_hetero", "route_fanout", "route_dsp",
            "route_scale_6k"} <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in manifest["workloads"]}


def test_rehearsal_names_the_metric(tmp_path):
    root = str(tmp_path / "cell")
    name = bench_cells.write_cell(root, "route")
    result = harness.run_cell(root, name, seed=2**31 + 46, seconds=0.5,
                              work_dir=str(tmp_path / "work"), trace=True)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True
    assert NAME in result["rehearsal"]["withheld"]
