"""trace_reduce on the small recorded trace: busy time, idle share, op
table and gap attribution as worked out by hand in the fixture."""

import pytest

import bench_cells
from benchmark import trace_reduce

FIXTURE = bench_cells.load("benchmark/fixtures/two_ops_one_gap.json")


def test_fixture_reduces_to_the_hand_worked_numbers():
    red = trace_reduce.reduce(FIXTURE["planes"])
    assert red["window_s"] == pytest.approx(20e-6)
    assert red["busy_s"] == pytest.approx(10e-6)
    assert red["idle_share"] == pytest.approx(0.5)
    assert red["n_device_planes"] == 1 and red["n_device_events"] == 3
    # self times: the while is charged only what its body leaves
    assert dict(red["device_ops"]) == pytest.approx(
        {"fusion.1": 4e-6, "while.2": 4e-6, "fusion.3": 2e-6})
    assert red["idle_gaps"] == [
        [trace_reduce.UNNAMED, pytest.approx(6e-6)],
        ["bench.route", pytest.approx(3e-6)],
        [trace_reduce.UNNAMED, pytest.approx(1e-6)]]


def test_without_a_window_span_the_events_set_the_window():
    planes = [dict(p, lines=[dict(ln, events=[
        e for e in ln["events"] if e[0] != trace_reduce.WINDOW_SPAN])
        for ln in p["lines"]]) for p in FIXTURE["planes"]]
    red = trace_reduce.reduce(planes)
    assert red["window_s"] == pytest.approx(13e-6)      # 1000 .. 14000
    assert red["busy_s"] == pytest.approx(10e-6)


def test_two_device_planes_average_their_busy_time():
    dev = FIXTURE["planes"][0]
    idle = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.9", 0, 2000]]}]}
    red = trace_reduce.reduce([dev, idle, FIXTURE["planes"][1]])
    assert red["busy_s"] == pytest.approx((10e-6 + 2e-6) / 2)


def test_no_device_plane_reads_as_nothing_to_read():
    red = trace_reduce.reduce([FIXTURE["planes"][1]])
    assert red["busy_s"] == 0.0 and red["n_device_planes"] == 0
    assert red["device_ops"] == [] and red["idle_gaps"] == []


def test_merge_and_self_times():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert trace_reduce.self_times([["a", 0, 10], ["b", 2, 3],
                                    ["c", 3, 1], ["a", 20, 5]]) == \
        {"a": 12, "b": 2, "c": 1}


def test_host_spans_are_laid_onto_the_trace_clock():
    """A span the harness timed from 1 us before the window span's
    start, for 4 us, lands at window_ns - 1000 on the trace's clock."""
    planes = FIXTURE["planes"]
    plane = trace_reduce.host_spans_plane(
        planes, [("bench.route", 9.999999, 10.000003)], window_t0=10.0)
    (name, start, dur), = plane["lines"][0]["events"]
    assert name == "bench.route"
    assert start == pytest.approx(-1000.0, abs=1.0)
    assert dur == pytest.approx(4000.0, abs=1.0)
    # and with no window span in the trace there is nothing to pin to
    assert trace_reduce.host_spans_plane(
        [planes[0]], [("bench.route", 0.0, 1.0)], 0.0
    )["lines"][0]["events"] == []
