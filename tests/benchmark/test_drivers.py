"""The route driver on the CPU at a tiny size: paths, arguments and
control flow, never anything about the device.  Three runs share one
process (and so one set of compiled programs): the sound one, the
lower-precision control, and a run whose timed path is broken
underneath."""

import numpy as np
import pytest

import bench_cells
from benchmark import harness


@pytest.fixture(scope="module")
def route_cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("route_cell")
    return str(root), bench_cells.write_cell(str(root), "route")


def _run(route_cell, tmp_path, **kw):
    root, name = route_cell
    return harness.run_cell(root, name, seed=2**31 + 11, seconds=1.0,
                            work_dir=str(tmp_path), **kw)


def test_route_loop_tiny(route_cell, tmp_path, capsys):
    result = _run(route_cell, tmp_path, trace=False)
    bench_cells.assert_cpu_result(result)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    out = capsys.readouterr().out
    # every number compared is printed beside its limit
    for name in ("routes_not_legal", "sink_delay_gap", "relax_gap",
                 "wirelength_x", "compiles_in_window"):
        assert f"check {name}:" in out


def test_route_loop_traced_reads_counts(route_cell, tmp_path):
    """--trace 1 off the chip: the readers run, the counts come back,
    and every time or share stays withheld."""
    result = _run(route_cell, tmp_path, trace=True)
    bench_cells.assert_cpu_result(result)
    counts = result["rehearsal"]["counts"]
    assert counts["negotiation.iterations"] >= 1
    assert counts["window.sweeps"] >= 1


def test_control_bf16_planes_is_not_correct(route_cell, tmp_path, capsys):
    """The control: the program's own lower-precision path (bfloat16
    planes committed without the guard) in the timed path's place has
    to come out as not correct, by the sink delays and the relaxation
    gap, at the limits the cell uses."""
    result = _run(route_cell, tmp_path, trace=False, router_overrides={
        "plane_dtype": "bf16", "dtype_guard": "off"})
    assert result["correct"] is False
    out = capsys.readouterr().out
    failed = [ln.split(":")[0][len("check "):] for ln in out.splitlines()
              if ln.startswith("check ") and ln.endswith("NOT ok")]
    assert "sink_delay_gap" in failed and "relax_gap" in failed


def test_broken_timed_path_is_not_correct(route_cell, tmp_path,
                                          monkeypatch):
    """A route altered where it is produced: one sink's path dropped
    after every run_route.  Everything else of a run is driven as it
    is, and ``correct`` comes out false."""
    from parallel_eda_tpu import flow as F

    real = F.run_route

    def broken(f, *a, **kw):
        out = real(f, *a, **kw)
        f.route.paths = np.array(f.route.paths)
        f.route.paths[0, 0, :] = f.rr.num_nodes     # the pad sentinel
        return out

    monkeypatch.setattr(F, "run_route", broken)
    result = _run(route_cell, tmp_path, trace=False)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
