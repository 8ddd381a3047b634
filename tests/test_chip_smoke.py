"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, 2.1): the
script's phase functions at a tiny size.  Only the script's ``main``
checks for the chip, so these prove paths, arguments and control flow —
never anything about the device.

The four-chip sub-phases rehearse on 4 of conftest's virtual devices.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_route_tiny(capsys):
    out = chip_smoke.phase_route(luts=30, chan_width=12)
    assert out["success"] and out["native_success"]
    assert out["wirelength"] == out["wirelength_second_run"]
    assert out["dispatch_compiles"] > 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "route" and line["luts"] == 30


def test_phase_flow_tiny(tmp_path):
    out = chip_smoke.phase_flow(luts=20, chan_width=16,
                                out_dir=str(tmp_path / "flow"))
    assert out["rc"] == 0 and len(out["artifacts"]) >= 3


def test_phase_daemon_tiny(tmp_path):
    out = chip_smoke.phase_daemon(luts=15, chan_width=12, slice_iters=2,
                                  inbox=str(tmp_path / "inbox"),
                                  n_jobs=2)
    assert out["wirelength"] == out["solo_wirelength"]
    assert not any(out["resil"].values())
    # a fresh inbox only: a second daemon phase must not reuse it
    with pytest.raises(FileExistsError):
        chip_smoke.phase_daemon(luts=15, chan_width=12, slice_iters=2,
                                inbox=str(tmp_path / "inbox"), n_jobs=2)


def test_phase_mesh_tiny_on_virtual_devices():
    out = chip_smoke.phase_mesh(luts=30, chan_width=12, n_devices=4)
    assert out["mesh_gspmd"] == out["reference"] == out["mesh_row"]
    assert out["transport"] == "ppermute"     # no remote DMA off-TPU


@pytest.mark.parametrize("before,after,held", [
    # every allocator rose; the reference's own device (0) only has to
    # be non-zero, its mark was set by the one-device route
    ({0: 900, 1: 0, 2: 0, 3: 0}, {0: 900, 1: 5, 2: 7, 3: 9},
     [0, 1, 2, 3]),
    # everything stayed on the first device
    ({0: 900, 1: 0, 2: 0, 3: 0}, {0: 999, 1: 0, 2: 0, 3: 0}, [0]),
    # a device that held bytes before and gained none is not counted
    ({0: 900, 1: 64, 2: 0, 3: 0}, {0: 900, 1: 64, 2: 7, 3: 9},
     [0, 2, 3]),
])
def test_held_data_reads_the_allocators(before, after, held):
    assert chip_smoke._held_data(before, after, ref_id=0) == held


def test_main_refuses_without_a_tpu():
    """No chip -> non-zero exit, no phase ran, no result line."""
    for extra in ([], ["--four-chips"]):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *extra],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        assert "chip_smoke" in r.stderr


def test_phase_fleet_tiny_stops_at_the_chip_check(tmp_path, capsys):
    """Two CPU workers pass everything but the last check — each worker
    on its own TPU chip — which nothing relaxes."""
    with pytest.raises(RuntimeError, match="own TPU chip"):
        chip_smoke.phase_fleet(luts=15, chan_width=12, n_workers=2,
                               n_jobs=4, base_dir=str(tmp_path),
                               slice_iters=2)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "fleet4"
    assert line["wirelength"] == line["solo_wirelength"]
    assert len(line["wirelength"]) == 4
    assert all(w["jobs_done"] == 2 and w["device"]["count"] >= 1
               for w in line["worker_devices"].values())
    # the product's own liveness settings: nothing lapsed or moved
    assert line["leases"]["leases_acquired"] == 4
    assert not any(line["leases"][k] for k in (
        "leases_lost", "leases_expired", "lease_steals",
        "jobs_failed_over"))
    # each worker's stderr is kept under the inbox
    assert sorted(n for n in os.listdir(tmp_path / "fleet")
                  if n.startswith("stderr.")) == ["stderr.w0.log",
                                                  "stderr.w1.log"]
