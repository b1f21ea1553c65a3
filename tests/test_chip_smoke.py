"""chip_smoke.py's phases at tiny sizes on the CPU: the references the chip
run is judged by must agree with the runtime here first, and the script
must refuse to report a result without a TPU."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402


def test_window_phase_matches_reference():
    res = chip_smoke.phase_window(n=8192, chunk=1024, n_syms=16, per_ms=4,
                                  cap=8192, keys=64, seed=1)
    assert res["rows_checked"] > 0 and res["overflow"] == 0
    assert res["warmup_errors"] == 0


def test_seq5_phase_matches_reference():
    # 20 ms/event puts the 60 s `within` bound at 3,000 events, so
    # partials expire inside the run and the boundary is exercised
    res = chip_smoke.phase_seq5(n=8192, chunk=1024, n_syms=4,
                                ms_per_event=20, seed=1)
    assert res["matches"] > 0 and res["overflow"] == 0


def test_seq5_reference_within_boundary():
    names = ["A"]
    ts = chip_smoke.np.array([0, 10, 20, 30, 60_000, 60_001], "int64")
    sym = chip_smoke.np.zeros(6, "int64")
    stage = chip_smoke.np.array([1, 1, 2, 3, 4, 5], "int32")
    v = chip_smoke.np.arange(6, dtype="int32")
    # the partial opened at t=0 is 60,001 ms old at e5: dropped; the one
    # opened at t=10 is 59,991 ms old: kept
    assert chip_smoke.seq5_reference(ts, sym, stage, v, names) == \
        [("A", 1, 5)]


def test_pool_phase_matches_reference():
    res = chip_smoke.phase_pool(tenants=4, rows=512, batch_max=128, seed=1)
    assert res["rows_checked"] > 0 and res["double_max_rel_err"] == 0.0


def test_partition_phase_mesh_equals_one_device():
    assert len(jax.devices()) >= 4
    res = chip_smoke.phase_partition(n_devices=4, n=4096, chunk=1024,
                                     n_syms=8, per_ms=4, cap=1024, seed=1)
    assert res["rows_checked"] > 0
    assert len(res["peak_bytes_per_device"]) == 4


def test_refuses_to_report_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setenv("SIDDHI_TPU_STATE_DIR", "unused")
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code == 2
    assert capsys.readouterr().out == ""
