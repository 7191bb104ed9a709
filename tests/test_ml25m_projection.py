"""v5e-8 projection constants (VERDICT r3, Next #7).

The only currently-"met" form of the <60 s ML-25M target is the
projection; its per-window collective constant must come from
measurement (the sharded-pallas-1chip row in TPU_ROUND2.jsonl) or carry
an explicit assumed-default label, and the projection must report error
bars either way.
"""

import json

import numpy as np
import pytest

from tpu_cooccurrence.bench import ml25m, tpu_round2
from tpu_cooccurrence.bench.ml25m import PSUM_LATENCY_DEFAULT_S


@pytest.fixture(scope="module")
def measured_20k():
    """ONE measured stand-in run shared by every projection test: the
    monkeypatched capture file only changes :func:`ml25m.project_v5e8`'s
    constants (arithmetic), never the measured stream numbers — so the
    expensive measurement half runs once per module, not per test. The
    projection tests consume host/device seconds and the window count
    arithmetically, so the stream length only needs enough windows to
    make the per-window collective term visible."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("MOVIELENS_25M", raising=False)  # stand-in stream
        return ml25m.measure_full(8_000, host_only=False)


def test_sharded_overhead_absent_before_capture(tmp_path, monkeypatch):
    monkeypatch.setattr(tpu_round2, "OUT", str(tmp_path / "none.jsonl"))
    s, src = ml25m.measured_sharded_overhead()
    assert s is None and "no sharded-pallas-1chip" in src


def test_projection_constants_reject_cpu_tagged_rows(tmp_path,
                                                     monkeypatch):
    """A CPU smoke row (jax_platform=cpu) in the tracked JSONL must not
    become a projection constant — same onchip_row predicate as the
    summary (shared altitude, not per-reader filters)."""
    out = tmp_path / "rounds.jsonl"
    with open(out, "w") as f:
        f.write(json.dumps({"name": "sharded-pallas-1chip", "ok": True,
                            "jax_platform": "cpu",
                            "sharded_overhead_ms_per_window": 13.6})
                + "\n")
    monkeypatch.setattr(tpu_round2, "OUT", str(out))
    s, src2 = ml25m.measured_sharded_overhead()
    assert s is None


def test_projection_point_uses_measured_overhead(tmp_path, monkeypatch,
                                                 measured_20k):
    """VERDICT r4 Next #7: once a sharded-pallas-1chip capture exists,
    the projection's per-window collective term is the measured
    shard_map+psum overhead — zero assumed constants — and the source
    strings say which measurement each constant came from."""
    out_file = tmp_path / "rounds.jsonl"
    with open(out_file, "w") as f:
        f.write(json.dumps({"name": "sharded-pallas-1chip", "ok": True,
                            "sharded_overhead_ms_per_window": 1.25,
                            "ts": "2026-03-04 00:00:00"}) + "\n")
    monkeypatch.setattr(tpu_round2, "OUT", str(out_file))
    out = ml25m.project_v5e8(measured_20k)
    assert out["psum_latency_s"] == 1.25e-3
    assert "measured 1-chip shard_map+psum" in out["psum_latency_source"]
    assert "2026-03-04" in out["psum_latency_source"]
    assert "assumed" not in out["psum_latency_source"]
    assert "assumed" not in out["psum_latency_upper_source"]
    host = out["host_sample_seconds"]
    dev = out["device_score_seconds"]
    w = out["windows"]
    np.testing.assert_allclose(
        out["v5e8_projected_seconds"],
        round(host + dev / 8 + w * 1.25e-3, 2), atol=0.011)
    # Upper bound: twice the point estimate per window.
    np.testing.assert_allclose(
        out["v5e8_projected_range"][1],
        round(host + dev / 8 + w * 2.5e-3, 2), atol=0.011)


def test_projection_carries_error_bars(tmp_path, monkeypatch,
                                       measured_20k):
    """run_full's projection reports point, range, and both constants'
    provenance. Tiny stand-in stream keeps this a unit test."""
    monkeypatch.setattr(tpu_round2, "OUT", str(tmp_path / "none.jsonl"))
    out = ml25m.project_v5e8(measured_20k)
    assert out["synthetic_standin"] is True
    low, high = out["v5e8_projected_range"]
    assert low <= out["v5e8_projected_seconds"] <= high
    # Point estimate: the stated on-pod allowance; the ceiling doubles it.
    assert out["psum_latency_s"] == PSUM_LATENCY_DEFAULT_S
    assert "on-pod" in out["psum_latency_source"]
    assert out["psum_latency_upper_s"] == 2 * PSUM_LATENCY_DEFAULT_S
    # The range endpoints follow the stated formula.
    host = out["host_sample_seconds"]
    dev = out["device_score_seconds"]
    w = out["windows"]
    np.testing.assert_allclose(low, round(host + dev / 8, 2), atol=0.011)
    np.testing.assert_allclose(
        high, round(host + dev / 8 + w * 2 * PSUM_LATENCY_DEFAULT_S, 2),
        atol=0.011)


def test_partitioned_projection_labeled(tmp_path, monkeypatch,
                                        measured_20k):
    """The secondary host-partitioned projection must be present,
    follow host/8 + device/8 + windows*psum, and carry the
    assumed-linear-scaling label (it is arithmetic, not measurement)."""
    monkeypatch.setattr(tpu_round2, "OUT", str(tmp_path / "none.jsonl"))
    out = ml25m.project_v5e8(measured_20k)
    host = out["host_sample_seconds"]
    dev = out["device_score_seconds"]
    w = out["windows"]
    np.testing.assert_allclose(
        out["v5e8_partitioned_projected_seconds"],
        round(host / 8 + dev / 8 + w * out["psum_latency_s"], 2),
        atol=0.011)
    assert "assumed" in out["v5e8_partitioned_note"]
    assert "--partition-sampling" in out["v5e8_partitioned_note"]


def test_sparse_host_floor_mocked_mode(monkeypatch):
    """--host-only --backend sparse runs the REAL sparse scorer with
    device dispatches stubbed (reproducible sparse host floor), and the
    patches are restored afterwards."""
    import tpu_cooccurrence.state.sparse_scorer as ss

    monkeypatch.delenv("MOVIELENS_25M", raising=False)  # stand-in stream
    orig = ss._apply_update
    out = ml25m.run_full(20_000, host_only=True,
                         backend=ml25m.Backend.SPARSE)
    assert out["backend"] == "sparse-device-mocked"
    assert out["windows"] > 0 and out["pairs"] > 0
    assert ss._apply_update is orig, "device stubs leaked"
