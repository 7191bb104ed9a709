"""Machine-generated on-chip summary + the guard name-shadowing fix."""

import json

from tpu_cooccurrence.bench import summarize, tpu_round2


def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_latest_by_name_maps_historic_config4_rows():
    rows = [
        {"name": "zipfian-1M-items", "ok": True, "backend": "hybrid",
         "pairs_per_sec": 32098.6},
        {"name": "zipfian-1M-items", "ok": True, "backend": "sparse",
         "pairs_per_sec": 71862.0},
        {"name": "config4-sparse", "ok": False, "error": "dead"},
        {"name": "ml25m-full", "ok": True, "seconds": 181.5},
    ]
    latest = summarize.latest_by_name(rows)
    assert latest["config4-sparse"]["pairs_per_sec"] == 71862.0
    assert latest["config4-hybrid"]["pairs_per_sec"] == 32098.6
    assert latest["ml25m-full"]["seconds"] == 181.5


def test_latest_by_name_rejects_non_tpu_platform_rows():
    """An ok row tagged jax_platform=cpu (smoke run whose OUT override
    was lost) must never become the latest on-chip number; untagged
    historic rows and tpu-tagged rows pass."""
    rows = [
        {"name": "config4-headline", "ok": True, "pairs_per_sec": 1.0,
         "jax_platform": "tpu"},
        {"name": "config4-headline", "ok": True, "pairs_per_sec": 9e9,
         "jax_platform": "cpu"},
        {"name": "ml25m-full", "ok": True, "seconds": 181.5},  # historic
    ]
    latest = summarize.latest_by_name(rows)
    assert latest["config4-headline"]["pairs_per_sec"] == 1.0
    assert latest["ml25m-full"]["seconds"] == 181.5


def test_render_sharded_overhead_line(tmp_path, monkeypatch):
    r2 = tmp_path / "rounds.jsonl"
    _write_jsonl(r2, [
        {"name": "sharded-pallas-1chip", "ok": True,
         "jax_platform": "tpu", "ts": "2026-08-01 00:05:00",
         "sharded_dense_int16": {"scores_allclose": True},
         "sharded_sparse": {"scores_allclose": True},
         "step_ms_per_window_unsharded": 10.0,
         "step_ms_per_window_sharded_1dev": 11.2,
         "sharded_overhead_ms_per_window": 1.2,
         "overhead_vocab": 59_047},
    ])
    monkeypatch.setattr(summarize, "ROUND2_PATH", str(r2))
    monkeypatch.setattr(summarize, "HISTORY_PATH",
                        str(tmp_path / "none.jsonl"))
    text = summarize.render()
    assert "1.2 ms/window" in text
    assert "59047-item row sums" in text
    assert "measured point estimate" in text


def test_render_targets_and_regeneration(tmp_path, monkeypatch):
    r2 = tmp_path / "rounds.jsonl"
    hist = tmp_path / "hist.jsonl"
    _write_jsonl(r2, [
        {"name": "config4-sparse", "ok": True, "pairs_per_sec": 500_000,
         "ts": "2026-08-01 00:00:00"},
        {"name": "ml25m-sparse", "ok": True, "seconds": 42.0,
         "ts": "2026-08-01 00:10:00"},
    ])
    _write_jsonl(hist, [
        {"ts": "2026-08-01 00:20:00", "pairs_per_sec": 3_000_000,
         "vs_baseline": 25.9, "backend": "tpu"},
    ])
    monkeypatch.setattr(tpu_round2, "OUT", str(r2))
    monkeypatch.setattr(summarize, "ROUND2_PATH", str(r2))
    monkeypatch.setattr(summarize, "HISTORY_PATH", str(hist))
    text = summarize.render()
    assert "25.9x host oracle" in text and text.count("**MET**") >= 3
    assert "500,000 pairs/s" in text
    assert "42.0 s single-chip** (**MET**)" in text


def test_render_config4_headline_and_upload_ab(tmp_path, monkeypatch):
    """A short session landing only the headline rows still reaches the
    summary; the upload A/B renders a verdict only on comparable rows
    (same event count) and flags mixed provenance instead."""
    r2 = tmp_path / "rounds.jsonl"
    rows = [
        {"name": "config4-headline", "ok": True, "pairs_per_sec": 480_000,
         "events": 1_000_000, "mode": "L16/fixed",
         "ts": "2026-08-01 00:00:00"},
        {"name": "config4-chunked", "ok": True, "pairs_per_sec": 700_000,
         "events": 1_000_000, "mode": "L16/fixed/chunks4",
         "ts": "2026-08-01 00:05:00"},
    ]
    _write_jsonl(r2, rows)
    monkeypatch.setattr(tpu_round2, "OUT", str(r2))
    monkeypatch.setattr(summarize, "ROUND2_PATH", str(r2))
    monkeypatch.setattr(summarize, "HISTORY_PATH",
                        str(tmp_path / "none.jsonl"))
    text = summarize.render()
    assert "700,000 pairs/s** (config4-chunked" in text
    assert "**MET**" in text            # 700k >= 458k target
    assert "chunked upload WINS" in text
    # Mixed provenance: a --quick chunked row must not decide the flip.
    rows[1] = dict(rows[1], events=200_000)
    _write_jsonl(r2, rows)
    text = summarize.render()
    assert "INCOMPARABLE" in text
    assert "WINS" not in text
    # Full-size rows outrank a faster quick row for the target line.
    assert "480,000 pairs/s** (config4-headline" in text


def test_guard_preserves_pass_name(tmp_path, monkeypatch):
    out = tmp_path / "out.jsonl"
    monkeypatch.setattr(tpu_round2, "OUT", str(out))

    @tpu_round2.guard("my-pass")
    def fake(quick):
        return {"name": "inner-bench-result", "value": 7}

    fake(False)
    row = json.loads(out.read_text().strip())
    assert row["name"] == "my-pass"
    assert row["config"] == "inner-bench-result"
    assert row["value"] == 7 and row["ok"] is True
