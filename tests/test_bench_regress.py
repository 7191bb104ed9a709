"""bench.regress: the bench-history regression gate (median +/- MAD
noise bands per metric per backend; exit 1 on regression, 0 on a clean
or too-thin history)."""

import json
import os

from tpu_cooccurrence.bench import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry(pairs=1000.0, backend="numpy", **over):
    e = {"backend": backend, "pairs_per_sec": pairs,
         "serving": {"qps": 500.0, "query_p99_s": 0.004},
         "ts": "2026-08-01T00:00:00"}
    e.update(over)
    return e


def _history(n=5, pairs=1000.0, backend="numpy",
             jitter=(0.98, 1.0, 1.02, 0.99, 1.01)):
    return [_entry(pairs=pairs * jitter[i % len(jitter)],
                   backend=backend)
            for i in range(n)]


def test_flatten_skips_verdict_and_bools():
    flat = regress.flatten(_entry(
        ok=True, regression={"ok": False, "regressions": [{"x": 1}]},
        note="text", nested={"deep": {"v": 2.0}, "flag": False}))
    assert flat["pairs_per_sec"] == 1000.0
    assert flat["serving.qps"] == 500.0
    assert flat["nested.deep.v"] == 2.0
    assert not any(k.startswith("regression") for k in flat)
    assert "ok" not in flat and "nested.flag" not in flat
    assert "ts" not in flat and "note" not in flat


def test_regression_flagged_on_2x_throughput_drop():
    verdict = regress.evaluate(_history(), _entry(pairs=500.0))
    assert not verdict["ok"]
    metrics = {r["metric"] for r in verdict["regressions"]}
    assert "pairs_per_sec" in metrics
    reg = next(r for r in verdict["regressions"]
               if r["metric"] == "pairs_per_sec")
    assert reg["direction"] == "higher" and reg["n_history"] == 5


def test_within_band_and_improvement_pass():
    assert regress.evaluate(_history(), _entry(pairs=990.0))["ok"]
    # A 2x IMPROVEMENT is news, not a regression.
    assert regress.evaluate(_history(), _entry(pairs=2000.0))["ok"]


def test_lower_is_better_metrics_flag_rises():
    hist = _history()
    worse = _entry(serving={"qps": 500.0, "query_p99_s": 0.05})
    verdict = regress.evaluate(hist, worse)
    assert not verdict["ok"]
    assert {r["metric"] for r in verdict["regressions"]} == \
        {"serving.query_p99_s"}
    better = _entry(serving={"qps": 500.0, "query_p99_s": 0.0001})
    assert regress.evaluate(hist, better)["ok"]


def test_backends_never_cross_band():
    """CPU history must not band a TPU candidate (and vice
    versa) — a backend switch is not a regression."""
    hist = _history(backend="numpy")
    verdict = regress.evaluate(hist, _entry(pairs=10.0, backend="jax"))
    assert verdict["ok"] and verdict["checked"] == 0
    assert "pairs_per_sec" in verdict["insufficient_history"]


def test_thin_history_passes_gate():
    verdict = regress.evaluate(_history(n=2), _entry(pairs=1.0))
    assert verdict["ok"] and verdict["checked"] == 0
    assert "pairs_per_sec" in verdict["insufficient_history"]


def test_quiet_history_uses_relative_floor():
    """MAD ~ 0 (identical runs) must not flag ordinary jitter — the
    relative floor keeps the band at rel_floor * median."""
    hist = [_entry(pairs=1000.0) for _ in range(5)]
    assert regress.evaluate(hist, _entry(pairs=950.0))["ok"]
    assert not regress.evaluate(hist, _entry(pairs=850.0))["ok"]


def test_cli_exit_codes(tmp_path, capsys):
    hpath = tmp_path / "hist.jsonl"
    with open(hpath, "w") as f:
        for e in _history():
            f.write(json.dumps(e) + "\n")
        f.write("{torn line\n")  # tolerated, skipped
    # Newest-entry mode: append a 2x regression as the candidate.
    with open(hpath, "a") as f:
        f.write(json.dumps(_entry(pairs=480.0)) + "\n")
    assert regress.main(["--history", str(hpath)]) == 1
    assert "REGRESSION pairs_per_sec" in capsys.readouterr().out
    # Explicit candidate file (bench.py stdout shape: "value" headline).
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps({"backend": "numpy", "value": 995.0}))
    assert regress.main(["--history", str(hpath), "--candidate",
                         str(cand), "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] and out["checked"] >= 1
    # Empty/missing history: nothing to band, gate stays open.
    assert regress.main(["--history", str(tmp_path / "nope.jsonl")]) == 0


def test_gate_passes_on_repo_history():
    """The repo's bench_history.jsonl (absent until a chip bench run
    appends one) must pass its own gate — the verify skill runs exactly
    this command after the bench step."""
    path = os.path.join(REPO, "bench_history.jsonl")
    assert regress.main(["--history", path]) == 0
