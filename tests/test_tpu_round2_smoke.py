"""CPU smoke tests for the on-chip measurement machinery.

The tpu_round2 passes only ever execute on the chip; an import error,
renamed helper, or signature drift inside one would otherwise surface
for the first time there and burn chip time. These tests run the cheap
machinery on CPU — JSONL rows, env pinning — without the heavy
measurement bodies.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_smoke_events_ignored_off_cpu(monkeypatch):
    """A stale TPU_COOC_SMOKE_EVENTS export must not shrink a grant
    capture: the knob only applies on the cpu backend."""
    import jax

    from tpu_cooccurrence.bench import tpu_round2

    monkeypatch.setenv("TPU_COOC_SMOKE_EVENTS", "2000")
    assert tpu_round2._config4_events(quick=False) == 2_000  # cpu: honored
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tpu_round2._config4_events(quick=False) == 1_000_000
    assert tpu_round2._config4_events(quick=True) == 200_000


def test_config4_passes_pin_their_env(tmp_path, monkeypatch):
    """config4-headline/-chunked must pin every A/B knob (ladder, fixed
    shapes, BOTH chunk knobs) against ambient operator settings, and
    restore them afterwards — contaminated arms decide hardware
    defaults on garbage."""
    from tpu_cooccurrence.bench import tpu_round2
    from tpu_cooccurrence.bench import configs

    monkeypatch.setattr(tpu_round2, "OUT", str(tmp_path / "o.jsonl"))
    monkeypatch.setenv("TPU_COOC_UPLOAD_CHUNKS", "4")       # ambient
    monkeypatch.setenv("TPU_COOC_UPLOAD_CHUNK_KB", "256")   # ambient
    monkeypatch.setenv("TPU_COOC_SCORE_LADDER", "64")       # ambient
    seen = []

    class FakeResult:
        pairs_per_sec = 123_456.0

        def as_dict(self):
            return {"name": "zipfian-1M-items", "pairs_per_sec": 123456.0,
                    "events": 1, "backend": "sparse"}

    def fake_config4(n_events):
        seen.append({k: os.environ.get(k) for k in
                     ("TPU_COOC_SCORE_LADDER", "TPU_COOC_FIXED_SCORE",
                      "TPU_COOC_UPLOAD_CHUNKS",
                      "TPU_COOC_UPLOAD_CHUNK_KB")})
        return FakeResult()

    monkeypatch.setattr(configs, "config4_zipfian_1m", fake_config4)
    assert tpu_round2.config4_headline(True) is True   # guard returns ok
    assert tpu_round2.config4_chunked(True) is True
    # Two runs (warmup + measure) per pass.
    assert len(seen) == 4
    for env in seen[:2]:   # headline: the monolithic arm
        assert env["TPU_COOC_UPLOAD_CHUNKS"] == "1"
        assert env["TPU_COOC_UPLOAD_CHUNK_KB"] == "0"
        assert env["TPU_COOC_SCORE_LADDER"] == "16"
        assert env["TPU_COOC_FIXED_SCORE"] == "1"
    for env in seen[2:]:   # chunked arm
        assert env["TPU_COOC_UPLOAD_CHUNKS"] == "4"
        assert env["TPU_COOC_SCORE_LADDER"] == "16"
    # Operator settings restored.
    assert os.environ["TPU_COOC_UPLOAD_CHUNKS"] == "4"
    assert os.environ["TPU_COOC_UPLOAD_CHUNK_KB"] == "256"
    assert os.environ["TPU_COOC_SCORE_LADDER"] == "64"
    rows = _read_jsonl(tmp_path / "o.jsonl")
    assert [r["name"] for r in rows] == ["config4-headline",
                                        "config4-chunked"]
    assert all(r["ok"] for r in rows)
    # The measurement name owns the row; the inner BenchResult's name
    # lands under "config".
    assert rows[0]["config"] == "zipfian-1M-items"
    # The JOB backend field (summarize.py keys on it) must survive the
    # platform tag — distinct keys, neither shadowing the other.
    import jax

    jax.devices()  # platform tag reads the cached backend
    assert rows[0]["backend"] == "sparse"
    assert tpu_round2._backend_tag() == {"jax_platform": "cpu"}


def test_bench_child_stderr_noise_filtered(tmp_path, monkeypatch, capsys):
    """The known-benign XLA machine-feature warning (+prefer-no-gather —
    it flooded the captured bench tails of earlier rounds) is withheld
    from the live stderr stream and surfaces as a count+sample debug
    field on the measurement JSON line; real warnings still stream."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    assert bench._is_benign_stderr(
        "AOT result. Target machine feature +prefer-no-gather is not "
        "supported on the host machine.")
    assert not bench._is_benign_stderr("XlaRuntimeError: RESOURCE_EXHAUSTED")

    fake = tmp_path / "fake_child.sh"
    fake.write_text(
        "#!/bin/sh\n"
        'echo \'{"value": 1.0, "unit": "pairs/s"}\'\n'
        'echo "Target machine feature +prefer-no-gather is not supported'
        ' on the host machine." >&2\n'
        'echo "a real warning that must stream through" >&2\n')
    fake.chmod(0o755)
    monkeypatch.setattr(sys, "executable", str(fake))
    line = bench._run_child(dict(os.environ), 60.0)
    assert line is not None
    rec = json.loads(line)
    assert rec["value"] == 1.0
    assert rec["stderr_noise"]["suppressed_lines"] == 1
    assert "+prefer-no-gather" in rec["stderr_noise"]["sample"]
    err = capsys.readouterr().err
    assert "a real warning that must stream through" in err
    assert "+prefer-no-gather" not in err
