"""CPU smoke tests for the on-chip measurement machinery.

The tpu_round2 passes only ever execute on the chip; an import error,
renamed helper, or signature drift inside one would otherwise surface
for the first time there and burn chip time. These tests run the cheap
machinery on CPU without the heavy measurement bodies.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
