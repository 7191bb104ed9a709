"""Retired environment levers change nothing.

Every scorer setting has one source, the ``Config``, and each window has
one upload path. Six ``TPU_COOC_*`` variables once switched the program
behind the config's back (upload chunking, the score ladder, fixed-shape
scoring, the row-registry layout, buffer donation); nothing reads them
now. Each case exports one of them and runs a small CPU job on the
backend it used to touch: the results, counters, per-window stage
counts, uplink labels, registry layout and donation rule must all read
as in a clean environment.
"""

import pytest

from tpu_cooccurrence.config import Backend, Config
from tpu_cooccurrence.job import CooccurrenceJob
from tpu_cooccurrence.observability import LEDGER
from tpu_cooccurrence.ops.donation import donate_argnums

from test_stage_spans import _stream

RETIRED = ("TPU_COOC_UPLOAD_CHUNKS", "TPU_COOC_UPLOAD_CHUNK_KB",
           "TPU_COOC_SCORE_LADDER", "TPU_COOC_FIXED_SCORE",
           "TPU_COOC_ROW_INDEX", "TPU_COOC_DONATE")

_clean = {}


def _snapshot(backend):
    kw = dict(window_size=100, seed=5, item_cut=30, user_cut=12,
              backend=backend)
    if backend is Backend.DEVICE:
        kw["num_items"] = 128
    LEDGER.reset()
    job = CooccurrenceJob(Config(**kw))
    users, items, ts = _stream()
    for lo in range(0, len(users), 500):
        job.add_batch(users[lo:lo + 500], items[lo:lo + 500],
                      ts[lo:lo + 500])
    job.finish()
    index = getattr(job.scorer, "index", None)
    return dict(
        latest=dict(job.latest),
        counters=job.counters.as_dict(),
        counts=[dict(w.counts) for w in job.step_timer.windows],
        h2d=LEDGER.labels("h2d"),
        registry=index.rows.kind if index is not None else None,
        donate=donate_argnums(0))


@pytest.mark.parametrize("var,value,backend", [
    ("TPU_COOC_UPLOAD_CHUNKS", "4", Backend.DEVICE),
    ("TPU_COOC_UPLOAD_CHUNK_KB", "16", Backend.SPARSE),
    ("TPU_COOC_SCORE_LADDER", "16", Backend.SPARSE),
    ("TPU_COOC_FIXED_SCORE", "1", Backend.SPARSE),
    ("TPU_COOC_ROW_INDEX", "dense", Backend.SPARSE),
    ("TPU_COOC_DONATE", "1", Backend.SPARSE),
], ids=["UPLOAD_CHUNKS=4", "UPLOAD_CHUNK_KB=16", "SCORE_LADDER=16",
        "FIXED_SCORE=1", "ROW_INDEX=dense", "DONATE=1"])
def test_retired_env_lever_changes_nothing(monkeypatch, var, value, backend):
    for name in RETIRED:
        monkeypatch.delenv(name, raising=False)
    if backend not in _clean:
        _clean[backend] = _snapshot(backend)
    want = _clean[backend]
    assert want["counts"] and any(c.get("launches") for c in want["counts"])
    monkeypatch.setenv(var, value)
    got = _snapshot(backend)
    for key in want:
        assert got[key] == want[key], key
