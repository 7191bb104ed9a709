"""DeferredResultsTable unit tests (shared by dense + sparse scorers)."""

import numpy as np
import jax.numpy as jnp

from tpu_cooccurrence.ops.device_scorer import DeferredResultsTable
from tpu_cooccurrence.state.results import pack_ids


def _packed(rows_vals, k):
    """Build a [2, S, K] packed block: vals descending, ids packed."""
    s = len(rows_vals)
    vals = np.full((s, k), -np.inf, np.float32)
    ids = np.zeros((s, k), np.int32)
    for i, (val, idx) in enumerate(rows_vals):
        vals[i, : len(val)] = val
        ids[i, : len(idx)] = idx
    return jnp.stack([jnp.asarray(vals), pack_ids(jnp.asarray(ids))])


def test_drain_empty_and_incremental():
    t = DeferredResultsTable(top_k=3, items_cap=8)
    assert len(t.drain()) == 0          # nothing scattered yet
    t.ensure()
    t.scatter(_packed([([5.0, 2.0], [7, 1])], 3),
              np.asarray([4], np.int32))
    t.mark(np.asarray([4]))
    b = t.drain()
    assert list(b.rows) == [4]
    np.testing.assert_allclose(b.vals[0, :2], [5.0, 2.0])
    assert list(b.idx[0, :2]) == [7, 1]
    assert len(t.drain()) == 0          # drained rows are clean

    # A re-scatter of the same row after drain is dirty again.
    t.scatter(_packed([([9.0], [2])], 3), np.asarray([4], np.int32))
    t.mark(np.asarray([4]))
    b2 = t.drain()
    assert list(b2.rows) == [4]
    np.testing.assert_allclose(b2.vals[0, 0], 9.0)


def test_sentinel_rows_do_not_scatter():
    t = DeferredResultsTable(top_k=2, items_cap=4)
    t.ensure()
    sent = np.asarray([0, np.iinfo(np.int32).max], np.int32)
    t.scatter(_packed([([1.0], [3]), ([8.0], [2])], 2), sent)
    t.mark(np.asarray([0]))
    b = t.drain()
    assert list(b.rows) == [0]
    np.testing.assert_allclose(b.vals[0, 0], 1.0)  # row 0 kept its block;
    # the padded entry (sentinel) was dropped, not written anywhere


def test_resize_preserves_entries_and_marks():
    t = DeferredResultsTable(top_k=2, items_cap=4)
    t.ensure()
    t.scatter(_packed([([3.0, 1.0], [1, 2])], 2), np.asarray([2], np.int32))
    t.mark(np.asarray([2]))
    t.resize(16)
    assert t.tbl.shape == (2, 16, 2)
    t.scatter(_packed([([4.0], [9])], 2), np.asarray([11], np.int32))
    t.mark(np.asarray([11]))
    b = t.drain()
    assert list(b.rows) == [2, 11]
    np.testing.assert_allclose(b.vals[0, :2], [3.0, 1.0])
    np.testing.assert_allclose(b.vals[1, 0], 4.0)


def test_float_ids_decode():
    t = DeferredResultsTable(top_k=2, items_cap=4)
    t.ensure()
    vals = jnp.asarray(np.array([[7.0, 6.0]], np.float32))
    ids_as_floats = jnp.asarray(np.array([[3.0, 1.0]], np.float32))
    t.scatter(jnp.stack([vals, ids_as_floats]), np.asarray([1], np.int32))
    t.mark(np.asarray([1]))
    b = t.drain(float_ids=True)
    assert list(b.idx[0]) == [3, 1]


def test_reset_clears_everything():
    t = DeferredResultsTable(top_k=2, items_cap=4)
    t.ensure()
    t.scatter(_packed([([1.0], [0])], 2), np.asarray([3], np.int32))
    t.mark(np.asarray([3]))
    t.reset(8)
    assert t.tbl is None
    assert len(t.drain()) == 0


def test_drain_survives_transient_fetch_failure(monkeypatch):
    """A fetch failure must leave the dirty marks set so a retrying
    caller still drains the rows (failure-atomic drain)."""
    import tpu_cooccurrence.ops.device_scorer as ds

    t = DeferredResultsTable(top_k=2, items_cap=8)
    t.ensure()
    t.scatter(_packed([([4.0, 1.0], [2, 5])], 2), np.asarray([3], np.int32))
    t.mark(np.asarray([3]))

    real = ds._gather_packed
    calls = {"n": 0}

    def flaky(tbl, rows):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient link failure")
        return real(tbl, rows)

    monkeypatch.setattr(ds, "_gather_packed", flaky)
    try:
        t.drain()
    except RuntimeError:
        pass
    else:
        raise AssertionError("expected the injected failure to propagate")
    b = t.drain()  # retry: rows are still dirty
    assert list(b.rows) == [3]
    np.testing.assert_allclose(b.vals[0, :2], [4.0, 1.0])
    assert len(t.drain()) == 0
