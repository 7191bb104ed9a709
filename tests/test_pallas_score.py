"""Pallas fused score/top-K kernel vs the XLA reference path.

Runs in interpreter mode on CPU (the standard way to validate Pallas TPU
kernels without hardware)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_cooccurrence.ops.device_scorer import _score
from tpu_cooccurrence.ops.pallas_score import (pallas_score_topk,
                                               pallas_score_topk_local,
                                               topk_parity)


@pytest.mark.parametrize("seed,num_items,s,top_k", [
    (0, 256, 8, 10),
    (1, 512, 16, 5),
    (2, 256, 32, 16),
])
def test_pallas_matches_xla_score(seed, num_items, s, top_k):
    rng = np.random.default_rng(seed)
    C = np.zeros((num_items, num_items), dtype=np.int32)
    nnz = 4000
    src = rng.integers(0, num_items, nnz)
    dst = rng.integers(0, num_items, nnz)
    np.add.at(C, (src, dst), 1)
    row_sums = C.sum(axis=1).astype(np.int32)
    observed = np.float32(row_sums.sum())
    rows = rng.integers(0, num_items, s).astype(np.int32)

    ref_vals, ref_idx = _score(jnp.asarray(C), jnp.asarray(row_sums),
                               jnp.asarray(rows), observed, top_k=top_k)
    got_vals, got_idx = pallas_score_topk(
        jnp.asarray(C), jnp.asarray(row_sums), jnp.asarray(rows), observed,
        top_k=top_k, tile=128, interpret=True)

    ref_vals = np.asarray(ref_vals)
    got_vals = np.asarray(got_vals)
    np.testing.assert_allclose(got_vals, ref_vals, rtol=1e-5, atol=1e-5)
    # Indices must agree wherever scores are not tied with a neighbor.
    ref_idx = np.asarray(ref_idx)
    got_idx = np.asarray(got_idx)
    for r in range(s):
        for k in range(top_k):
            if not np.isfinite(ref_vals[r, k]):
                continue
            ties = np.isclose(ref_vals[r], ref_vals[r, k]).sum()
            if ties == 1:
                assert got_idx[r, k] == ref_idx[r, k], (r, k)


def test_pallas_empty_rows():
    num_items = 128
    C = jnp.zeros((num_items, num_items), dtype=jnp.int32)
    row_sums = jnp.zeros((num_items,), dtype=jnp.int32)
    rows = jnp.zeros((4,), dtype=jnp.int32)
    vals, idx = pallas_score_topk(C, row_sums, rows, np.float32(0.0),
                                  top_k=10, tile=128, interpret=True)
    assert not np.isfinite(np.asarray(vals)).any()


def test_pallas_rejects_bad_tile():
    C = jnp.zeros((130, 130), dtype=jnp.int32)
    with pytest.raises(ValueError):
        pallas_score_topk(C, jnp.zeros((130,), jnp.int32),
                          jnp.zeros((2,), jnp.int32), np.float32(0),
                          top_k=5, tile=128, interpret=True)


def test_pallas_packed_value_space_decode():
    """packed=True ships idx as float *values* (not a bitcast view).

    The host decode is ``astype(int32)``; a bitcast of the kernel's second
    output miscompiles on real-TPU Mosaic at >=4 row blocks, which is why
    the contract is value-space (see pallas_score.py).
    """
    rng = np.random.default_rng(7)
    num_items, s, top_k = 256, 32, 8
    C = np.zeros((num_items, num_items), dtype=np.int32)
    src = rng.integers(0, num_items, 3000)
    dst = rng.integers(0, num_items, 3000)
    np.add.at(C, (src, dst), 1)
    row_sums = C.sum(axis=1).astype(np.int32)
    observed = np.float32(row_sums.sum())
    rows = rng.integers(0, num_items, s).astype(np.int32)

    vals, idx = pallas_score_topk(
        jnp.asarray(C), jnp.asarray(row_sums), jnp.asarray(rows), observed,
        top_k=top_k, tile=128, interpret=True)
    packed = np.asarray(pallas_score_topk(
        jnp.asarray(C), jnp.asarray(row_sums), jnp.asarray(rows), observed,
        top_k=top_k, tile=128, interpret=True, packed=True))
    np.testing.assert_allclose(packed[0], np.asarray(vals), rtol=1e-6)
    np.testing.assert_array_equal(packed[1].astype(np.int32), np.asarray(idx))


def test_pallas_rejects_vocab_beyond_float32_exact():
    import functools

    import jax

    big = (1 << 24) + 128
    with pytest.raises(ValueError, match="2\\^24"):
        # eval_shape: the guard must fire at trace time, no allocation.
        jax.eval_shape(
            functools.partial(pallas_score_topk, top_k=5, tile=128,
                              interpret=True),
            jax.ShapeDtypeStruct((big, big), jnp.int32),
            jax.ShapeDtypeStruct((big,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))


@pytest.mark.parametrize("seed,num_items,s,top_k", [
    (3, 256, 8, 10),
    (4, 512, 24, 5),
])
def test_pallas_int16_counts_match_xla(seed, num_items, s, top_k):
    """int16 (reference-style short) counts run with 16-row blocks."""
    rng = np.random.default_rng(seed)
    C = np.zeros((num_items, num_items), dtype=np.int16)
    nnz = 4000
    src = rng.integers(0, num_items, nnz)
    dst = rng.integers(0, num_items, nnz)
    np.add.at(C, (src, dst), 1)
    row_sums = C.sum(axis=1, dtype=np.int64).astype(np.int32)
    observed = np.float32(row_sums.sum())
    rows = rng.integers(0, num_items, s).astype(np.int32)

    ref_vals, ref_idx = _score(jnp.asarray(C), jnp.asarray(row_sums),
                               jnp.asarray(rows), observed, top_k=top_k)
    got_vals, got_idx = pallas_score_topk(
        jnp.asarray(C), jnp.asarray(row_sums), jnp.asarray(rows), observed,
        top_k=top_k, tile=128, interpret=True)
    ref_vals = np.asarray(ref_vals)
    got_vals = np.asarray(got_vals)
    np.testing.assert_allclose(got_vals, ref_vals, rtol=1e-5, atol=1e-5)
    # Tie-aware index check (same protocol as the int32 test above): a
    # col_base/run_idx bug under 16-row blocks must not hide behind
    # correct scores.
    ref_idx = np.asarray(ref_idx)
    got_idx = np.asarray(got_idx)
    for r in range(s):
        for k in range(top_k):
            if not np.isfinite(ref_vals[r, k]):
                continue
            if np.isclose(ref_vals[r], ref_vals[r, k]).sum() == 1:
                assert got_idx[r, k] == ref_idx[r, k], (r, k)


def test_pallas_int16_device_scorer_end_to_end():
    """DeviceScorer accepts --pallas on with --count-dtype int16 and
    matches the XLA path's results."""
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    rng = np.random.default_rng(9)
    n = 3000
    src = rng.integers(0, 512, n).astype(np.int64)
    dst = rng.integers(0, 512, n).astype(np.int64)
    keep = src != dst
    pairs = PairDeltaBatch(src[keep], dst[keep],
                           np.ones(int(keep.sum()), dtype=np.int32))
    out = {}
    for pallas in ("on", "off"):
        sc = DeviceScorer(512, top_k=10, use_pallas=pallas,
                          count_dtype="int16")
        sc.process_window(0, pairs)
        out[pallas] = sc.flush()
    np.testing.assert_array_equal(out["on"].rows, out["off"].rows)
    np.testing.assert_allclose(out["on"].vals, out["off"].vals,
                               rtol=1e-5, atol=1e-5)
    # Indices agree wherever a row's scores have no ties at the cutoff.
    for r in range(len(out["on"].rows)):
        v = out["off"].vals[r]
        for k in range(v.shape[0]):
            if np.isfinite(v[k]) and np.isclose(v, v[k]).sum() == 1:
                assert out["on"].idx[r, k] == out["off"].idx[r, k]


def test_pallas_auto_rule():
    """--pallas auto: kernel on exactly for int16 counts on a real TPU
    (measured 247x there, ~5x slower at int32 — TPU_ROUND2.jsonl)."""
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer, pallas_auto

    assert pallas_auto(np.dtype(np.int16), "tpu") is True
    assert pallas_auto(np.dtype(np.int32), "tpu") is False
    assert pallas_auto(np.dtype(np.int16), "cpu") is False
    assert pallas_auto(np.dtype(np.int32), "cpu") is False
    # top_k beyond the kernel's 128-lane output width: XLA path, not a
    # crash one window in (pallas_score_topk would reject it).
    assert pallas_auto(np.dtype(np.int16), "tpu", top_k=128) is True
    assert pallas_auto(np.dtype(np.int16), "tpu", top_k=200) is False
    # The constructor must resolve "auto" through the same rule (on the
    # CPU test backend both dtypes give False; on a TPU host int16 gives
    # True — compare against the rule, not a hard-coded value).
    import jax

    for dt in ("int16", "int32"):
        assert (DeviceScorer(64, 5, use_pallas="auto",
                             count_dtype=dt).use_pallas
                is pallas_auto(np.dtype(dt), jax.default_backend(), 5))


# -- the kernel's own row fetch from C --------------------------------------

def _counts(rng, num_items, dtype, nnz=6000):
    C = np.zeros((num_items, num_items), dtype=dtype)
    np.add.at(C, (rng.integers(0, num_items, nnz),
                  rng.integers(0, num_items, nnz)), 1)
    return C, C.sum(axis=1, dtype=np.int64).astype(np.int32)


#: Row sets that exercise the fetch: each row's 8-row group is DMA'd,
#: rows sharing a group share one DMA, a short set pads with row 0.
FETCH_ROWS = {
    # unsorted, repeated, 19 rows (not a block multiple: pads with row 0)
    "unsorted-repeats": [255, 3, 3, 2, 9, 0, 7, 6, 254, 100, 101, 102, 5,
                         5, 250, 1, 8, 17, 33],
    # a whole group, odd and even rows, one group split across blocks
    "shared-groups": [8, 9, 10, 11, 12, 13, 14, 15, 41, 42, 43, 44, 45,
                      46, 47, 48, 49, 50, 51, 52, 53, 54, 55, 56],
    # the catalog's last rows, one at a time across groups
    "last-rows": [511, 504, 496, 503, 510, 509, 1, 488, 480, 479, 505],
}


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
@pytest.mark.parametrize("case", sorted(FETCH_ROWS))
def test_dense_kernel_fetch_matches_xla(dtype, case):
    """Rows fetched by the kernel from C in HBM score like the XLA
    scorer's, over four column tiles, and a row's result does not depend
    on which rows share its block or group (bitwise under a shuffle)."""
    num_items = 512
    C, row_sums = _counts(np.random.default_rng(11), num_items, dtype)
    observed = np.float32(row_sums.sum())
    rows = np.asarray(FETCH_ROWS[case], dtype=np.int32)
    top_k = 8
    args = (jnp.asarray(C), jnp.asarray(row_sums))
    ref_vals, ref_idx = _score(*args, jnp.asarray(rows), observed,
                               top_k=top_k)
    got_vals, got_idx = pallas_score_topk(*args, jnp.asarray(rows), observed,
                                          top_k=top_k, tile=128,
                                          interpret=True)
    ok, mism = topk_parity(got_vals, got_idx, ref_vals, ref_idx)
    assert ok and mism == 0

    perm = np.random.default_rng(12).permutation(len(rows))
    p_vals, p_idx = pallas_score_topk(*args, jnp.asarray(rows[perm]),
                                      observed, top_k=top_k, tile=128,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(p_vals),
                                  np.asarray(got_vals)[perm])
    np.testing.assert_array_equal(np.asarray(p_idx),
                                  np.asarray(got_idx)[perm])


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_dense_kernel_local_block_offset(dtype):
    """The sharded form reads a shard's row block of C (global rows
    [lo, lo + 128)) and scores exactly what the whole-C form scores."""
    num_items, lo, per_shard = 512, 256, 128
    C, row_sums = _counts(np.random.default_rng(13), num_items, dtype)
    observed = np.float32(row_sums.sum())
    rows = np.asarray([383, 256, 300, 301, 302, 256, 311, 376, 377, 290,
                       264, 265, 333], dtype=np.int32)
    packed = pallas_score_topk_local(
        jnp.asarray(C[lo:lo + per_shard]), jnp.asarray(row_sums),
        jnp.asarray(rows), lo, observed, top_k=6, tile=128, interpret=True)
    whole = pallas_score_topk(jnp.asarray(C), jnp.asarray(row_sums),
                              jnp.asarray(rows), observed, top_k=6,
                              tile=128, interpret=True, packed=True)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(whole))


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_dense_kernel_skips_blocks_past_live(dtype):
    """Rows past ``live`` are padding: their whole blocks fetch and score
    nothing and return (-inf, 0), and every row of a live block scores
    bitwise as without the bound."""
    import jax

    from tpu_cooccurrence.ops.pallas_score import dense_topk

    num_items, live = 512, 100
    C, row_sums = _counts(np.random.default_rng(14), num_items, dtype)
    rows = np.zeros(256, dtype=np.int32)
    rows[:live] = np.random.default_rng(15).integers(0, num_items, live)
    args = (jnp.asarray(C), jnp.asarray(rows), jnp.asarray(row_sums),
            np.float32(row_sums.sum()))

    def run(bound):
        return [np.asarray(a) for a in jax.jit(
            lambda c, r, rs, o, n: dense_topk(c, r, rs, o, top_k=8,
                                              tile=128, interpret=True,
                                              live=n))(*args, bound)]

    got_vals, got_idx = run(np.int32(live))
    all_vals, all_idx = run(np.int32(len(rows)))
    scored = 128  # the two blocks that hold live rows
    np.testing.assert_array_equal(got_vals[:scored], all_vals[:scored])
    np.testing.assert_array_equal(got_idx[:scored], all_idx[:scored])
    assert np.isneginf(got_vals[scored:]).all()
    assert not got_idx[scored:].any()
    assert np.isfinite(all_vals[scored:, 0]).any()


@pytest.mark.parametrize("dtype", ["int16", "int32"])
def test_dense_scorer_counts_fetch_cells(dtype):
    """``fetch_cells`` is the groups the kernel DMAs x 8 x the width: in
    each row block, a row starts a DMA unless the row before it is in
    the same 8-row group (padding rows are row 0)."""
    from tpu_cooccurrence.ops.device_scorer import DeviceScorer, pad_pow4
    from tpu_cooccurrence.ops.pallas_score import BLOCK_ROWS, _fetch_plan
    from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch

    src = np.asarray([9, 10, 11, 12, 40, 41, 200, 201, 207, 208, 300, 301,
                      302, 303, 304, 305, 306, 307, 308, 500, 505, 90],
                     dtype=np.int64)
    dst = (src * 7 + 3) % 512
    sc = DeviceScorer(512, top_k=5, use_pallas="on", count_dtype=dtype)
    sc.process_window(0, PairDeltaBatch(src, dst,
                                        np.ones(len(src), np.int32)))
    rows = np.unique(src)
    padded = np.zeros(pad_pow4(len(rows), minimum=64), dtype=np.int64)
    padded[:len(rows)] = rows
    groups = sum(1 for k in range(len(padded))
                 if k % BLOCK_ROWS == 0 or padded[k] // 8 != padded[k - 1] // 8)
    counts = sc.stage_clock.counts
    assert counts["fetch_cells"] == groups * 8 * sc.num_items
    # ...and that is the plan the kernel fetches by.
    _slots, _firsts, n_groups = _fetch_plan(jnp.asarray(padded, jnp.int32))
    assert int(n_groups.sum()) == groups
    assert counts["live_cells"] == len(rows) * sc.num_items
    sc.flush()
