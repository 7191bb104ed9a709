"""Fused Pallas sparse-rectangle scorer vs the XLA `_score_rect` path.

Interpret mode on CPU (the standard way to validate Pallas TPU kernels
without hardware). The kernel must be a drop-in for
``state/sparse_scorer._score_rect``: same packed [2, S, K] wire format
(ids as int32 bitcast), same tie semantics (earliest slab slot wins),
same zero-cell masking. (VERDICT r3, Next #2 — reference hot loop 4:
ItemRowRescorerTwoInputStreamOperator.java:158-228.)
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_cooccurrence.ops.pallas_score import (pallas_score_rect,
                                               rect_supported, rect_tile)
from tpu_cooccurrence.state.results import unpack_ids
from tpu_cooccurrence.sampling.reservoir import PairDeltaBatch
from tpu_cooccurrence.state.sparse_scorer import (SparseDeviceScorer,
                                                  _score_rect)

# Interpret-mode Pallas across meshes: minutes of wall-clock. Slow lane
# (deselected by default; TPU_COOC_FULL_SUITE=1 selects it back in).
pytestmark = pytest.mark.slow


def _random_slab(rng, n_rows, num_items, R, zero_frac=0.1,
                 count_hi=50):
    """Synthetic slab: ``n_rows`` rows with random lens in [0, R],
    contiguous starts, random partner ids / counts (some zero =
    cancelled cells), plus 3 all-padding meta rows (len 0)."""
    lens = rng.integers(0, R + 1, n_rows).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    cap = int(lens.sum()) + 8
    cnt = rng.integers(1, count_hi, cap).astype(np.int32)
    cnt[rng.random(cap) < zero_frac] = 0
    dst = rng.integers(0, num_items, cap).astype(np.int32)
    rowids = rng.choice(num_items, n_rows, replace=False).astype(np.int32)
    meta = np.zeros((3, n_rows + 3), dtype=np.int32)  # 3 padding rows
    meta[0, :n_rows] = rowids
    meta[1, :n_rows] = starts
    meta[2, :n_rows] = lens
    row_sums = rng.integers(1, 1 << 16, num_items).astype(np.int32)
    observed = np.float32(1e7)
    return cnt, dst, row_sums, meta, observed


def _unpack(packed, s):
    host = np.asarray(packed)
    return host[0, :s], unpack_ids(host[1, :s])


@pytest.mark.parametrize("seed,R,n_rows", [
    (0, 256, 13),    # single column tile, non-multiple-of-8 rows
    (1, 512, 24),    # tile == R
    (2, 4096, 9),    # two column tiles: running merge across tiles
])
def test_rect_kernel_matches_score_rect(seed, R, n_rows):
    rng = np.random.default_rng(seed)
    num_items = 2048
    top_k = 10
    cnt, dst, row_sums, meta, observed = _random_slab(
        rng, n_rows, num_items, R)

    ref = _score_rect(jnp.asarray(cnt), jnp.asarray(dst),
                      jnp.asarray(row_sums), jnp.asarray(meta), observed,
                      top_k, R)
    got = pallas_score_rect(jnp.asarray(cnt), jnp.asarray(dst),
                            jnp.asarray(row_sums), jnp.asarray(meta),
                            observed, top_k=top_k, R=R, interpret=True)
    s = meta.shape[1]
    ref_vals, ref_idx = _unpack(ref, s)
    got_vals, got_idx = _unpack(got, s)
    np.testing.assert_allclose(got_vals, ref_vals, rtol=1e-5, atol=1e-5)
    # Ids must agree exactly wherever the score is not tied (ties keep
    # set equality — checked via the score match above plus the
    # untied-position identity here).
    for r in range(s):
        for k in range(top_k):
            if not np.isfinite(ref_vals[r, k]):
                continue
            if np.isclose(ref_vals[r], ref_vals[r, k]).sum() == 1:
                assert got_idx[r, k] == ref_idx[r, k], (r, k)


def test_rect_kernel_tie_prefers_earliest_slot():
    """Equal scores: the earliest-inserted slab cell (lowest slot) wins,
    matching lax.top_k in _score_rect and the reference heap's
    keep-earlier rule (IntDoublePriorityQueue.java:146-150)."""
    num_items = 512
    R = 256
    top_k = 4
    # One row, 6 live cells; partners chosen with IDENTICAL row sums and
    # counts so all six scores tie exactly.
    lens = np.asarray([6], dtype=np.int32)
    meta = np.zeros((3, 8), dtype=np.int32)
    meta[0, 0] = 7
    meta[1, 0] = 0
    meta[2, 0] = lens[0]
    cnt = np.zeros(R, dtype=np.int32)
    cnt[:6] = 5
    dst = np.zeros(R, dtype=np.int32)
    partners = np.asarray([40, 30, 20, 10, 50, 60], dtype=np.int32)
    dst[:6] = partners
    row_sums = np.full(num_items, 1000, dtype=np.int32)
    observed = np.float32(1e6)

    ref = _score_rect(jnp.asarray(cnt), jnp.asarray(dst),
                      jnp.asarray(row_sums), jnp.asarray(meta), observed,
                      top_k, R)
    got = pallas_score_rect(jnp.asarray(cnt), jnp.asarray(dst),
                            jnp.asarray(row_sums), jnp.asarray(meta),
                            observed, top_k=top_k, R=R, interpret=True)
    _, ref_idx = _unpack(ref, 1)
    _, got_idx = _unpack(got, 1)
    # Both keep slot order among the all-tied cells: first 4 partners.
    np.testing.assert_array_equal(ref_idx[0], partners[:top_k])
    np.testing.assert_array_equal(got_idx[0], partners[:top_k])


def test_rect_supported_gating():
    assert rect_supported(256, 10)
    assert rect_supported(1024, 10)
    assert not rect_supported(64, 10)       # narrow: XLA carries it
    assert not rect_supported(16, 10)
    assert not rect_supported(256, 200)     # top_k beyond lane width
    assert rect_tile(4096) == 2048  # wide tiles amortize the merge
    assert rect_tile(256) == 256
    with pytest.raises(ValueError, match="rect_supported"):
        pallas_score_rect(jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32),
                          jnp.zeros(16, jnp.int32),
                          jnp.zeros((3, 4), jnp.int32), np.float32(0),
                          top_k=10, R=64, interpret=True)


def test_rect_rejects_vocab_beyond_float32_exact():
    import functools

    import jax

    big = (1 << 24) + 128
    with pytest.raises(ValueError, match="2\\^24"):
        jax.eval_shape(
            functools.partial(pallas_score_rect, top_k=5, R=256,
                              interpret=True),
            jax.ShapeDtypeStruct((1024,), jnp.int32),
            jax.ShapeDtypeStruct((1024,), jnp.int32),
            jax.ShapeDtypeStruct((big,), jnp.int32),
            jax.ShapeDtypeStruct((3, 8), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))


def _dense_stream(seed=11, n=60_000, items=512):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, items, n).astype(np.int64)
    dst = rng.integers(0, items, n).astype(np.int64)
    keep = src != dst
    return PairDeltaBatch(src[keep], dst[keep],
                          np.ones(int(keep.sum()), dtype=np.int32))


def _assert_topk_match(out_on, out_off):
    """Kernel vs XLA result dicts {row: (vals, idx)} under the shared
    parity contract (ops/pallas_score.topk_parity — the same check the
    on-chip bench rows run)."""
    from tpu_cooccurrence.ops.pallas_score import topk_parity

    assert set(out_on) == set(out_off) and out_on
    rows = sorted(out_on)
    ok, mism = topk_parity(
        np.stack([out_off[r][0] for r in rows]),
        np.stack([out_off[r][1] for r in rows]),
        np.stack([out_on[r][0] for r in rows]),
        np.stack([out_on[r][1] for r in rows]))
    assert ok, "scores diverge between the kernel and XLA paths"
    assert mism == 0, f"{mism} untied positions carry different ids"


@pytest.mark.parametrize("mode", ["pipelined", "deferred-fixed"])
def test_sparse_scorer_pallas_end_to_end(mode):
    """SparseDeviceScorer --pallas on matches off, through both dispatch
    forms. The dense random stream pushes rows past 64 partners so the
    R=256 bucket (kernel-carried) is actually exercised."""
    pairs = _dense_stream()
    out = {}
    for pl in ("on", "off"):
        kw = (dict(defer_results=True, fixed_shapes=True)
              if mode == "deferred-fixed" else dict(defer_results=False))
        sc = SparseDeviceScorer(10, use_pallas=pl, **kw)
        sc.process_window(0, pairs)
        batches = [sc.flush()]
        if mode == "pipelined":
            batches.append(sc.flush())  # drain the one-window pipeline
        got = {int(r): (v.copy(), i.copy())
               for b in batches
               for r, i, v in zip(b.rows, b.idx, b.vals)}
        out[pl] = got
        # Sanity: the kernel path actually carried a wide bucket.
        if pl == "on":
            assert sc._rect_pallas(256), "R=256 bucket should be kernel-carried"
    _assert_topk_match(out["on"], out["off"])


def test_sharded_sparse_pallas_matches_xla():
    """ShardedSparseScorer --pallas on == off over the virtual 8-device
    mesh: the rectangle kernel runs per shard inside shard_map."""
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    pairs = _dense_stream(seed=13, n=40_000, items=384)
    out = {}
    for pl in ("on", "off"):
        sc = ShardedSparseScorer(10, num_shards=8, defer_results=True,
                                 fixed_shapes=True, use_pallas=pl)
        # Small fixed rectangles: interpret-mode pallas across 8 shards
        # is minutes at the default budget, seconds at this one.
        sc.FIXED_BUDGET = 1 << 13
        sc.FIXED_ROW_CAP = 32
        sc.process_window(0, pairs)
        b = sc.flush()
        out[pl] = {int(r): (v.copy(), i.copy())
                   for r, i, v in zip(b.rows, b.idx, b.vals)}
        if pl == "on":
            assert sc._rect_pallas(256)
    _assert_topk_match(out["on"], out["off"])


def test_sharded_dense_pallas_matches_xla():
    """ShardedScorer --pallas on == off over the virtual 8-device mesh
    (the dense kernel gathers from each shard's local row block against
    the replicated row sums). Small tile keeps interpret mode fast."""
    from tpu_cooccurrence.parallel.sharded import ShardedScorer

    class SmallTile(ShardedScorer):
        PALLAS_TILE = 128

    pairs = _dense_stream(seed=17, n=20_000, items=250)
    out = {}
    for pl in ("on", "off"):
        sc = SmallTile(250, 10, num_shards=8, use_pallas=pl,
                       count_dtype="int16")
        sc.process_window(0, pairs)
        b = sc.flush()
        out[pl] = {int(r): (v.copy(), i.copy())
                   for r, i, v in zip(b.rows, b.idx, b.vals)}
    _assert_topk_match(out["on"], out["off"])


def test_sparse_scorer_rejects_bad_pallas_value():
    with pytest.raises(ValueError, match="auto|on|off"):
        SparseDeviceScorer(10, use_pallas="yes")


def test_sparse_pallas_auto_defaults_off_on_cpu():
    """auto resolves OFF for the int32 slab (measured: XLA wins dense
    int32 ~5x; the sparse-pallas tpu_round2 row re-decides on chip)."""
    sc = SparseDeviceScorer(10, use_pallas="auto")
    assert sc.use_pallas is False
    assert not sc._rect_pallas(1024)


def test_sharded_dense_pallas_checkpoint_cross_padding(tmp_path):
    """A checkpoint written WITHOUT pallas (vocab padded to n_shards
    only) restores into a pallas-enabled scorer (vocab padded to a
    kernel-tile multiple) and vice versa — both directions continue to
    identical results."""
    from tpu_cooccurrence.parallel.sharded import ShardedScorer

    class SmallTile(ShardedScorer):
        PALLAS_TILE = 128

    pairs1 = _dense_stream(seed=21, n=8_000, items=250)
    pairs2 = _dense_stream(seed=22, n=8_000, items=250)

    def run(pl_first, pl_second):
        a = SmallTile(250, 10, num_shards=8, count_dtype="int16",
                      use_pallas=pl_first)
        a.process_window(0, pairs1)
        a.flush()
        st = a.checkpoint_state()
        b = SmallTile(250, 10, num_shards=8, count_dtype="int16",
                      use_pallas=pl_second)
        b.restore_state(st)
        b.process_window(10, pairs2)
        batch = b.flush()
        return {int(r): (v.copy(), i.copy())
                for r, i, v in zip(batch.rows, batch.idx, batch.vals)}

    ref = run("off", "off")
    for combo in (("off", "on"), ("on", "off"), ("on", "on")):
        _assert_topk_match(run(*combo), ref)
