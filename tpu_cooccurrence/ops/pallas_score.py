"""Pallas TPU kernel: fused LLR scoring + streaming top-K.

The XLA path (``ops/device_scorer._score``) materializes a ``[S, I]`` float32
score matrix in HBM and then runs ``lax.top_k`` over it — two full passes of
HBM traffic over data that is consumed once. This kernel fuses the whole of
hot loop 4 (SURVEY §3.4: contingency build + LLR + top-K selection): for
each block of scored rows it streams column tiles of those rows of ``C``
through VMEM, computes the stable-form LLR on the VPU, and folds each tile
into a running top-K scratch without ever writing scores back to HBM.

The kernel reads its rows from ``C`` in place (``dense_topk``). ``C``
stays in HBM (``memory_space=pl.ANY``). Mosaic slices HBM along whole
``(8, 128)`` tiles only (int16 as well: it packs row pairs into 32-bit
words), so a row is fetched with the 8-row group that holds it: one DMA
of ``[8, TILE]`` per group, shared by consecutive rows of a block that
fall in the same group. XLA plans the groups of each block from the row
ids (``_fetch_plan``, a few ops over ``S`` ints), and the plan rides in
scalar memory (``PrefetchScalarGridSpec``). Each grid step starts the
next step's DMAs before it scores its own tile, so the fetch overlaps
the LLR, and loads each row from its group (int16 through a 32-bit view
and a half-word shift) into the ``[R, TILE]`` count block. Nothing of
``C`` is copied or gathered outside the kernel.

Grid: ``(S // R, I // TILE)`` with ``R = BLOCK_ROWS`` rows per block.
The counts reach the LLR as float32, so ``R`` is free of the count
dtype's tiling: 64 rows a step amortize the per-step cost of the grid
and of issuing the fetches (measured on a v5e against 16, 32 and 128).
The running top-K lives in VMEM scratch that persists across the
column-tile dimension (sequential grid execution, innermost-last
order), initialized at ``j == 0`` and written to the output block at
the last tile. A block the caller marks as padding (``live``) plans no
groups: it fetches and scores nothing.

Tie-breaking matches ``lax.top_k`` (lowest column index among equal scores):
within a tile the extraction picks the minimum position, and the running
candidates occupy lower positions than the current tile's columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import tuning
from .llr import llr_stable

_K_PAD = 128     # output lane width; logical top_k occupies the first K lanes
#: Rows per fetch from C: C's HBM tiling is 8 rows deep for int32 and
#: int16 alike (int16 packs row pairs into 32-bit words), and Mosaic
#: slices HBM along whole tiles only, so a row comes with its group.
_GROUP = 8
#: Rows per grid step of the dense kernel (see the module docstring).
BLOCK_ROWS = 64


def fetch_cells(rows, width: int) -> int:
    """Cells of ``C`` the dense kernel DMAs for one call over the padded
    ``rows`` (host ints) at catalog ``width``: in each row block, a row
    fetches its ``_GROUP``-row group unless the row before it lies in the
    same group."""
    g = np.asarray(rows).reshape(-1, BLOCK_ROWS) // _GROUP
    groups = g.shape[0] + int((g[:, 1:] != g[:, :-1]).sum())
    return groups * _GROUP * width


def _load_row(group, sub):
    """Row ``sub`` of one fetched ``[_GROUP, TILE]`` group ref, as a
    ``[1, TILE]`` float32 (exact: counts are int16/int32)."""
    if group.dtype == jnp.int32:
        return group[pl.ds(sub, 1), :].astype(jnp.float32)
    # int16: rows 2m and 2m + 1 share 32-bit word m (low half first), and
    # Mosaic loads a single row of a 32-bit ref only. (Selecting the row
    # with a mask over all 8 took 2.5% longer on a v5e.)
    word = group.bitcast(jnp.int32)[pl.ds(lax.shift_right_logical(sub, 1),
                                          1), :]
    shift = lax.shift_left(lax.bitwise_xor(lax.bitwise_and(sub, 1), 1), 4)
    return lax.shift_right_arithmetic(lax.shift_left(word, shift),
                                      16).astype(jnp.float32)


def _score_topk_kernel(slots_ref, firsts_ref, n_ref, c_hbm, rsj_ref,
                       rsi_ref, obs_ref, vals_ref, idx_ref, groups, sems,
                       counts_ref, run_vals, run_idx, *, top_k, tile):
    i, j = pl.program_id(0), pl.program_id(1)
    n_i, n_j = pl.num_programs(0), pl.num_programs(1)
    R = BLOCK_ROWS
    step = i * n_j + j
    buf = step % 2

    def copy(bi, bj, b, g):
        """The DMA of row block ``bi``'s ``g``-th group, column tile
        ``bj``, into buffer ``b``."""
        first = pl.multiple_of(firsts_ref[bi * R + g], _GROUP)
        return pltpu.make_async_copy(
            c_hbm.at[pl.ds(first, _GROUP),
                     pl.ds(pl.multiple_of(bj * tile, tile), tile)],
            groups.at[b, g], sems.at[b, g])

    def start(bi, bj, b):
        @pl.loop(0, n_ref[bi])
        def _(g):
            copy(bi, bj, b, g).start()

    # Double buffering over the sequential grid: step t's groups were
    # requested at step t - 1, and step t + 1's go out before this
    # step's compute.
    @pl.when(step == 0)
    def _first():
        start(i, j, buf)

    @pl.when(step + 1 < n_i * n_j)
    def _next():
        last = j == n_j - 1
        start(jnp.where(last, i + 1, i), jnp.where(last, 0, j + 1), 1 - buf)

    @pl.when(j == 0)
    def _init():
        run_vals[...] = jnp.full((R, _K_PAD), -jnp.inf, dtype=jnp.float32)
        run_idx[...] = jnp.zeros((R, _K_PAD), dtype=jnp.float32)

    @pl.loop(0, n_ref[i])
    def _(g):
        copy(i, j, buf, g).wait()

    # A block with no groups is padding past the caller's live rows: it
    # fetched nothing and scores nothing (its output stays -inf).
    @pl.when(n_ref[i] > 0)
    def _score():
        _score_block(slots_ref, rsj_ref, rsi_ref, obs_ref, groups, buf,
                     counts_ref, run_vals, run_idx, i=i, j=j,
                     top_k=top_k, tile=tile)

    @pl.when(j == n_j - 1)
    def _emit():
        vals_ref[...] = run_vals[...]
        idx_ref[...] = run_idx[...]


def _score_block(slots_ref, rsj_ref, rsi_ref, obs_ref, groups, buf,
                 counts_ref, run_vals, run_idx, *, i, j, top_k, tile):
    """Row block ``i``'s column tile ``j``: load its rows from the
    groups fetched into buffer ``buf``, score them and fold the tile
    into the running top-K."""
    R = BLOCK_ROWS
    # Unrolled, so the scalar work uses lax ops: each jnp operator traces
    # a jitted wrapper of its own.
    base = i * R
    for k in range(R):
        slot_sub = slots_ref[lax.add(base, k)]
        counts_ref[k:k + 1, :] = _load_row(
            groups.at[buf, lax.shift_right_logical(slot_sub, 3)],
            lax.bitwise_and(slot_sub, _GROUP - 1))

    k11 = counts_ref[...]                                   # [R, TILE] f32
    rsj = rsj_ref[0, :].astype(jnp.float32)[None, :]        # [1, TILE]
    rsi = rsi_ref[...].astype(jnp.float32)                  # [R, 1]
    observed = obs_ref[0, 0].astype(jnp.float32)

    k12 = rsi - k11
    k21 = rsj - k11
    k22 = observed + k11 - k12 - k21
    scores = llr_stable(k11, k12, k21, k22)
    scores = jnp.where(k11 != 0, scores, -jnp.inf)          # [R, TILE]

    # Threshold skip. The LLR above is the larger per-cell cost: about
    # 326 traced ops and 8 divisions a cell, against about 80 for the
    # merge below (top_k extractions of about 8 ops over the
    # _K_PAD + tile candidates) and about 5 to load a row (PERF.md §5).
    # A tile needs the merge only if some row's tile-max beats that row's
    # running K-th best, but `need_merge` is an `any` over the block's 64
    # rows, so the skip rarely fires.
    thresh = run_vals[:, top_k - 1:top_k]                   # [R, 1]
    tile_max = jnp.max(scores, axis=1, keepdims=True)       # [R, 1]
    need_merge = jnp.any(tile_max > thresh)

    @pl.when((j == 0) | need_merge)
    def _merge():
        # Column ids ride through the selection as float32: int32 VMEM
        # scratch carried across grid steps miscompiles on current Mosaic
        # (output block silently zeroed once the row-grid dimension reaches
        # 4 — observed on v5e, jax 0.8.x); float32 holds ids exactly below
        # 2^24, which the wrapper enforces via the vocab-size guard.
        col_base = j * tile
        cols = (col_base
                + jax.lax.broadcasted_iota(jnp.int32, (R, tile), dimension=1)
                ).astype(jnp.float32)

        # Candidates: running top-K (positions 0.._K_PAD-1) then this tile.
        cand_vals = jnp.concatenate([run_vals[...], scores], axis=1)
        cand_idx = jnp.concatenate([run_idx[...], cols], axis=1)
        width = _K_PAD + tile
        positions = jax.lax.broadcasted_iota(jnp.int32, (R, width), dimension=1)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (R, _K_PAD), dimension=1)

        new_vals = jnp.full((R, _K_PAD), -jnp.inf, dtype=jnp.float32)
        new_idx = jnp.zeros((R, _K_PAD), dtype=jnp.float32)
        for k in range(top_k):  # static unroll; top_k is small
            m = jnp.max(cand_vals, axis=1, keepdims=True)             # [R, 1]
            pos = jnp.min(jnp.where(cand_vals == m, positions, width),
                          axis=1, keepdims=True)                      # [R, 1]
            sel = positions == pos                                    # [R, W]
            chosen = jnp.max(jnp.where(sel, cand_idx, 0.0),
                             axis=1, keepdims=True)                   # [R, 1]
            lane_k = lanes == k
            new_vals = jnp.where(lane_k, m, new_vals)
            new_idx = jnp.where(lane_k, chosen, new_idx)
            cand_vals = jnp.where(sel, -jnp.inf, cand_vals)

        run_vals[...] = new_vals
        run_idx[...] = new_idx


def _fetch_plan(local):
    """The DMAs of each row block, from its ``[Sp]`` local row ids
    (clamped into ``C``, as an XLA gather clamps its indices: a DMA is not
    checked): a row shares the group of the row before it when both lie
    in one ``_GROUP``-row group, else starts the block's next group.
    Returns ``slots`` [Sp] (group number * 8 + row within the group),
    ``firsts`` [Sp] (per block, each group's first row of ``C``) and
    ``n_groups`` [Sp // BLOCK_ROWS]. :func:`fetch_cells` counts the same.
    """
    first = jnp.bitwise_and(local, -_GROUP).reshape(-1, BLOCK_ROWS)
    nb = first.shape[0]
    starts = jnp.concatenate([jnp.ones((nb, 1), bool),
                              first[:, 1:] != first[:, :-1]], axis=1)
    group = jnp.cumsum(starts, axis=1, dtype=jnp.int32) - 1
    firsts = jnp.zeros_like(first).at[
        jnp.arange(nb)[:, None], group].set(first)
    slots = group.reshape(-1) * _GROUP + jnp.bitwise_and(local, _GROUP - 1)
    return slots, firsts.reshape(-1), group[:, -1] + 1


def dense_topk(C, rows, row_sums, observed, *, top_k: int, tile: int,
               interpret: bool, lo=0, live=None):
    """THE dense scoring core: fused LLR + top-K of ``C``'s rows
    ``rows - lo``, fetched by the kernel from ``C`` in HBM.

    C        [N, I] int32|int16 — dense counts, or a shard's row block
             of them starting at global row ``lo`` (N % 8 == 0,
             I % tile == 0)
    row_sums [I] int32 — global row sums
    rows     [Sp] int32 — global row ids, Sp % BLOCK_ROWS == 0
    live     int32 scalar or None — rows past the first ``live`` are
             padding: their whole blocks fetch and score nothing and
             return (-inf, 0), so one program serves every row count up
             to ``Sp`` at the cost of the live blocks only
    Returns (vals [Sp, _K_PAD] f32, idx [Sp, _K_PAD] f32 — ids as exact
    float values). The row ids ride in scalar memory; each grid step
    DMAs the 8-row groups its block needs for the next column tile while
    it scores this one.
    """
    n_rows, num_items = C.shape
    if C.dtype not in (jnp.int32, jnp.int16):
        raise ValueError(
            f"pallas scorer supports int32|int16 counts, got {C.dtype}")
    if num_items % tile != 0:
        raise ValueError(
            f"num_items {num_items} must be a multiple of tile {tile}")
    if n_rows % _GROUP:
        raise ValueError(
            f"C's {n_rows} rows must be a multiple of {_GROUP}: the kernel "
            f"fetches whole {_GROUP}-row groups")
    if num_items > 1 << 24:
        raise ValueError(
            f"num_items {num_items} exceeds 2^24: column ids are tracked as "
            f"exact float32 inside the kernel (int32 scratch miscompiles on "
            f"Mosaic); use the XLA scorer (pallas='off') beyond that")
    if top_k > _K_PAD:
        raise ValueError(
            f"top_k {top_k} exceeds the kernel's lane width {_K_PAD}; "
            f"use the XLA scorer (pallas='off') for larger K")
    blk = BLOCK_ROWS
    sp = rows.shape[0]
    # Device-side stage name of the row-sum lookup and the fetch plan (op
    # metadata in a profiler trace).
    with jax.named_scope("gather"):
        rsi = row_sums[rows].reshape(sp, 1)
        slots, firsts, n_groups = _fetch_plan(
            jnp.clip(rows - lo, 0, n_rows - 1))
        if live is not None:
            n_groups = jnp.where(
                jnp.arange(sp // blk, dtype=jnp.int32) * blk < live,
                n_groups, 0)
    obs = jnp.full((1, 1), observed, dtype=jnp.float32)
    kernel = functools.partial(_score_topk_kernel, top_k=top_k, tile=tile)
    # A Pallas custom call takes the innermost scope's name: the trace's
    # readers match it as ``pallas_score_topk`` in every program.
    with jax.named_scope("pallas_score_topk"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(sp // blk, num_items // tile),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((1, tile), lambda i, j, *_: (0, j)),
                    pl.BlockSpec((blk, 1), lambda i, j, *_: (i, 0)),
                    pl.BlockSpec((1, 1), lambda i, j, *_: (0, 0)),
                ],
                out_specs=(
                    pl.BlockSpec((blk, _K_PAD), lambda i, j, *_: (i, 0)),
                    pl.BlockSpec((blk, _K_PAD), lambda i, j, *_: (i, 0)),
                ),
                scratch_shapes=[
                    pltpu.VMEM((2, blk, _GROUP, tile), C.dtype),
                    pltpu.SemaphoreType.DMA((2, blk)),
                    pltpu.VMEM((blk, tile), jnp.float32),
                    pltpu.VMEM((blk, _K_PAD), jnp.float32),
                    pltpu.VMEM((blk, _K_PAD), jnp.float32),
                ],
            ),
            out_shape=(
                jax.ShapeDtypeStruct((sp, _K_PAD), jnp.float32),
                jax.ShapeDtypeStruct((sp, _K_PAD), jnp.float32),
            ),
            # Sequential grid: a step waits on DMAs the step before started.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(slots, firsts, n_groups, C, row_sums.reshape(1, num_items), rsi,
          obs)


def pallas_score_topk_local(C_loc, row_sums, rows_global, lo, observed, *,
                            top_k: int, tile: int = 512,
                            interpret: bool = False):
    """Sharded-dense form: score global ``rows_global`` out of a LOCAL row
    block ``C_loc`` (`[rows_per_shard, I]`, rows ``[lo, lo+rows_per_shard)``)
    against the replicated global ``row_sums``. For use inside a
    ``shard_map`` body (pallas_call is an ordinary per-device op there).

    Returns packed [2, S, top_k] float32 with ids as float *values*
    (decode with astype — same contract as ``pallas_score_topk(packed=
    True)``). Padded rows may repeat a real row; the caller drops them.
    """
    S = rows_global.shape[0]
    pad_s = (-S) % BLOCK_ROWS
    if pad_s:
        rows_global = jnp.concatenate(
            [rows_global, jnp.full(pad_s, lo, dtype=rows_global.dtype)])
    vals, idxf = dense_topk(C_loc, rows_global, row_sums, observed,
                            top_k=top_k, tile=tile, interpret=interpret,
                            lo=lo)
    return jnp.stack([vals[:S, :top_k], idxf[:S, :top_k]])


def _rect_topk_kernel(k11_ref, dsf_ref, rsj_ref, rsi_ref, obs_ref,
                      vals_ref, idx_ref, run_vals, run_idx, *, top_k,
                      tile, block):
    """Sparse-rectangle variant of :func:`_score_topk_kernel`.

    Same streaming top-K structure; differences: the contingency columns
    are slab cells, so the partner row sums arrive as a full
    ``[R, TILE]`` tile (gathered by partner id in XLA — the dense kernel
    broadcasts one ``[1, TILE]`` row-sum slice), and the candidate ids
    are the gathered partner ids (as float32 values), not a column iota.
    Tie-breaking still picks the lowest candidate *position* — position
    order is slab-slot order, which is exactly
    ``state/sparse_scorer._score_rect``'s ``lax.top_k`` tie rule
    (earliest-inserted cell of the row wins).
    """
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    R = block

    @pl.when(j == 0)
    def _init():
        run_vals[...] = jnp.full((R, _K_PAD), -jnp.inf, dtype=jnp.float32)
        run_idx[...] = jnp.zeros((R, _K_PAD), dtype=jnp.float32)

    k11i = k11_ref[...]                                     # [R, TILE] counts
    k11 = k11i.astype(jnp.float32)
    rsj = rsj_ref[...]                                      # [R, TILE]
    rsi = rsi_ref[...]                                      # [R, 1]
    observed = obs_ref[0, 0]

    k12 = rsi - k11
    k21 = rsj - k11
    k22 = observed + k11 - k12 - k21
    scores = llr_stable(k11, k12, k21, k22)
    scores = jnp.where(k11i != 0, scores, -jnp.inf)         # [R, TILE]

    # Threshold skip — see _score_topk_kernel.
    thresh = run_vals[:, top_k - 1:top_k]
    tile_max = jnp.max(scores, axis=1, keepdims=True)
    need_merge = jnp.any(tile_max > thresh)

    @pl.when((j == 0) | need_merge)
    def _merge():
        cand_vals = jnp.concatenate([run_vals[...], scores], axis=1)
        cand_idx = jnp.concatenate([run_idx[...], dsf_ref[...]], axis=1)
        width = _K_PAD + tile
        positions = jax.lax.broadcasted_iota(jnp.int32, (R, width),
                                             dimension=1)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (R, _K_PAD), dimension=1)

        new_vals = jnp.full((R, _K_PAD), -jnp.inf, dtype=jnp.float32)
        new_idx = jnp.zeros((R, _K_PAD), dtype=jnp.float32)
        for k in range(top_k):  # static unroll; top_k is small
            m = jnp.max(cand_vals, axis=1, keepdims=True)
            pos = jnp.min(jnp.where(cand_vals == m, positions, width),
                          axis=1, keepdims=True)
            sel = positions == pos
            chosen = jnp.max(jnp.where(sel, cand_idx, 0.0),
                             axis=1, keepdims=True)
            lane_k = lanes == k
            new_vals = jnp.where(lane_k, m, new_vals)
            new_idx = jnp.where(lane_k, chosen, new_idx)
            cand_vals = jnp.where(sel, -jnp.inf, cand_vals)

        run_vals[...] = new_vals
        run_idx[...] = new_idx

    @pl.when(j == n_j - 1)
    def _emit():
        vals_ref[...] = run_vals[...]
        idx_ref[...] = run_idx[...]


def rect_tile(R: int) -> int:
    """Column-tile width for a rectangle of width ``R`` (lane-aligned).

    Wide tiles amortize the sequential top-K merge: the on-chip dense
    sweep measured 2048 → 179 ms vs 512 → 300 ms at [8192, 61440] int16
    (before this round; awaits a cell), and the int32 rectangle blocks
    are 8 sublanes, so a [8, 2048] i32 tile is ~64 KB — far under VMEM. The
    sparse-pallas bench row re-times each rectangle width on chip.
    """
    return min(2048, R)


#: Narrowest rectangle the fused kernel accepts (registry-declared).
_RECT_MIN_ROWS = int(tuning.default("rect_min_rows"))


def rect_supported(R: int, top_k: int) -> bool:
    """Whether the fused rectangle kernel can carry this bucket.

    Narrow rectangles (R < 256) don't tile the 128-lane VPU cleanly and
    are cheap for XLA anyway; ``top_k`` must fit the output lane width.
    """
    t = rect_tile(R)
    return (R >= _RECT_MIN_ROWS and R % t == 0 and t % 128 == 0
            and top_k <= _K_PAD)


def rect_routed(enabled: bool, R: int, top_k: int, items_cap: int) -> bool:
    """THE routing rule for sparse rectangles, shared by the
    single-device and sharded sparse scorers: kernel iff requested,
    the bucket is kernel-carriable, and the vocab fits the float32-id
    encoding (partner ids ride as exact f32 below 2^24) — a vocab
    growing past the bound reroutes new plans to XLA instead of
    raising mid-stream."""
    return enabled and rect_supported(R, top_k) and items_cap <= 1 << 24


def topk_parity(vals_a, idx_a, vals_b, idx_b, rtol=1e-5, atol=1e-5):
    """THE kernel-vs-XLA parity contract, shared by tests and the on-chip
    bench checks: scores allclose, and every UNTIED position (score
    unique within its row under the same tolerance) carries the same id.
    Tied positions may legitimately order differently. Vectorized —
    cheap enough to run inside a chip session.

    Returns ``(scores_allclose: bool, untied_id_mismatches: int)``.
    """
    import numpy as np

    vals_a, vals_b = np.asarray(vals_a), np.asarray(vals_b)
    idx_a, idx_b = np.asarray(idx_a), np.asarray(idx_b)
    scores_ok = bool(np.allclose(vals_a, vals_b, rtol=rtol, atol=atol))
    untied = np.isclose(vals_a[:, :, None], vals_a[:, None, :],
                        rtol=rtol, atol=atol).sum(-1) == 1
    mism = int(((idx_a != idx_b) & np.isfinite(vals_a) & untied).sum())
    return scores_ok, mism


def resolve_sparse_pallas_flag(use_pallas: str) -> bool:
    """Resolve an ``auto|on|off`` --pallas request for a SPARSE scorer.

    auto is OFF for now: slab counts are int32, where the dense A/B
    measured before this round favored XLA ~5x (v5e; awaits a cell,
    ROADMAP A4); the sparse-pallas measurement re-decides this on chip,
    and this default flips if the rectangle form cliffs like dense int16
    did (247x). 'on' forces the kernel for every rectangle
    :func:`rect_supported` can carry; narrow buckets stay XLA either
    way."""
    if use_pallas not in ("auto", "on", "off"):
        raise ValueError(f"use_pallas must be auto|on|off, got {use_pallas!r}")
    return use_pallas == "on"


def pallas_score_rect(cnt, dst, row_sums, meta, observed, *, top_k: int,
                      R: int, interpret: bool = False):
    """Fused LLR + top-K over one slab length-bucket rectangle.

    Drop-in replacement for ``state/sparse_scorer._score_rect`` (same
    arguments, same packed ``[2, S_pad, K]`` float32 output with ids as
    an int32 *bitcast*, same tie semantics), for use inside a jit — the
    slab/row-sum gathers stay in XLA (a rectangle's cells lie at
    arbitrary slab offsets); the kernel fuses away the ``[S, R]`` float32
    score materialization and ``top_k``'s second full pass over it.

    cnt/dst   [cap]  int32 — slab cells (counts / partner ids)
    row_sums  [I]    int32
    meta      [3, S] int32 — (row id, slab start, row len); len==0 pads
    observed  scalar float32
    """
    if not rect_supported(R, top_k):
        raise ValueError(
            f"rectangle R={R} top_k={top_k} unsupported by the fused "
            f"kernel; gate callers on rect_supported()")
    num_items = row_sums.shape[0]
    if num_items > 1 << 24:
        raise ValueError(
            f"vocab {num_items} exceeds 2^24: partner ids ride the kernel "
            f"as exact float32 (int32 scratch miscompiles on Mosaic); use "
            f"the XLA rectangle scorer beyond that")
    tile = rect_tile(R)
    blk = 8  # int32 sublane tile
    rowids, starts, lens = meta[0], meta[1], meta[2]
    S = meta.shape[1]
    pad_s = (-S) % blk
    if pad_s:
        z = jnp.zeros((3, pad_s), dtype=meta.dtype)
        rowids = jnp.concatenate([rowids, z[0]])
        starts = jnp.concatenate([starts, z[1]])
        lens = jnp.concatenate([lens, z[2]])
    sp = S + pad_s

    # XLA pre-gathers (the kernel reads rectangles, Mosaic can't index
    # arbitrary slab offsets from inside a block) — the SAME gather/mask
    # code as the XLA scorer, so the two paths cannot drift.
    from ..state.sparse_scorer import gather_rect

    meta_p = jnp.stack([rowids, starts, lens])
    k11, _valid, ds, rsj, rsi = gather_rect(cnt, dst, row_sums, meta_p, R)
    dsf = ds.astype(jnp.float32)                         # exact < 2^24
    obs = jnp.full((1, 1), observed, dtype=jnp.float32)

    kernel = functools.partial(_rect_topk_kernel, top_k=top_k, tile=tile,
                               block=blk)
    vals, idxf = pl.pallas_call(
        kernel,
        grid=(sp // blk, R // tile),
        in_specs=[
            pl.BlockSpec((blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((blk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((blk, _K_PAD), lambda i, j: (i, 0)),
            pl.BlockSpec((blk, _K_PAD), lambda i, j: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk, _K_PAD), jnp.float32),
            pltpu.VMEM((blk, _K_PAD), jnp.float32),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((sp, _K_PAD), jnp.float32),
            jax.ShapeDtypeStruct((sp, _K_PAD), jnp.float32),
        ),
        interpret=interpret,
    )(k11, dsf, rsj, rsi, obs)
    # Same wire format as _score_rect (results.pack_ids; the float->int
    # conversion happens here in XLA, where it is exact and immune to
    # the Mosaic carried-scratch issue the value-space encoding works
    # around inside the kernel).
    from ..state.results import pack_ids

    ids = idxf[:S, :top_k].astype(jnp.int32)
    return jnp.stack([vals[:S, :top_k], pack_ids(ids)])


@functools.partial(jax.jit,
                   static_argnames=("top_k", "tile", "interpret", "packed"))
def pallas_score_topk(C, row_sums, rows, observed, *, top_k: int,
                      tile: int = 512, interpret: bool = False,
                      packed: bool = False):
    """Fused LLR + top-K over rows of ``C``, which the kernel fetches
    from HBM itself (``dense_topk``). Mirrors ``device_scorer._score``.

    C        [I, I] int32|int16 — dense co-occurrence counts (I % tile == 0)
    row_sums [I]    int32
    rows     [S]    int32 — row ids to score (padded rows allowed)
    observed scalar float32
    Returns (vals [S, top_k] f32, idx [S, top_k] i32), scores descending;
    with ``packed=True`` a single [2, S, top_k] float32 — idx as exact
    float *values* (decode with ``astype``, not a bitcast view) — so the
    caller fetches one buffer.
    """
    S = rows.shape[0]
    pad_s = (-S) % BLOCK_ROWS
    if pad_s:
        rows = jnp.concatenate([rows, jnp.zeros(pad_s, dtype=rows.dtype)])
    vals, idx = dense_topk(C, rows, row_sums, observed, top_k=top_k,
                           tile=tile, interpret=interpret)
    vals = vals[:S, :top_k]
    if packed:
        # Value-space packing: ids stay exact float32 (wrapper guard caps
        # the vocab at 2^24). bitcast_convert_type on the kernel's second
        # output miscompiles to zeros on current Mosaic once the row grid
        # reaches 4 blocks, so the host decodes with astype, not view —
        # see DeviceScorer._materialize.
        return jnp.stack([vals, idx[:S, :top_k]])
    return vals, idx[:S, :top_k].astype(jnp.int32)
