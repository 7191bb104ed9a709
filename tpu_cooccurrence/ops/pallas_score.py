"""Pallas TPU kernel: fused LLR scoring + streaming top-K.

The XLA path (``ops/device_scorer._score``) materializes a ``[S, I]`` float32
score matrix in HBM and then runs ``lax.top_k`` over it — two full passes of
HBM traffic over data that is consumed once. This kernel fuses the whole of
hot loop 4 (SURVEY §3.4: contingency build + LLR + top-K selection): for
each block of scored rows it streams column tiles of the gathered count
rows through VMEM, computes the stable-form LLR on the VPU, and folds each
tile into a running top-K scratch without ever writing scores back to HBM.

The row gather ``C[rows]`` happens in XLA before the kernel and does
materialize an ``[S, I]`` int32 buffer in HBM (TPU block layout requires
sublane-aligned blocks, so arbitrary single-row blocks can't be indexed
from inside the kernel). What the fusion removes versus the XLA path is
the float32 score matrix write plus ``top_k``'s separate full re-read of
it; the caller additionally bounds ``S`` so the gathered buffer stays
within a fixed HBM budget (``DeviceScorer.max_score_rows``).

Grid: ``(S // R, I // TILE)`` with ``R = row_block(count_dtype)`` rows per
block — the count dtype's sublane tile (8 for int32, 16 for int16, whose
halved bytes are exactly the regime where fusing away the f32 score
matrix matters most). The running top-K lives in VMEM scratch that
persists across the column-tile dimension (sequential grid execution,
innermost-last order), initialized at ``j == 0`` and written to the
output block at the last tile.

Tie-breaking matches ``lax.top_k`` (lowest column index among equal scores):
within a tile the extraction picks the minimum position, and the running
candidates occupy lower positions than the current tile's columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import tuning
from .llr import llr_stable

_K_PAD = 128     # output lane width; logical top_k occupies the first K lanes


def row_block(count_dtype) -> int:
    """Rows per grid step: the sublane tile of the count dtype.

    int32 tiles are (8, 128); int16 packs two values per sublane word, so
    its native tile is (16, 128) — 16-row blocks keep the gathered count
    rectangle layout-aligned and feed the VPU full registers.
    """
    return 16 if jnp.dtype(count_dtype).itemsize == 2 else 8


def _score_topk_kernel(g_ref, rsj_ref, rsi_ref, obs_ref,
                       vals_ref, idx_ref, run_vals, run_idx, *, top_k, tile,
                       block):
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    R = block

    @pl.when(j == 0)
    def _init():
        run_vals[...] = jnp.full((R, _K_PAD), -jnp.inf, dtype=jnp.float32)
        run_idx[...] = jnp.zeros((R, _K_PAD), dtype=jnp.float32)

    counts = g_ref[...]                                     # [R, TILE] counts
    k11 = counts.astype(jnp.float32)
    rsj = rsj_ref[0, :].astype(jnp.float32)[None, :]        # [1, TILE]
    rsi = rsi_ref[...].astype(jnp.float32)                  # [R, 1]
    observed = obs_ref[0, 0].astype(jnp.float32)

    k12 = rsi - k11
    k21 = rsj - k11
    k22 = observed + k11 - k12 - k21
    scores = llr_stable(k11, k12, k21, k22)
    scores = jnp.where(counts != 0, scores, -jnp.inf)       # [R, TILE]

    # Threshold skip: the merge below costs more VPU work than the LLR
    # itself (top_k sequential extractions over the candidate width). A
    # tile only needs it if some row's tile-max beats that row's running
    # K-th best; after the first few column tiles most tiles lose and the
    # whole merge is skipped, leaving the kernel LLR-bound.
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

    @pl.when(j == n_j - 1)
    def _emit():
        vals_ref[...] = run_vals[...]
        idx_ref[...] = run_idx[...]


def _pallas_topk_gathered(gathered, rs2d, rsi, observed, *, top_k: int,
                          tile: int, blk: int, interpret: bool):
    """The dense kernel's pallas_call on pre-gathered inputs.

    gathered [Sp, I] int32|int16 (Sp % blk == 0, I % tile == 0),
    rs2d [1, I] int32, rsi [Sp, 1] int32, observed scalar f32.
    Returns (vals [Sp, _K_PAD] f32, idx [Sp, _K_PAD] f32 — ids as exact
    float values). Shared by the single-chip wrapper (which gathers
    ``C[rows]``) and the sharded backend (which gathers from its local
    row block but passes the replicated global row sums).
    """
    sp, num_items = gathered.shape
    obs = jnp.full((1, 1), observed, dtype=jnp.float32)
    kernel = functools.partial(_score_topk_kernel, top_k=top_k, tile=tile,
                               block=blk)
    return pl.pallas_call(
        kernel,
        grid=(sp // blk, num_items // tile),
        in_specs=[
            pl.BlockSpec((blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((1, tile), lambda i, j: (0, j)),
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
    )(gathered, rs2d, rsi, obs)


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
    num_items = C_loc.shape[1]
    if C_loc.dtype not in (jnp.int32, jnp.int16):
        raise ValueError(
            f"pallas scorer supports int32|int16 counts, got {C_loc.dtype}")
    if num_items % tile != 0:
        raise ValueError(
            f"num_items {num_items} must be a multiple of tile {tile}")
    if num_items > 1 << 24:
        raise ValueError(
            f"num_items {num_items} exceeds 2^24: column ids ride as exact "
            f"float32; use the XLA scorer beyond that")
    if top_k > _K_PAD:
        raise ValueError(
            f"top_k {top_k} exceeds the kernel's lane width {_K_PAD}")
    blk = row_block(C_loc.dtype)
    S = rows_global.shape[0]
    pad_s = (-S) % blk
    if pad_s:
        rows_global = jnp.concatenate(
            [rows_global, jnp.full(pad_s, lo, dtype=rows_global.dtype)])
    sp = S + pad_s
    gathered = C_loc[rows_global - lo]                   # [Sp, I]
    rsi = row_sums[rows_global].reshape(sp, 1)
    rs2d = row_sums.reshape(1, num_items)
    vals, idxf = _pallas_topk_gathered(gathered, rs2d, rsi, observed,
                                       top_k=top_k, tile=tile, blk=blk,
                                       interpret=interpret)
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
    slab/row-sum gathers stay in XLA exactly like the dense kernel's
    ``C[rows]`` gather; the kernel fuses away the ``[S, R]`` float32
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


def _expand_kernel(basket_ref, new_ref, len_ref, skip_ref, sign_ref,
                   src_ref, dst_ref, delta_ref, *, width, block):
    """On-chip basket expansion: one star op per row.

    Row ``r`` expands op ``(new, basket[:len], skip, sign)`` into the
    ``2 * width`` COO lanes ``[new -> basket[j] | j] ++ [basket[j] ->
    new | j]`` with ``delta = sign`` on the valid lanes (``j < len``,
    ``j != skip``) and the padded ``(0, 0, 0)`` no-op triple everywhere
    else — the same pad-slot invariant the chained COO upload carries
    (``device_scorer.process_window``), so the scatter that consumes
    these lanes needs no masking. Pure VPU selects over a column iota;
    no cross-lane traffic.
    """
    R = block
    basket = basket_ref[...]                            # [R, W] int32
    new = new_ref[...]                                  # [R, 1] int32
    lens = len_ref[...]                                 # [R, 1] int32
    skip = skip_ref[...]                                # [R, 1] int32
    sign = sign_ref[...]                                # [R, 1] int32
    j = jax.lax.broadcasted_iota(jnp.int32, (R, width), dimension=1)
    valid = (j < lens) & (j != skip)
    zero = jnp.zeros((R, width), dtype=jnp.int32)
    fwd_src = jnp.where(valid, new + zero, zero)
    fwd_dst = jnp.where(valid, basket, zero)
    d = jnp.where(valid, sign + zero, zero)
    src_ref[...] = jnp.concatenate([fwd_src, fwd_dst], axis=1)
    dst_ref[...] = jnp.concatenate([fwd_dst, fwd_src], axis=1)
    delta_ref[...] = jnp.concatenate([d, d], axis=1)


#: Ops-axis block of the expansion kernel (int32 sublane tile).
_EXPAND_BLOCK = 8


def pallas_expand_baskets(basket, new, lens, skips, signs, *,
                          interpret: bool = False):
    """Expand a padded basket tensor into COO pair-delta lanes on chip.

    The device half of the fused window dispatch
    (``device_scorer._fused_window_emit``/``_defer``): takes the padded
    per-op basket rectangle the host uplinked and produces the
    ``(src, dst, delta)`` lanes the count scatter consumes, replacing
    the host-side ``native/reservoir_expand.cpp`` expansion plus the
    3x-wider COO uplink.

    basket [N, W] int32 — partner rows (cells at ``j >= len`` are
                          UNSPECIFIED, masked in-kernel; ``W % 128 == 0``)
    new/lens/skips/signs [N, 1] int32 — star item, valid-cell count,
                          excluded column (-1 = none), delta sign
                          (padded ops: len 0, sign 0)
    Returns ``(src, dst, delta)`` each [N, 2W] int32; invalid lanes
    carry the (0, 0, 0) scatter no-op triple.
    """
    n, width = basket.shape
    if n % _EXPAND_BLOCK:
        raise ValueError(
            f"op count {n} must be a multiple of {_EXPAND_BLOCK} "
            f"(pad the ops axis)")
    if width % 128:
        raise ValueError(
            f"basket width {width} must be a multiple of 128 lanes")
    kernel = functools.partial(_expand_kernel, width=width,
                               block=_EXPAND_BLOCK)
    blk = _EXPAND_BLOCK
    return pl.pallas_call(
        kernel,
        grid=(n // blk,),
        in_specs=[
            pl.BlockSpec((blk, width), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((blk, 2 * width), lambda i: (i, 0)),
            pl.BlockSpec((blk, 2 * width), lambda i: (i, 0)),
            pl.BlockSpec((blk, 2 * width), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, 2 * width), jnp.int32),
            jax.ShapeDtypeStruct((n, 2 * width), jnp.int32),
            jax.ShapeDtypeStruct((n, 2 * width), jnp.int32),
        ),
        interpret=interpret,
    )(basket, new, lens, skips, signs)


@functools.partial(jax.jit,
                   static_argnames=("top_k", "tile", "interpret", "packed"))
def pallas_score_topk(C, row_sums, rows, observed, *, top_k: int,
                      tile: int = 512, interpret: bool = False,
                      packed: bool = False):
    """Fused LLR + top-K over gathered rows. Mirrors ``device_scorer._score``.

    C        [I, I] int32|int16 — dense co-occurrence counts (I % tile == 0)
    row_sums [I]    int32
    rows     [S]    int32 — row ids to score (padded rows allowed)
    observed scalar float32
    Returns (vals [S, top_k] f32, idx [S, top_k] i32), scores descending;
    with ``packed=True`` a single [2, S, top_k] float32 — idx as exact
    float *values* (decode with ``astype``, not a bitcast view) — so the
    caller fetches one buffer.
    """
    num_items = C.shape[0]
    if C.dtype not in (jnp.int32, jnp.int16):
        raise ValueError(
            f"pallas scorer supports int32|int16 counts, got {C.dtype}")
    blk = row_block(C.dtype)
    if num_items % tile != 0:
        raise ValueError(f"num_items {num_items} must be a multiple of tile {tile}")
    if num_items > 1 << 24:
        raise ValueError(
            f"num_items {num_items} exceeds 2^24: column ids are tracked as "
            f"exact float32 inside the kernel (int32 scratch miscompiles on "
            f"Mosaic); use the XLA scorer (pallas='off') beyond that")
    if top_k > _K_PAD:
        raise ValueError(
            f"top_k {top_k} exceeds the kernel's lane width {_K_PAD}; "
            f"use the XLA scorer (pallas='off') for larger K")
    S = rows.shape[0]
    pad_s = (-S) % blk
    if pad_s:
        rows = jnp.concatenate([rows, jnp.zeros(pad_s, dtype=rows.dtype)])
    sp = S + pad_s
    # Device-side stage name of the row gather (op metadata in a
    # profiler trace). The kernel stays outside any scope: a Pallas
    # custom call takes the innermost scope's name, and the trace's
    # readers match it as ``pallas_score_topk``.
    with jax.named_scope("gather"):
        gathered = C[rows]                               # [Sp, I] count dtype
        rsi = row_sums[rows].reshape(sp, 1)
    rs2d = row_sums.reshape(1, num_items)
    vals, idx = _pallas_topk_gathered(gathered, rs2d, rsi, observed,
                                      top_k=top_k, tile=tile, blk=blk,
                                      interpret=interpret)
    vals = vals[:S, :top_k]
    if packed:
        # Value-space packing: ids stay exact float32 (wrapper guard caps
        # the vocab at 2^24). bitcast_convert_type on the kernel's second
        # output miscompiles to zeros on current Mosaic once the row grid
        # reaches 4 blocks, so the host decodes with astype, not view —
        # see DeviceScorer._materialize.
        return jnp.stack([vals, idx[:S, :top_k]])
    return vals, idx[:S, :top_k].astype(jnp.int32)
