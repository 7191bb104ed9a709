"""Multi-chip sparse backend: row-sharded HBM slabs over an item mesh.

Combines the two scale axes of this framework: the device-resident sparse
slab of ``state/sparse_scorer.py`` (vocabularies beyond any dense ceiling,
minimal host<->device transfer) and the mesh distribution of
``parallel/sharded.py`` (the TPU-native replacement of the reference's
keyed shuffle + broadcast, SURVEY §2.6):

  * Item rows are **modulo-sharded**: shard ``d`` of ``D`` owns every row
    ``r`` with ``r % D == d`` — the ``keyBy(item)`` analogue. Modulo (not
    block) keeps Zipf-head rows spread across chips. Each shard runs its
    own :class:`~tpu_cooccurrence.state.sparse_scorer.SlabIndex` over
    *shard-local* row ids ``r // D`` and a private slab in its HBM.
  * ``row_sums`` is **replicated** (the broadcast analogue,
    ``FlinkCooccurrences.java:163``): each shard scatters its owned rows'
    window deltas into a partial vector and a ``lax.psum`` over ICI
    makes every replica whole — the only cross-chip communication in the
    entire step. Scoring then reads any partner's sum locally.
  * Scoring and top-K stay **shard-local** (each shard owns its rows
    outright), exactly like the dense sharded backend.

One program per step phase (``shard_map`` under ``jit``), fixed shapes
via the same configurable score ladders (default pow-4) as the
single-device sparse backend, host placement decisions per shard. Works identically on a virtual CPU mesh
and real TPU meshes.

Single-process checkpoints use the canonical sparse-matrix format (global
key space), so they are interchangeable with the single-device sparse and
hybrid backends — a 1-chip checkpoint restores onto 8 shards and back.
Multi-host (multi-controller) runs save per process instead
(``process_suffix``, like the dense sharded backend): the host-replicated
index keys go in every file, the slab counts only for the shards the
process's chips own; restore requires the writing run's process layout.

``--fused-window on`` extends the single-device one-dispatch window
(state/sparse_scorer._fused_sparse_body) to this mesh: per-shard
device-resident registry mirrors (``reg_start``/``reg_len`` blocks
indexed by shard-local row id) sync from each shard's
``_RegistryDirtyLog``, the packed-uplink decode prologue runs per shard
on its ownership-partitioned word streams, and the update scatter + psum
+ mirror sync + rescore + results scatter compile into ONE ``shard_map``
program — a steady-state window is exactly one launch per worker.
Relocation / promotion / upload-split windows and the first window after
construction or restore (the rescale seam: every bucket plan is invalid
until rebuilt from post-restore registry state) route down the chained
path per window, bit-identically — the fused body is built from the same
trace bodies (``_apply_cells``, ``_rect_score``) the chained programs
use. See ``_fallback_chained`` for the reason taxonomy (each reason is a
documented contract enforced by the analyzer's fused-fallback-registry
rule).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..metrics import Counters, RESCORED_ITEMS, ROW_SUM_PROCESS_WINDOW
from .. import tuning
from ..observability import LEDGER, StageClock
from ..ops.aggregate import (aggregate_window_coo, distinct_sorted,
                             narrow_deltas_int32)
from ..ops.device_scorer import pad_pow2, pad_pow4
from ..ops.donation import donate_argnums
from ..sampling.reservoir import PairDeltaBatch
from ..state.results import TopKBatch, unpack_ids
from ..state.sparse_scorer import (_SENT, SlabIndex, _apply_cells,
                                   _pow2ceil, _score_rect, bucket_r,
                                   fixed_block, ladder_bits,
                                   make_slab_index, resolve_fixed_shapes,
                                   score_buckets)
from .mesh import ITEM_AXIS, make_mesh, shard_map_maybe_relaxed
from .sharded import _record_shard_metrics


class ShardedSparseScorer:
    """Modulo-row-sharded sparse slabs + replicated row sums via psum."""

    SCORE_BUDGET = 1 << 24  # per-shard padded-cell budget per score call
    # Fixed-shape mode budgets (PER SHARD — every shard pads to the same
    # rectangle; see state/sparse_scorer.SparseDeviceScorer).
    FIXED_BUDGET = 1 << 22
    FIXED_ROW_CAP = 1 << 16

    def __init__(self, top_k: int, num_shards: Optional[int] = None,
                 counters: Optional[Counters] = None,
                 mesh: Optional[Mesh] = None,
                 development_mode: bool = False,
                 capacity: int = 1 << 14,
                 items_capacity: int = 1 << 10,
                 compact_min_heap: int = 1 << 16,
                 score_ladder: Optional[int] = None,
                 defer_results: bool = False,
                 fixed_shapes: Optional[bool] = None,
                 use_pallas: str = "auto",
                 cell_dtype: str = "int32",
                 wire_format: str = "raw",
                 fused_window: str = "off") -> None:
        from ..state.wire import CELL_DTYPES, cell_promote_threshold

        if cell_dtype not in CELL_DTYPES:
            raise ValueError(
                f"cell_dtype must be one of {sorted(CELL_DTYPES)}, got "
                f"{cell_dtype!r}")
        if wire_format not in ("raw", "packed"):
            raise ValueError(
                f"wire_format must be raw or packed, got {wire_format!r}")
        self.cell_dtype = cell_dtype
        self._cnt_dtype = CELL_DTYPES[cell_dtype]
        self.promote_threshold = cell_promote_threshold(cell_dtype)
        self.wire_format = wire_format
        self.wire_packed = wire_format == "packed"
        self.top_k = top_k
        self.score_ladder = int(score_ladder if score_ladder is not None
                                else tuning.env_read(
                                    "TPU_COOC_SCORE_LADDER", 4))
        ladder_bits(self.score_ladder)  # validate at construction
        self.counters = counters if counters is not None else Counters()
        self.development_mode = development_mode
        self.mesh = mesh if mesh is not None else make_mesh(num_shards)
        self.n_shards = self.mesh.devices.size
        self.indexes = [make_slab_index(rows_capacity=max(
                            items_capacity // self.n_shards, 16))
                        for _ in range(self.n_shards)]
        self.items_cap = int(items_capacity)
        self.row_sums_host = np.zeros(self.items_cap, dtype=np.int64)
        self.compact_min_heap = int(compact_min_heap)
        self.capacity = int(capacity)  # per-shard slab capacity
        self.observed = 0
        self._pending: Optional[List] = None
        self.last_dispatched_rows = 0
        # (R, pallas-routed) -> jitted shard_map fn
        self._score_fns: Dict[tuple, object] = {}
        # Deferred-results mode (same design as the single-device scorers,
        # ops/device_scorer.DeferredResultsTable, here sharded): each
        # shard scatters its rows' packed top-K into a mesh-sharded
        # [D, 2, local_cap, K] table inside the scoring dispatch; flush
        # drains only rows dirty since the last flush, each process
        # fetching its addressable shards. Per-window result downlink
        # drops to zero. The lifecycle (lazy ensure, resize-on-growth,
        # mark/drain-pop, reset-on-restore) deliberately parallels
        # DeferredResultsTable rather than reusing it: the sharded table
        # shape, the shard_map scatter/gather, and the per-process
        # addressable-shard drain replace every method body — keep the
        # two in sync when changing mask semantics (see that class's
        # docstring for the contract).
        self.defer_results = bool(defer_results)
        self._tbl = None          # lazy [D, 2, local_cap, K] device array
        self._tbl_dirty = np.zeros(self.items_cap, dtype=bool)
        self._score_into_fns: Dict[tuple, object] = {}  # (R, pallas-routed)
        self._score_window_fns: Dict[tuple, object] = {}  # (plan, routed)
        self._tbl_gather_fns: Dict[int, object] = {}
        # Fixed-shape scoring (same contract and env override as the
        # single-device sparse scorer — constant per-bucket rectangles,
        # one fused window dispatch over a monotone high-water plan).
        self.fixed_shapes = resolve_fixed_shapes(fixed_shapes,
                                                 self.defer_results)
        self._plan_buckets = {}  # bucket -> high-water chunk count
        # Fused-kernel routing, same contract as the single-device sparse
        # scorer (ops/pallas_score.resolve_sparse_pallas_flag): the
        # Pallas rectangle kernel runs PER SHARD inside the shard_map
        # bodies (pallas_call is an ordinary per-device op there).
        from ..ops.pallas_score import resolve_sparse_pallas_flag

        self.use_pallas = resolve_sparse_pallas_flag(use_pallas)
        self._pallas_interpret = jax.default_backend() != "tpu"

        from .distributed import put_global

        self._put_global = put_global
        self.cnt = put_global(
            np.zeros((self.n_shards, self.capacity), self._cnt_dtype),
            self.mesh, P(ITEM_AXIS, None))
        self.dst = put_global(
            np.zeros((self.n_shards, self.capacity), np.int32),
            self.mesh, P(ITEM_AXIS, None))
        self.row_sums = put_global(
            np.zeros((self.items_cap,), np.int32), self.mesh, P())
        # Narrow cell dtypes: the wide int32 side-table (same design as
        # the single-device scorer — rows whose sum crossed the narrow
        # bound move wholesale), here a second sharded slab pair over
        # per-shard SlabIndexes. ``wide_rows`` is host-replicated like
        # every placement decision.
        if self.promote_threshold is not None:
            self.indexes_w = [make_slab_index(rows_capacity=max(
                                  items_capacity // self.n_shards, 16))
                              for _ in range(self.n_shards)]
            self.wide_rows = np.zeros(self.items_cap, dtype=bool)
            self.capacity_w = 1 << 10
            self.cnt_w = put_global(
                np.zeros((self.n_shards, self.capacity_w), np.int32),
                self.mesh, P(ITEM_AXIS, None))
            self.dst_w = put_global(
                np.zeros((self.n_shards, self.capacity_w), np.int32),
                self.mesh, P(ITEM_AXIS, None))
        else:
            self.indexes_w = None
            self.wide_rows = None
            self.capacity_w = 0
            self.cnt_w = self.dst_w = None
        self._plan_buckets_w = {}  # wide rows' own monotone plan
        # Fused one-dispatch window on the mesh (--fused-window on the
        # sharded sparse backend): deferred results only; promotion /
        # relocation / upload-split windows and the first window after
        # construction or restore route chained per window (see
        # _fallback_chained). Same contract as the single-device scorer.
        from ..observability.registry import REGISTRY
        from ..ops.device_scorer import resolve_fused_flag

        self.use_fused = self.defer_results and resolve_fused_flag(
            fused_window)
        self.last_dispatch_fused = False
        self.last_fallback_reason: Optional[str] = None
        # Tracing plane: per-window stage seconds (index /
        # uplink-encode / rescore) the job carves into journal span
        # tuples.
        self.stage_clock = StageClock()
        self._fused_shapes = set()
        # The rescale/restore seam and cold start: bucket plans must
        # rebuild from live registry state before any fused static plan
        # is baked, so the first window dispatches chained.
        self._fused_cold = True
        self._fused_dispatches = REGISTRY.gauge(
            "cooc_fused_dispatches_total",
            help="windows dispatched through the fused one-dispatch "
                 "window program")
        self._chained_dispatches = REGISTRY.gauge(
            "cooc_chained_dispatches_total",
            help="windows dispatched through the chained "
                 "scatter+score path")
        self._bucket_compiles = REGISTRY.gauge(
            "cooc_fused_bucket_compilations_total",
            help="distinct fused-window program shapes dispatched "
                 "(per-bucket shape-specialization compile churn)")
        if self.use_fused:
            # Host side of the per-shard device registry mirrors: every
            # registry mutation logs its local rows; each fused dispatch
            # uplinks the dirty rows' (start, len) as a delta sync.
            for ix in self.indexes:
                ix.rows.enable_dirty_log()
            self.reg_start = put_global(
                np.zeros((self.n_shards, self._local_cap), np.int32),
                self.mesh, P(ITEM_AXIS))
            self.reg_len = put_global(
                np.zeros((self.n_shards, self._local_cap), np.int32),
                self.mesh, P(ITEM_AXIS))
        else:
            self.reg_start = self.reg_len = None
        self._build_update()
        # Elastic-state interface (state/store.py): single-process
        # checkpoints are global-key-space blobs, so restore re-buckets
        # onto THIS run's shard count — a checkpoint taken at
        # --num-shards N restores onto M (Flink savepoint semantics).
        from ..state.store import ShardedRescaleStore

        self.store = ShardedRescaleStore(self)

    # -- mesh kernels -----------------------------------------------------

    def _build_update(self) -> None:
        """(Re)build the update program for the current items_cap."""
        items_cap = self.items_cap

        def _update(cnt_loc, dst_loc, row_sums, upd_loc, bounds_loc,
                    rs_part_loc):
            # Per-shard slices arrive as leading-1 blocks.
            cnt, dst = _apply_cells(cnt_loc[0], dst_loc[0], upd_loc[0],
                                    bounds_loc[0])
            # Owned-row partial sums -> psum makes every replica whole:
            # the step's only collective (ICI), replacing the reference's
            # keyed shuffle + re-broadcast round trip.
            part = jnp.zeros((items_cap,), jnp.int32).at[
                rs_part_loc[0, 0]].add(rs_part_loc[0, 1], mode="drop")
            row_sums = row_sums + jax.lax.psum(part, ITEM_AXIS)
            return cnt[None], dst[None], row_sums

        self._update = jax.jit(shard_map(
            _update, mesh=self.mesh,
            in_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None), P(),
                      P(ITEM_AXIS), P(ITEM_AXIS), P(ITEM_AXIS)),
            out_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None), P()),
        ), donate_argnums=donate_argnums(0, 1, 2))

        # Move/grow/compaction programs are built per static width on
        # demand and cached — a fresh jit wrapper per call would miss
        # jax's compile cache every time (cache resets on items_cap
        # growth; they just retrace).
        self._move_fns: Dict[int, object] = {}
        self._grow_fns: Dict[int, object] = {}
        self._compact_fns: Dict[int, object] = {}
        self._promote_fns: Dict[int, object] = {}
        # The fused window program bakes items_cap into its psum scatter
        # (like _update above), so growth invalidates the whole cache.
        self._fused_fns: Dict[tuple, object] = {}

    def _moves_fn(self, L: int):
        fn = self._move_fns.get(L)
        if fn is None:
            def _moves(cnt_loc, dst_loc, mv_loc):
                mv = mv_loc[0]
                old_start, new_start, ln = mv[0], mv[1], mv[2]
                col = jnp.arange(L, dtype=jnp.int32)[None, :]
                valid = col < ln[:, None]
                src_idx = jnp.where(valid, old_start[:, None] + col, 0)
                out_idx = jnp.where(valid, new_start[:, None] + col, _SENT)
                cnt = cnt_loc[0].at[out_idx.ravel()].set(
                    cnt_loc[0][src_idx].ravel(), mode="drop")
                dst = dst_loc[0].at[out_idx.ravel()].set(
                    dst_loc[0][src_idx].ravel(), mode="drop")
                return cnt[None], dst[None]

            fn = jax.jit(shard_map(
                _moves, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None),
                          P(ITEM_AXIS)),
                out_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None)),
            ), donate_argnums=donate_argnums(0, 1))
            self._move_fns[L] = fn
        return fn

    def _rect_pallas(self, R: int) -> bool:
        """Whether bucket width ``R`` routes through the fused kernel
        (ops/pallas_score.rect_routed — the shared routing rule)."""
        from ..ops.pallas_score import rect_routed

        return rect_routed(self.use_pallas, R, self.top_k, self.items_cap)

    def _rect_score(self, cnt, dst, row_sums, meta, observed, R: int):
        """One rectangle on one shard: the fused kernel when routed,
        else the XLA body — identical packed output either way."""
        if self._rect_pallas(R):
            from ..ops.pallas_score import pallas_score_rect

            return pallas_score_rect(cnt, dst, row_sums, meta, observed,
                                     top_k=self.top_k, R=R,
                                     interpret=self._pallas_interpret)
        return _score_rect(cnt, dst, row_sums, meta, observed,
                           self.top_k, R)

    def _score_fn(self, R: int):
        key = (R, self._rect_pallas(R))
        fn = self._score_fns.get(key)
        if fn is None:
            def _score(cnt_loc, dst_loc, row_sums, meta_loc, observed):
                out = self._rect_score(cnt_loc[0], dst_loc[0], row_sums,
                                       meta_loc[0], observed, R)
                return out[None]

            fn = jax.jit(shard_map_maybe_relaxed(
                _score, self.mesh,
                (P(ITEM_AXIS, None), P(ITEM_AXIS, None), P(),
                 P(ITEM_AXIS), P()),
                P(ITEM_AXIS), relaxed=key[1]))
            self._score_fns[key] = fn
        return fn

    @property
    def _local_cap(self) -> int:
        """Per-shard row capacity of the deferred-results table."""
        return -(-self.items_cap // self.n_shards)

    def _score_into_fn(self, R: int):
        """Scoring dispatch that scatters straight into the sharded
        deferred-results table (rows are shard-local: global // D)."""
        key = (R, self._rect_pallas(R))
        fn = self._score_into_fns.get(key)
        if fn is None:
            D = self.n_shards

            def _score_into(tbl_loc, cnt_loc, dst_loc, row_sums, meta_loc,
                            observed):
                out = self._rect_score(cnt_loc[0], dst_loc[0], row_sums,
                                       meta_loc[0], observed, R)
                rowids, lens = meta_loc[0][0], meta_loc[0][2]
                local = jnp.where(lens > 0, rowids // D, _SENT)
                return tbl_loc[0].at[:, local].set(out, mode="drop")[None]

            fn = jax.jit(shard_map_maybe_relaxed(
                _score_into, self.mesh,
                (P(ITEM_AXIS), P(ITEM_AXIS, None),
                 P(ITEM_AXIS, None), P(), P(ITEM_AXIS), P()),
                P(ITEM_AXIS), relaxed=key[1]), donate_argnums=donate_argnums(0))
            self._score_into_fns[key] = fn
        return fn

    def _score_window_into_fn(self, plan: tuple):
        """Fused window scoring into the sharded table: one shard_map
        dispatch runs every plan rectangle on each shard (same static
        plan on all shards — the caller pads every shard's meta to the
        common per-bucket cap)."""
        # Routing is a pure function of R except for the vocab bound,
        # which can flip when items_cap grows past 2^24 — key on it.
        key = (plan, self.use_pallas and self.items_cap <= 1 << 24)
        fn = self._score_window_fns.get(key)
        if fn is None:
            D = self.n_shards

            def _f(tbl_loc, cnt_loc, dst_loc, row_sums, meta_loc, observed):
                tbl = tbl_loc[0]
                for R, S, off in plan:
                    meta = jax.lax.slice(meta_loc[0], (0, off), (3, off + S))
                    out = self._rect_score(cnt_loc[0], dst_loc[0], row_sums,
                                           meta, observed, R)
                    local = jnp.where(meta[2] > 0, meta[0] // D, _SENT)
                    tbl = tbl.at[:, local].set(out, mode="drop")
                return tbl[None]

            fn = jax.jit(shard_map_maybe_relaxed(
                _f, self.mesh,
                (P(ITEM_AXIS), P(ITEM_AXIS, None),
                 P(ITEM_AXIS, None), P(), P(ITEM_AXIS), P()),
                P(ITEM_AXIS), relaxed=key[1]), donate_argnums=donate_argnums(0))
            self._score_window_fns[key] = fn
        return fn

    def _tbl_gather_fn(self, rp: int):
        fn = self._tbl_gather_fns.get(rp)
        if fn is None:
            def _g(tbl_loc, rows_loc):
                return tbl_loc[0][:, rows_loc[0]][None]

            fn = jax.jit(shard_map(
                _g, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS), P(ITEM_AXIS)),
                out_specs=P(ITEM_AXIS),
            ))
            self._tbl_gather_fns[rp] = fn
        return fn

    def _ensure_tbl(self) -> None:
        if self._tbl is None:
            self._tbl = self._put_global(
                np.full((self.n_shards, 2, self._local_cap, self.top_k),
                        -np.inf, np.float32),
                self.mesh, P(ITEM_AXIS))

    def _reset_deferred(self) -> None:
        """Restore path: pre-checkpoint rows already live in the job's
        LatestResults (flushed before every save)."""
        self._tbl = None
        self._tbl_dirty = np.zeros(self.items_cap, dtype=bool)
        self._plan_buckets = {}
        self._plan_buckets_w = {}
        # Rescale seam: every bucket plan above was derived from the OLD
        # topology's per-shard row partition, and the registry rebuild
        # marked every row dirty. The next window dispatches chained
        # (rebuilding the plans from post-restore registry state); the
        # one after re-enters fused with a full all-dirty mirror resync.
        self._fused_cold = True
        if self.use_fused:
            self.reg_start = self._put_global(
                np.zeros((self.n_shards, self._local_cap), np.int32),
                self.mesh, P(ITEM_AXIS))
            self.reg_len = self._put_global(
                np.zeros((self.n_shards, self._local_cap), np.int32),
                self.mesh, P(ITEM_AXIS))

    def _grow_fn(self, n: int):
        fn = self._grow_fns.get(n)
        if fn is None:
            def _grow2(cnt_loc, dst_loc):
                # cnt may be a narrow cell dtype; dst is always int32
                # (jit retraces per input dtype — one cache entry serves
                # the narrow and wide slab pairs).
                zc = jnp.zeros((1, n), cnt_loc.dtype)
                zd = jnp.zeros((1, n), dst_loc.dtype)
                return (zc.at[:, : cnt_loc.shape[1]].set(cnt_loc),
                        zd.at[:, : dst_loc.shape[1]].set(dst_loc))

            fn = jax.jit(shard_map(
                _grow2, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None)),
                out_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None)),
            ))
            self._grow_fns[n] = fn
        return fn

    def _compact_gather_fn(self, g_pad: int):
        fn = self._compact_fns.get(g_pad)
        if fn is None:
            def _cg(cnt_loc, dst_loc, gmap_loc):
                gmap = gmap_loc[0]
                cap = cnt_loc.shape[1]
                return (jnp.zeros((cap,), cnt_loc.dtype).at[: g_pad].set(
                            cnt_loc[0][gmap])[None],
                        jnp.zeros((cap,), dst_loc.dtype).at[: g_pad].set(
                            dst_loc[0][gmap])[None])

            fn = jax.jit(shard_map(
                _cg, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None),
                          P(ITEM_AXIS)),
                out_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None)),
            ), donate_argnums=donate_argnums(0, 1))
            self._compact_fns[g_pad] = fn
        return fn

    # -- capacity ---------------------------------------------------------

    def _ensure_items(self, max_id: int) -> None:
        if max_id >= (1 << 31) - 1:
            raise ValueError("sparse backend supports item ids < 2^31 - 1")
        if max_id < self.items_cap:
            return
        new_cap = int(_pow2ceil(np.asarray([max_id + 1]), 1024)[0])
        grown = np.zeros(new_cap, dtype=np.int64)
        grown[: len(self.row_sums_host)] = self.row_sums_host
        self.row_sums_host = grown
        self.items_cap = new_cap
        if self.wide_rows is not None:
            wr = np.zeros(new_cap, dtype=bool)
            wr[: len(self.wide_rows)] = self.wide_rows
            self.wide_rows = wr
        # The replicated row-sum vector is reconstructible from the host
        # mirror — re-upload instead of growing on device.
        self.row_sums = self._put_global(
            self.row_sums_host.astype(np.int32), self.mesh, P())
        self._build_update()  # items_cap is baked into the psum scatter
        if self.use_fused:
            # Registry mirrors zero-extend (shard-local row ids are
            # stable under items_cap growth: r // D never changes).
            lc = self._local_cap

            def _gr(rs_loc, rl_loc):
                zs = jnp.zeros((1, lc), jnp.int32)
                zl = jnp.zeros((1, lc), jnp.int32)
                return (zs.at[:, : rs_loc.shape[1]].set(rs_loc),
                        zl.at[:, : rl_loc.shape[1]].set(rl_loc))

            self.stage_clock.add("launches")
            self.reg_start, self.reg_len = jax.jit(shard_map(
                _gr, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS), P(ITEM_AXIS)),
                out_specs=(P(ITEM_AXIS), P(ITEM_AXIS)),
            ), donate_argnums=donate_argnums(0, 1))(
                self.reg_start, self.reg_len)
        dirty = np.zeros(new_cap, dtype=bool)
        m = min(new_cap, len(self._tbl_dirty))
        dirty[:m] = self._tbl_dirty[:m]
        self._tbl_dirty = dirty
        if self._tbl is not None:
            old = self._tbl
            lc = self._local_cap

            def _gt(tbl_loc):
                z = jnp.full((1, 2, lc, self.top_k), -jnp.inf, jnp.float32)
                return z.at[:, :, : tbl_loc.shape[2]].set(tbl_loc)

            self.stage_clock.add("launches")
            self._tbl = jax.jit(shard_map(
                _gt, mesh=self.mesh, in_specs=P(ITEM_AXIS),
                out_specs=P(ITEM_AXIS)), donate_argnums=donate_argnums(0))(old)

    def _ensure_heap(self, need_end: int) -> None:
        if need_end <= self.capacity:
            return
        new_cap = self.capacity
        while new_cap < need_end:
            new_cap *= 2
        self.stage_clock.add("launches")
        self.cnt, self.dst = self._grow_fn(new_cap)(self.cnt, self.dst)
        self.capacity = new_cap

    def _ensure_heap_w(self, need_end: int) -> None:
        if need_end <= self.capacity_w:
            return
        new_cap = self.capacity_w
        while new_cap < need_end:
            new_cap *= 2
        self.stage_clock.add("launches")
        self.cnt_w, self.dst_w = self._grow_fn(new_cap)(
            self.cnt_w, self.dst_w)
        self.capacity_w = new_cap

    # -- the window step --------------------------------------------------

    def _local_key(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        return ((src // self.n_shards).astype(np.int64) << 32) | dst

    def process_window(self, ts: int, pairs: PairDeltaBatch):
        self.last_dispatched_rows = 0
        self.last_dispatch_fused = False
        self.last_fallback_reason = None
        self.stage_clock.reset()
        D = self.n_shards
        if len(pairs) == 0:
            if self.defer_results:
                # Nothing in flight; results wait for the final flush.
                return TopKBatch.empty(self.top_k)
            return self.flush()
        clk = self.stage_clock
        # The window's host bookkeeping before any upload: the index
        # stage (as the single-device scorer's).
        with clk.stage("index"):
            if any(ix.needs_compaction(self.compact_min_heap)
                   for ix in self.indexes):
                self._compact_all()
            if (self.indexes_w is not None
                    and any(ix.needs_compaction(self.compact_min_heap)
                            for ix in self.indexes_w)):
                self._compact_all(wide=True)
            delta64 = pairs.delta.astype(np.int64)
            self._ensure_items(int(max(pairs.src.max(), pairs.dst.max())))
            src_d, dst_d, d_val, _ = aggregate_window_coo(
                pairs.src, pairs.dst, delta64, return_key=True)
            d_val32 = narrow_deltas_int32(d_val)

            # Global row sums (watermark ordering first), host-exact.
            rows = distinct_sorted(src_d)
            row_ends = np.searchsorted(src_d, rows, side="right")
            cum = np.concatenate([[0], np.cumsum(d_val)])
            rs_delta = cum[row_ends] - cum[np.searchsorted(src_d, rows)]
            self.row_sums_host[rows] += rs_delta
            if self.row_sums_host[rows].max(initial=0) >= 2**31:
                raise ValueError("row sum exceeds int32 range")
            window_sum = int(delta64.sum())
            self.observed += window_sum
            self.counters.add(ROW_SUM_PROCESS_WINDOW, window_sum)
            # Incremental-checkpoint dirty feed (state/delta.py): global
            # rows touched this window. No-op unless
            # --checkpoint-incremental armed the store's log.
            self.store.note_touched(rows)
            row_owner = (rows % D).astype(np.int64)
            owner_counts = np.bincount(row_owner, minlength=D)

            # Narrow-cell promotion, then the per-slab split: a cell routes
            # by its row's residency, decided BEFORE this window's deltas
            # apply (same ordering as the single-device scorer).
            if self.indexes_w is not None:
                self._promote_rows(rows)
                cell_wide = self.wide_rows[src_d]
            else:
                cell_wide = None

        # Fused routing gate: steady-state all-narrow windows take the
        # one-launch-per-worker program; everything else routes chained
        # per window, bit-identically.
        prealloc = None
        fused_done = False
        if self.use_fused:
            if cell_wide is not None and cell_wide.any():
                self._fallback_chained("promotion")
            elif self._fused_cold:
                self._fallback_chained("plan-rebuild")
            else:
                fused_done, prealloc = self._fused_window(
                    src_d, dst_d, d_val32, rows, rs_delta, row_owner)
        self._fused_cold = False
        if fused_done:
            if self.development_mode:
                self._check_row_sums(rows)
            self.counters.add(RESCORED_ITEMS, len(rows))
            self.last_dispatched_rows = len(rows)
            self.last_dispatch_fused = True
            self._record_dispatch_gauges(fused=True)
            _record_shard_metrics(len(rows), owner_counts)
            self._record_state_gauges()
            # Deferred results only: this window's top-K was scattered
            # into the sharded device table inside the fused program.
            return TopKBatch.empty(self.top_k)

        self._record_dispatch_gauges(fused=False)
        split = cell_wide is not None and cell_wide.any()
        # Slot allocation is index work; an allocation from a fused
        # attempt that bailed must not be applied twice.
        with clk.stage("index"):
            if split:
                prealloc = self._apply_shards(
                    src_d[~cell_wide], dst_d[~cell_wide],
                    d_val32[~cell_wide])
                prealloc_w = self._apply_shards(
                    src_d[cell_wide], dst_d[cell_wide], d_val32[cell_wide],
                    wide=True)
            elif prealloc is None:
                prealloc = self._apply_shards(src_d, dst_d, d_val32)
        with clk.stage("uplink-encode"):
            if split:
                # Wide rows ride the same update program on the wide slab
                # pair; row sums travel once, with the narrow call.
                self._window_update(rows, rs_delta, prealloc)
                self._window_update(rows[:0], rs_delta[:0], prealloc_w,
                                    wide=True)
            else:
                self._window_update(rows, rs_delta, prealloc)

        if self.development_mode:
            self._check_row_sums(rows)

        self.counters.add(RESCORED_ITEMS, len(rows))
        self.last_dispatched_rows = len(rows)
        _record_shard_metrics(len(rows), owner_counts)
        if self.indexes_w is not None and self.wide_rows[rows].any():
            wmask = self.wide_rows[rows]
            chunks = self._dispatch_scoring(rows[~wmask], row_owner[~wmask])
            chunks += self._dispatch_scoring(rows[wmask], row_owner[wmask],
                                             wide=True)
        else:
            chunks = self._dispatch_scoring(rows, row_owner)
        self._record_state_gauges()
        prev, self._pending = self._pending, chunks
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _apply_shards(self, src_d: np.ndarray, dst_d: np.ndarray,
                      d_val32: np.ndarray, wide: bool = False):
        """Allocate this window's cells in every shard's index.

        Per-shard placement: cells by owner, local keys stay sorted
        because src // D is monotone within a fixed residue class.
        Side-effecting (slots are allocated) — a window that allocates
        here and then routes chained must hand the result to
        ``_window_update`` via ``prealloc`` instead of re-applying.
        """
        D = self.n_shards
        indexes = self.indexes_w if wide else self.indexes
        owner = (src_d % D).astype(np.int64)
        plans = []
        sec_new: List[Tuple[np.ndarray, np.ndarray]] = []
        sec_delta: List[Tuple[np.ndarray, np.ndarray]] = []
        mv_blocks: List[Tuple[Optional[np.ndarray], int]] = []
        for d in range(D):
            sel = owner == d
            lk = self._local_key(src_d[sel], dst_d[sel])
            plan = indexes[d].apply(lk)
            plans.append(plan)
            sec_new.append((plan.slots[plan.new_sel],
                            (lk[plan.new_sel] & 0xFFFFFFFF).astype(np.int32)))
            sec_delta.append((plan.slots, d_val32[sel]))
            mv_blocks.append((plan.mv, plan.mv_len))
        return plans, sec_new, sec_delta, mv_blocks

    def _window_update(self, rows: np.ndarray, rs_delta: np.ndarray,
                       prealloc, wide: bool = False) -> None:
        """The chained update step for one slab pair, under the
        allocation ``_apply_shards`` made (``prealloc``): moves (if
        any), then one [D, 2, N_pad] cell-section upload +
        owner-partitioned row-sum parts (psum'd to every replica)."""
        D = self.n_shards
        indexes = self.indexes_w if wide else self.indexes
        _plans, sec_new, sec_delta, mv_blocks = prealloc
        if wide:
            self._ensure_heap_w(max(ix.heap_end for ix in indexes))
            cnt_ref, dst_ref = self.cnt_w, self.dst_w
        else:
            self._ensure_heap(max(ix.heap_end for ix in indexes))
            cnt_ref, dst_ref = self.cnt, self.dst
        lbl = "-wide" if wide else ""

        # Moves: one [D, 3, Mv_pad] block at the widest shard's rectangle.
        mv_pad = max((mv.shape[1] for mv, _ in mv_blocks if mv is not None),
                     default=0)
        mv_len = max((ml for mv, ml in mv_blocks if mv is not None),
                     default=0)
        if mv_pad:
            mv_all = np.zeros((D, 3, mv_pad), dtype=np.int32)
            for d, (mv, _) in enumerate(mv_blocks):
                if mv is not None:
                    mv_all[d, :, : mv.shape[1]] = mv
            LEDGER.up("update-moves-sharded" + lbl, mv_all)
            self.stage_clock.add("launches")
            cnt_ref, dst_ref = self._moves_fn(mv_len)(
                cnt_ref, dst_ref,
                self._put_global(mv_all, self.mesh, P(ITEM_AXIS)))

        n_per = [len(s[0]) + len(dl[0]) for s, dl in zip(sec_new, sec_delta)]
        n_pad = pad_pow4(max(n_per + [1]), minimum=1 << 10)
        upd = np.full((D, 2, n_pad), _SENT, dtype=np.int32)
        upd[:, 1, :] = 0
        bounds = np.zeros((D, 2), dtype=np.int32)
        for d in range(D):
            (ns, nd), (ds_, dv) = sec_new[d], sec_delta[d]
            b0 = len(ns)
            b1 = b0 + len(ds_)
            upd[d, 0, :b0] = ns
            upd[d, 1, :b0] = nd
            upd[d, 0, b0:b1] = ds_
            upd[d, 1, b0:b1] = dv
            bounds[d] = (b0, b1)
        row_owner = (rows % D).astype(np.int64)
        rp = pad_pow4(int(np.bincount(row_owner, minlength=D).max())
                      if len(rows) else 1, minimum=256)
        rs_part = np.full((D, 2, rp), _SENT, dtype=np.int32)
        rs_part[:, 1, :] = 0
        for d in range(D):
            sel = row_owner == d
            k = int(sel.sum())
            rs_part[d, 0, :k] = rows[sel]
            rs_part[d, 1, :k] = rs_delta[sel].astype(np.int32)
        # Wire accounting (the single-device scorer's discipline): the
        # sharded update step never recorded its uploads, leaving
        # fused-vs-sharded wire comparisons blind on one side.
        LEDGER.up("update-sharded" + lbl, upd, bounds, rs_part)
        self.stage_clock.add("launches")
        out = self._update(
            cnt_ref, dst_ref, self.row_sums,
            self._put_global(upd, self.mesh, P(ITEM_AXIS)),
            self._put_global(bounds, self.mesh, P(ITEM_AXIS)),
            self._put_global(rs_part, self.mesh, P(ITEM_AXIS)))
        if wide:
            self.cnt_w, self.dst_w, self.row_sums = out
        else:
            self.cnt, self.dst, self.row_sums = out

    def _promote_rows(self, rows: np.ndarray) -> None:
        """Promote rows whose (already-updated) sum crossed the narrow
        bound: move their cells to the wide sharded side-table before
        this window's deltas touch them — saturation can never be
        observed. One shard_map program moves every shard's cells."""
        thr = self.promote_threshold
        sel = (self.row_sums_host[rows] >= thr) & ~self.wide_rows[rows]
        if not sel.any():
            return
        newly = rows[sel]
        self.wide_rows[newly] = True
        D = self.n_shards
        per: List[Tuple[np.ndarray, np.ndarray]] = []
        m_max = 0
        for d in range(D):
            loc = (newly[newly % D == d] // D).astype(np.int64)
            if len(loc):
                keys, slots = self.indexes[d].row_cells(loc)
                self.indexes[d].free_rows(loc)
            else:
                keys = np.zeros(0, dtype=np.int64)
                slots = np.zeros(0, dtype=np.int32)
            if len(keys):
                order = np.argsort(keys, kind="stable")
                keys = keys[order]
                slots = slots[order].astype(np.int32)
                plan_w = self.indexes_w[d].apply(keys)
                dslots = plan_w.slots
            else:
                dslots = np.zeros(0, dtype=np.int32)
            per.append((slots, dslots))
            m_max = max(m_max, len(keys))
        if m_max == 0:
            return  # first-ever window already past the bound: no cells
        self._ensure_heap_w(max(ix.heap_end for ix in self.indexes_w))
        m_pad = pad_pow2(m_max, minimum=64)
        src = np.zeros((D, m_pad), dtype=np.int32)
        dsts = np.full((D, m_pad), _SENT, dtype=np.int32)
        for d, (s, t) in enumerate(per):
            src[d, : len(s)] = s
            dsts[d, : len(t)] = t
        LEDGER.up("promote-cells-sharded", src, dsts)
        self.stage_clock.add("launches")
        self.cnt_w, self.dst_w = self._promote_fn(m_pad)(
            self.cnt, self.dst, self.cnt_w, self.dst_w,
            self._put_global(src, self.mesh, P(ITEM_AXIS)),
            self._put_global(dsts, self.mesh, P(ITEM_AXIS)))

    def _promote_fn(self, m_pad: int):
        fn = self._promote_fns.get(m_pad)
        if fn is None:
            def _p(cnt_loc, dst_loc, cw_loc, dw_loc, src_loc, dsts_loc):
                # Padding: src 0 (any valid slot — the gather is safe),
                # dsts _SENT (scatter-dropped); widen on the way over.
                vals = cnt_loc[0][src_loc[0]].astype(jnp.int32)
                cw = cw_loc[0].at[dsts_loc[0]].set(vals, mode="drop")
                dw = dw_loc[0].at[dsts_loc[0]].set(
                    dst_loc[0][src_loc[0]], mode="drop")
                return cw[None], dw[None]

            fn = jax.jit(shard_map(
                _p, mesh=self.mesh,
                in_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None),
                          P(ITEM_AXIS, None), P(ITEM_AXIS, None),
                          P(ITEM_AXIS), P(ITEM_AXIS)),
                out_specs=(P(ITEM_AXIS, None), P(ITEM_AXIS, None)),
            ), donate_argnums=donate_argnums(2, 3))
            self._promote_fns[m_pad] = fn
        return fn

    # -- the fused window -------------------------------------------------

    def _fallback_chained(self, reason: str) -> None:
        """Route this window down the chained path, recording why.

        Every reason string used at a call site is a contract: the
        analyzer's fused-fallback-registry rule requires each to appear
        in docs/ARCHITECTURE.md's fallback table and in a tests/
        reference, so no fallback condition can land undocumented or
        untested.
        """
        self.last_fallback_reason = reason

    @property
    def fused_compilations(self) -> int:
        """Distinct fused-program static shapes dispatched so far (=
        XLA compiles of the fused window; the journal's per-window
        ``fused_compiles`` field)."""
        return len(self._fused_shapes)

    def _note_fused_shape(self, key) -> None:
        """Track distinct fused-program static shapes (= XLA compiles):
        the per-bucket shape-specialization churn gauge."""
        if key not in self._fused_shapes:
            self._fused_shapes.add(key)
            self._bucket_compiles.set(len(self._fused_shapes))

    def _record_dispatch_gauges(self, fused: bool) -> None:
        """Process-level fused/chained dispatch pair plus the per-shard
        split (every shard of one worker sees the same launch count by
        SPMD construction; the suffixed series make per-worker dispatch
        accounting greppable next to the per-shard RSS gauges)."""
        from ..observability.registry import REGISTRY

        (self._fused_dispatches if fused
         else self._chained_dispatches).add(1)
        prefix = ("cooc_fused_dispatches_total_shard" if fused
                  else "cooc_chained_dispatches_total_shard")
        hlp = ("fused windows dispatched, as seen by one shard" if fused
               else "chained windows dispatched, as seen by one shard")
        for d in range(self.n_shards):
            REGISTRY.gauge(f"{prefix}{d}", help=hlp).add(1)

    def _bump_plan(self, plan_buckets: dict, bucket: np.ndarray,
                   order: np.ndarray, row_owner: np.ndarray,
                   min_r: int) -> None:
        """Monotone high-water plan bump, shard-uniform: the shard_map
        program is shared, so a bucket's chunk count is driven by the
        fullest shard and every shard pads to it. Shared by the chained
        fixed-mode dispatch and the fused window so plans cannot drift
        when a run alternates between the two paths."""
        D = self.n_shards
        for bb in np.unique(bucket).tolist():
            members = order[bucket[order] == bb]
            R = bucket_r(bb, min_r, self.score_ladder)
            S = fixed_block(R, self.FIXED_BUDGET, self.FIXED_ROW_CAP)
            per_shard_max = int(np.bincount(row_owner[members],
                                            minlength=D).max())
            plan_buckets[bb] = max(plan_buckets.get(bb, 0),
                                   max(1, -(-per_shard_max // S)))

    def _fused_window(self, src_d: np.ndarray, dst_d: np.ndarray,
                      d_val32: np.ndarray, rows: np.ndarray,
                      rs_delta: np.ndarray, row_owner: np.ndarray):
        """Dispatch one steady-state window through the fused one-
        launch-per-worker program. Returns ``(handled, prealloc)``:
        ``(True, None)`` when the window ran fused, ``(False, prealloc)``
        when it must route chained — the allocation already happened, so
        the chained ``_window_update`` receives it instead of
        re-applying (re-applying would double-insert the new cells).

        Not fused-routable (decided here, after allocation): relocation
        windows (``plan.mv`` on any shard — the fused program carries no
        move kernel) and windows under an explicit upload-split request
        (TPU_COOC_UPLOAD_CHUNKS/_CHUNK_KB pins the raw chunked path).
        The caller gates promotion windows and the post-restore plan
        rebuild before allocation.
        """
        from ..ops.device_scorer import split_upload_auto

        D = self.n_shards
        clk = self.stage_clock
        with clk.stage("index"):
            prealloc = self._apply_shards(src_d, dst_d, d_val32)
            _plans, sec_new, sec_delta, mv_blocks = prealloc
            if any(mv is not None for mv, _ in mv_blocks):
                self._fallback_chained("relocation")
                return False, prealloc
            self._ensure_heap(max(ix.heap_end for ix in self.indexes))

        # The update upload: per-shard 3-section update (new | delta |
        # owned row sums; the third section replaces the chained path's
        # separate rs_part upload — the fused body scatters it into the
        # psum partial), then the registry mirror's delta sync and the
        # wire encoding.
        with clk.stage("uplink-encode"):
            owner_counts = np.bincount(row_owner, minlength=D)
            n_per = [len(sec_new[d][0]) + len(sec_delta[d][0])
                     + int(owner_counts[d]) for d in range(D)]
            n_pad = pad_pow4(max(n_per + [1]), minimum=1 << 12)
            upd = np.full((D, 2, n_pad), _SENT, dtype=np.int32)
            upd[:, 1, :] = 0
            bounds = np.zeros((D, 2), dtype=np.int32)
            for d in range(D):
                (ns, nd), (ds_, dv) = sec_new[d], sec_delta[d]
                b0 = len(ns)
                b1 = b0 + len(ds_)
                upd[d, 0, :b0] = ns
                upd[d, 1, :b0] = nd
                upd[d, 0, b0:b1] = ds_
                upd[d, 1, b0:b1] = dv
                sel = row_owner == d
                k = int(sel.sum())
                upd[d, 0, b1: b1 + k] = rows[sel]
                upd[d, 1, b1: b1 + k] = rs_delta[sel].astype(np.int32)
                bounds[d] = (b0, b1)
            if split_upload_auto(upd[0]) is not None:
                self._fallback_chained("upload-split")
                return False, prealloc

            # Registry mirror delta sync, per shard in LOCAL row ids:
            # rows whose host (start, len) changed since the mirror last
            # synced. A restore/rescale marked everything dirty —
            # resync every occupied row. Sentinel-padded to the widest
            # shard's count.
            dirty_l: List[np.ndarray] = []
            n_reg = 0
            for d in range(D):
                dirty, all_dirty = self.indexes[d].rows.drain_dirty()
                if all_dirty:
                    dirty = self.indexes[d].rows.occupied().astype(np.int64)
                dirty_l.append(dirty)
                n_reg = max(n_reg, len(dirty))
            reg_pad = pad_pow2(max(n_reg, 1), minimum=256)
            reg_upd = np.full((D, 3, reg_pad), _SENT, dtype=np.int32)
            for d, dirty in enumerate(dirty_l):
                k = len(dirty)
                if k:
                    r_start, r_len, _c = self.indexes[d].rows.get(dirty)
                    reg_upd[d, 0, :k] = dirty
                    reg_upd[d, 1, :k] = r_start
                    reg_upd[d, 2, :k] = r_len
            if self.wire_packed:
                from ..state.wire import encode_update

                # Ownership-partitioned packed uplink: each shard's
                # sections encode independently; word streams pad to the
                # widest shard's pow2 bucket (+1 guard word for the
                # decode gather).
                enc = [encode_update(upd[d], bounds[d], n_per[d])
                       for d in range(D)]
                wi_w = pad_pow2(max(len(e[0]) for e in enc) + 1,
                                minimum=256)
                wv_w = pad_pow2(max(len(e[1]) for e in enc) + 1,
                                minimum=256)
                wi = np.zeros((D, wi_w), dtype=np.uint32)
                wv = np.zeros((D, wv_w), dtype=np.uint32)
                hdr = np.zeros((D, 5), dtype=np.int32)
                for d, (ei, ev, eh) in enumerate(enc):
                    wi[d, : len(ei)] = ei
                    wv[d, : len(ev)] = ev
                    hdr[d] = eh

        # Monotone shard-uniform scoring plan (the fixed-shape rule via
        # _bump_plan): every (bucket, chunk-rank) ever occupied on any
        # shard dispatches — absent ones as all-padding rectangles — so
        # the static plan only grows and compile count stays bounded.
        # The bump is index work and the rectangles are scoring work, as
        # on the chained path (_dispatch_scoring).
        with clk.stage("index"):
            local = (rows // D).astype(np.int64)
            lens = np.empty(len(rows), dtype=np.int32)
            for d in range(D):
                sel = row_owner == d
                _s, lens[sel], _c = self.indexes[d].rows.get(local[sel])
            min_r = max(16, self.top_k)
            bucket, order = score_buckets(lens, min_r, self.score_ladder)
            self._bump_plan(self._plan_buckets, bucket, order, row_owner,
                            min_r)
        with clk.stage("rescore"):
            b_sorted = bucket[order]
            plan_t = []
            segs: List[np.ndarray] = []
            off = 0
            for bb in sorted(self._plan_buckets):
                R = bucket_r(bb, min_r, self.score_ladder)
                S = fixed_block(R, self.FIXED_BUDGET, self.FIXED_ROW_CAP)
                lo = int(np.searchsorted(b_sorted, bb))
                hi = int(np.searchsorted(b_sorted, bb, side="right"))
                members = order[lo:hi]
                per_shard = [members[row_owner[members] == d]
                             for d in range(D)]
                for c in range(self._plan_buckets[bb]):
                    seg = np.full((D, S), _SENT, dtype=np.int32)
                    for d in range(D):
                        p = per_shard[d][c * S: (c + 1) * S]
                        seg[d, : len(p)] = rows[p]
                    segs.append(seg)
                    plan_t.append((R, S, off, self._rect_pallas(R)))
                    off += S
            rows_all = np.concatenate(segs, axis=1)
            plan_t = tuple(plan_t)
        self._count_scored(plan_t, lens)

        self._ensure_tbl()
        observed = np.float32(self.observed)
        pg = self._put_global
        if self.wire_packed:
            LEDGER.up_encoded("fused-window-packed",
                              upd.nbytes + bounds.nbytes, wi, wv, hdr)
            LEDGER.up("fused-window-meta", reg_upd, rows_all)
            key = ("packed", n_pad, wi_w, wv_w, reg_pad, plan_t)
            self._note_fused_shape(key)
            (self.cnt, self.dst, self.row_sums, self._tbl,
             self.reg_start, self.reg_len) = self._fused_fn(key)(
                self.cnt, self.dst, self.row_sums, self._tbl,
                self.reg_start, self.reg_len,
                pg(wi, self.mesh, P(ITEM_AXIS)),
                pg(wv, self.mesh, P(ITEM_AXIS)),
                pg(hdr, self.mesh, P(ITEM_AXIS)),
                pg(reg_upd, self.mesh, P(ITEM_AXIS)),
                pg(rows_all, self.mesh, P(ITEM_AXIS)), observed)
        else:
            LEDGER.up("fused-window", upd, bounds, reg_upd, rows_all)
            key = ("raw", n_pad, reg_pad, plan_t)
            self._note_fused_shape(key)
            (self.cnt, self.dst, self.row_sums, self._tbl,
             self.reg_start, self.reg_len) = self._fused_fn(key)(
                self.cnt, self.dst, self.row_sums, self._tbl,
                self.reg_start, self.reg_len,
                pg(upd, self.mesh, P(ITEM_AXIS)),
                pg(bounds, self.mesh, P(ITEM_AXIS)),
                pg(reg_upd, self.mesh, P(ITEM_AXIS)),
                pg(rows_all, self.mesh, P(ITEM_AXIS)), observed)
        self._tbl_dirty[rows] = True
        return True, None

    def _fused_fn(self, key: tuple):
        """Build (or fetch) the one-launch fused program for one static
        shape key. The body chains the exact trace bodies the chained
        programs use — ``_apply_cells`` + the psum row-sum merge (the
        ``_update`` body), the mirror scatter, and ``_rect_score`` per
        plan rectangle into the deferred table — so fused and chained
        windows are bit-identical by construction."""
        fn = self._fused_fns.get(key)
        if fn is not None:
            return fn
        D = self.n_shards
        items_cap = self.items_cap
        packed = key[0] == "packed"
        if packed:
            _kind, n_pad, _wi_w, _wv_w, _reg_pad, plan = key
        else:
            _kind, n_pad, _reg_pad, plan = key
        relaxed = any(pl for _R, _S, _off, pl in plan)

        def _body(cnt, dst, row_sums, tbl, reg_start, reg_len, upd,
                  bounds, reg_upd, rows_all, observed):
            cnt, dst = _apply_cells(cnt, dst, upd, bounds)
            # Section 3 (pos >= bounds[1]): this shard's owned rows'
            # window deltas -> partial vector -> psum (the chained
            # _update body's collective, fused in).
            pos = jnp.arange(upd.shape[1], dtype=jnp.int32)
            in_rs = pos >= bounds[1]
            part = jnp.zeros((items_cap,), jnp.int32).at[
                jnp.where(in_rs, upd[0], _SENT)].add(
                jnp.where(in_rs, upd[1], 0), mode="drop")
            row_sums = row_sums + jax.lax.psum(part, ITEM_AXIS)
            reg_start = reg_start.at[reg_upd[0]].set(reg_upd[1],
                                                     mode="drop")
            reg_len = reg_len.at[reg_upd[0]].set(reg_upd[2], mode="drop")
            for R, S, off, _pl in plan:
                g_rows = jax.lax.slice(rows_all, (off,), (off + S,))
                live = g_rows != _SENT
                lr = jnp.where(live, g_rows // D, 0)
                meta = jnp.stack([g_rows, reg_start[lr],
                                  jnp.where(live, reg_len[lr], 0)])
                out = self._rect_score(cnt, dst, row_sums, meta,
                                       observed, R)
                loc = jnp.where(meta[2] > 0, lr, _SENT)
                tbl = tbl.at[:, loc].set(out, mode="drop")
            return cnt, dst, row_sums, tbl, reg_start, reg_len

        if packed:
            from ..state.wire import decode_update

            def _f(cnt_loc, dst_loc, row_sums, tbl_loc, rs_loc, rl_loc,
                   wi_loc, wv_loc, hdr_loc, reg_loc, rows_loc, observed):
                upd, bounds = decode_update(wi_loc[0], wv_loc[0],
                                            hdr_loc[0], n_pad)
                cnt, dst, row_sums, tbl, r_s, r_l = _body(
                    cnt_loc[0], dst_loc[0], row_sums, tbl_loc[0],
                    rs_loc[0], rl_loc[0], upd, bounds, reg_loc[0],
                    rows_loc[0], observed)
                return (cnt[None], dst[None], row_sums, tbl[None],
                        r_s[None], r_l[None])

            wire_specs = (P(ITEM_AXIS), P(ITEM_AXIS), P(ITEM_AXIS))
        else:
            def _f(cnt_loc, dst_loc, row_sums, tbl_loc, rs_loc, rl_loc,
                   upd_loc, bounds_loc, reg_loc, rows_loc, observed):
                cnt, dst, row_sums, tbl, r_s, r_l = _body(
                    cnt_loc[0], dst_loc[0], row_sums, tbl_loc[0],
                    rs_loc[0], rl_loc[0], upd_loc[0], bounds_loc[0],
                    reg_loc[0], rows_loc[0], observed)
                return (cnt[None], dst[None], row_sums, tbl[None],
                        r_s[None], r_l[None])

            wire_specs = (P(ITEM_AXIS), P(ITEM_AXIS))
        in_specs = ((P(ITEM_AXIS, None), P(ITEM_AXIS, None), P(),
                     P(ITEM_AXIS), P(ITEM_AXIS), P(ITEM_AXIS))
                    + wire_specs
                    + (P(ITEM_AXIS), P(ITEM_AXIS), P()))
        out_specs = (P(ITEM_AXIS, None), P(ITEM_AXIS, None), P(),
                     P(ITEM_AXIS), P(ITEM_AXIS), P(ITEM_AXIS))
        fn = jax.jit(shard_map_maybe_relaxed(
            _f, self.mesh, in_specs, out_specs, relaxed=relaxed),
            donate_argnums=donate_argnums(0, 1, 2, 3, 4, 5))
        self._fused_fns[key] = fn
        return fn

    def _record_state_gauges(self) -> None:
        """Per-window state-footprint gauges, per shard AND summed.

        The summed series reuse the single-process sparse backend's
        canonical names (``cooc_host_index_rss_bytes`` /
        ``cooc_slab_live_cells`` / ``cooc_slab_device_bytes``) so
        dashboards read one process-level number regardless of backend;
        the per-shard breakdown rides suffixed series
        (``cooc_host_index_rss_bytes_shard*``) for imbalance debugging.
        """
        from ..observability.registry import REGISTRY

        rss_total = 0
        cells_total = 0
        for d, ix in enumerate(self.indexes):
            rss = ix.nbytes
            cells = len(ix)
            REGISTRY.gauge(
                f"cooc_host_index_rss_bytes_shard{d}",
                help="host-side slab index footprint of one shard"
            ).set(rss)
            REGISTRY.gauge(
                f"cooc_slab_live_cells_shard{d}",
                help="live matrix cells of one shard's slab").set(cells)
            rss_total += rss
            cells_total += cells
        REGISTRY.gauge(
            "cooc_host_index_rss_bytes",
            help="host-side slab index footprint (registry + cell "
                 "index), refreshed per window").set(rss_total)
        REGISTRY.gauge(
            "cooc_slab_live_cells",
            help="live matrix cells across narrow and wide slabs"
        ).set(cells_total)
        REGISTRY.gauge(
            "cooc_slab_device_bytes",
            help="device slab allocation (cnt + dst, narrow and wide)"
        ).set(self.cnt.nbytes + self.dst.nbytes)

    def _dispatch_scoring(self, rows: np.ndarray, row_owner: np.ndarray,
                          wide: bool = False) -> List[Tuple]:
        """Global pow-4 length buckets; within a bucket, rows partition by
        owner into one [D, 3, S_pad] meta block per dispatch. ``wide``
        reads the promoted int32 side-table's slab pair and plan (jit
        retraces per slab dtype, so the trace bodies are shared)."""
        D = self.n_shards
        indexes = self.indexes_w if wide else self.indexes
        plan_buckets = self._plan_buckets_w if wide else self._plan_buckets
        cnt_ref, dst_ref = ((self.cnt_w, self.dst_w) if wide
                            else (self.cnt, self.dst))
        if len(rows) == 0 and not plan_buckets:
            return []
        clk = self.stage_clock
        with clk.stage("index"):
            local = (rows // D).astype(np.int64)
            starts = np.empty(len(rows), dtype=np.int32)
            lens = np.empty(len(rows), dtype=np.int32)
            for d in range(D):
                sel = row_owner == d
                # One registry pass per shard (the _RowField views are
                # the compat shim; this is the per-window hot path).
                starts[sel], lens[sel], _ = indexes[d].rows.get(local[sel])
            min_r = max(16, self.top_k)
            bucket, order = score_buckets(lens, min_r, self.score_ladder)
            if self.fixed_shapes:
                # Monotone plan over every (bucket, chunk-rank) ever
                # occupied on ANY shard (the shard_map program is shared,
                # so the plan must be shard-uniform); absent ones ride as
                # all-padding. Shared with the fused window so the plans
                # cannot drift.
                self._bump_plan(plan_buckets, bucket, order, row_owner,
                                min_r)
        with clk.stage("rescore"):
            b_sorted = bucket[order]
            chunks: List[Tuple] = []
            # (R, S, parts)
            rects: List[Tuple[int, int, List[np.ndarray]]] = []
            pos = 0
            while pos < len(order):
                b = int(b_sorted[pos])
                end = int(np.searchsorted(b_sorted, b, side="right"))
                R = bucket_r(b, min_r, self.score_ladder)
                if self.fixed_shapes:
                    s_block = fixed_block(R, self.FIXED_BUDGET,
                                          self.FIXED_ROW_CAP)
                else:
                    s_block = max(self.SCORE_BUDGET // R, 16)
                members = order[pos:end]
                counts = np.bincount(row_owner[members], minlength=D)
                # Per-shard chunking: split the bucket so no shard
                # exceeds s_block rows per dispatch.
                n_dispatch = max(1, -(-int(counts.max()) // s_block))
                per_shard = [members[row_owner[members] == d]
                             for d in range(D)]
                for i in range(n_dispatch):
                    parts = [p[i * s_block: (i + 1) * s_block]
                             for p in per_shard]
                    if self.fixed_shapes:
                        rects.append((R, s_block, parts))
                        continue
                    s_max = max((len(p) for p in parts), default=0)
                    s_pad = min(pad_pow4(max(s_max, 1), minimum=16),
                                s_block)
                    meta = np.zeros((D, 3, s_pad), dtype=np.int32)
                    for d, p in enumerate(parts):
                        meta[d, 0, : len(p)] = rows[p]
                        meta[d, 1, : len(p)] = starts[p]
                        meta[d, 2, : len(p)] = lens[p]
                    meta_g = self._put_global(meta, self.mesh, P(ITEM_AXIS))
                    self._count_scored(((R, s_pad),),
                                       lens[np.concatenate(parts)])
                    if self.defer_results:
                        self._ensure_tbl()
                        self._tbl = self._score_into_fn(R)(
                            self._tbl, cnt_ref, dst_ref, self.row_sums,
                            meta_g, np.float32(self.observed))
                        continue
                    packed = self._score_fn(R)(
                        cnt_ref, dst_ref, self.row_sums, meta_g,
                        np.float32(self.observed))
                    if hasattr(packed, "copy_to_host_async"):
                        packed.copy_to_host_async()
                    chunks.append(([rows[p] for p in parts], packed))
                pos = end
            if self.fixed_shapes:
                # Top up to the high-water plan (absent (bucket,
                # chunk-rank) entries dispatch as all-padding).
                have = {}
                for R, _S, _p in rects:
                    have[R] = have.get(R, 0) + 1
                for bb, n_chunks in plan_buckets.items():
                    R = bucket_r(bb, min_r, self.score_ladder)
                    S = fixed_block(R, self.FIXED_BUDGET,
                                    self.FIXED_ROW_CAP)
                    for _ in range(n_chunks - have.get(R, 0)):
                        rects.append((R, S, [order[:0]] * D))
            if rects:
                # One packed [D, 3, sum(S)] upload + ONE fused dispatch
                # for the whole window (fixed mode is defer-only,
                # enforced at construction); canonical R order keeps the
                # plan identical regardless of which buckets were empty
                # this window.
                self._count_scored(rects, lens)
                rects.sort(key=lambda t: t[0])
                total = sum(S for _R, S, _p in rects)
                meta_all = np.zeros((D, 3, total), dtype=np.int32)
                plan = []
                off = 0
                for R, S, parts in rects:
                    for d, p in enumerate(parts):
                        n = len(p)
                        meta_all[d, 0, off: off + n] = rows[p]
                        meta_all[d, 1, off: off + n] = starts[p]
                        meta_all[d, 2, off: off + n] = lens[p]
                    plan.append((R, S, off))
                    off += S
                self._ensure_tbl()
                self._tbl = self._score_window_into_fn(tuple(plan))(
                    self._tbl, cnt_ref, dst_ref, self.row_sums,
                    self._put_global(meta_all, self.mesh, P(ITEM_AXIS)),
                    np.float32(self.observed))
        if self.defer_results:
            self._tbl_dirty[rows] = True
        return chunks

    def _count_scored(self, rects, lens: np.ndarray) -> None:
        """One scoring program over ``rects`` (``(R, S, ...)``
        rectangles, each scored on every shard, a fixed plan's
        all-padding ones included) for rows of lengths ``lens``: the
        window's launch and cell counts."""
        clk = self.stage_clock
        clk.add("launches")
        clk.add("score_cells",
                self.n_shards * sum(r[0] * r[1] for r in rects))
        clk.add("live_cells", int(lens.sum()))

    def _compact_all(self, wide: bool = False) -> None:
        indexes = self.indexes_w if wide else self.indexes
        cap = self.capacity_w if wide else self.capacity
        gmaps = [ix.compact() for ix in indexes]
        g_pad = min(pad_pow2(max(len(g) for g in gmaps), minimum=1 << 10),
                    cap)
        gm = np.zeros((self.n_shards, g_pad), dtype=np.int32)
        for d, g in enumerate(gmaps):
            gm[d, : len(g)] = g
        gm_g = self._put_global(gm, self.mesh, P(ITEM_AXIS))
        self.stage_clock.add("launches")
        if wide:
            self.cnt_w, self.dst_w = self._compact_gather_fn(g_pad)(
                self.cnt_w, self.dst_w, gm_g)
        else:
            self.cnt, self.dst = self._compact_gather_fn(g_pad)(
                self.cnt, self.dst, gm_g)

    def _local_slabs(self, arr=None) -> Dict[int, np.ndarray]:
        """Fetch the count slab of every ADDRESSABLE shard (multi-host: the
        shards this process's chips own) keyed by global shard id."""
        arr = self.cnt if arr is None else arr
        return {int(shard.index[0].start or 0): np.asarray(shard.data)[0]
                for shard in arr.addressable_shards}

    def _check_row_sums(self, rows: np.ndarray) -> None:
        local = self._local_slabs()
        local_w = (self._local_slabs(self.cnt_w)
                   if self.indexes_w is not None else None)
        D = self.n_shards
        for r in rows.tolist():
            d, lr = r % D, r // D
            if d not in local:  # owned by another process's chips
                continue
            if local_w is not None and self.wide_rows[r]:
                ix = self.indexes_w[d]
                slab = local_w[d]
            else:
                ix = self.indexes[d]
                slab = local[d]
            s = int(ix.row_start[lr])
            ln = int(ix.row_len[lr])
            actual = int(slab[s: s + ln].sum())
            if actual != int(self.row_sums_host[r]):
                raise AssertionError(
                    f"Item row {int(self.row_sums_host[r])} does not match "
                    f"actual row sum {actual} (item {r})")

    # -- results ----------------------------------------------------------

    def flush(self) -> TopKBatch:
        if self.defer_results:
            # Incremental drain, one sharded gather: each process fetches
            # its addressable shards' dirty rows (multi-host emission
            # contract unchanged — a process emits the rows its chips
            # own; the dirty mask is host-replicated so every process
            # clears the same rows).
            rows = np.flatnonzero(self._tbl_dirty)
            if self._tbl is None or len(rows) == 0:
                return TopKBatch.empty(self.top_k)
            D = self.n_shards
            owner = (rows % D).astype(np.int64)
            counts = np.bincount(owner, minlength=D)
            rp = pad_pow2(int(counts.max()), minimum=16)
            rows_b = np.zeros((D, rp), dtype=np.int32)
            per_shard: List[np.ndarray] = []
            for d in range(D):
                sel = rows[owner == d]
                rows_b[d, : len(sel)] = (sel // D).astype(np.int32)
                per_shard.append(sel)
            packed = self._tbl_gather_fn(rp)(
                self._tbl,
                self._put_global(rows_b, self.mesh, P(ITEM_AXIS)))
            rows_l, idx_l, vals_l = [], [], []
            for shard in packed.addressable_shards:
                d = shard.index[0].start or 0
                n = len(per_shard[d])
                if not n:
                    continue
                host = np.asarray(shard.data)[0]  # [2, rp, K]
                rows_l.append(per_shard[d].astype(np.int32))
                vals_l.append(host[0, :n])
                idx_l.append(unpack_ids(host[1, :n]))
            # Clear marks only after the host copies are in hand (a
            # transient fetch failure must leave the rows drainable).
            self._tbl_dirty[rows] = False
            return TopKBatch.concatenate(rows_l, idx_l, vals_l, self.top_k)
        prev, self._pending = self._pending, None
        return (self._materialize(prev) if prev is not None
                else TopKBatch.empty(self.top_k))

    def _materialize(self, chunks) -> TopKBatch:
        rows_l, idx_l, vals_l = [], [], []
        for per_shard_rows, packed in chunks:
            for shard in packed.addressable_shards:
                d = shard.index[0].start or 0
                rows_d = per_shard_rows[d]
                if not len(rows_d):
                    continue
                host = np.asarray(shard.data)[0]  # [2, S_pad, K]
                rows_l.append(rows_d)
                vals_l.append(host[0, : len(rows_d)])
                idx_l.append(unpack_ids(host[1, : len(rows_d)]))
        return TopKBatch.concatenate(rows_l, idx_l, vals_l, self.top_k)

    # -- checkpoint -------------------------------------------------------

    @property
    def process_suffix(self) -> str:
        """Checkpoint filename suffix: multi-host runs save per process."""
        return f".p{jax.process_index()}" if jax.process_count() > 1 else ""

    @property
    def local_shard_ids(self) -> "List[int]":
        """Global shard ids this process's chips own — the multi-host
        emission/ownership contract, derived from the mesh layout alone
        (no device fetch; the cross-topology restore filters the merged
        top-K table through this before any slab exists)."""
        me = jax.process_index()
        return sorted(d for d, dev in enumerate(
            self.mesh.devices.reshape(-1)) if dev.process_index == me)

    def _global_key(self, d: int, local_key: np.ndarray) -> np.ndarray:
        local_rows = (local_key >> 32).astype(np.int64)
        return ((local_rows * self.n_shards + d) << 32) | (
            local_key & 0xFFFFFFFF)

    def checkpoint_state(self) -> dict:
        """Canonical snapshot via the state store (state/store.py) —
        single-process blobs are global-key-space, shard-count-free."""
        return self.store.checkpoint_state()

    def restore_state(self, st: dict) -> None:
        """Restore via the state store: re-buckets a global blob onto
        THIS run's shard count (N->M rescale-on-restore)."""
        self.store.restore_state(st)

    def _device_checkpoint_state(self) -> dict:
        local = self._local_slabs()
        if jax.process_count() > 1:
            # Per-process snapshot. The *index* (cell keys, placement) is
            # host-replicated — every process has all D of them and saves
            # the identical global key union so a restored process can
            # rebuild every shard's SlabIndex from its own file. The slab
            # *counts* live on chips; each process saves only its
            # addressable shards' (ascending shard id, g_key order).
            views = [ix.keys_and_slots() for ix in self.indexes]
            keys_l = [self._global_key(d, k)
                      for d, (k, _s) in enumerate(views) if len(k)]
            keys = (np.sort(np.concatenate(keys_l)) if keys_l
                    else np.zeros(0, dtype=np.int64))
            shard_ids = sorted(local)
            cnt_l = [local[d][views[d][1]] for d in shard_ids]
            return {
                "mh_rows_key": keys,
                "mh_local_shards": np.asarray(shard_ids, dtype=np.int64),
                "mh_local_cnt": (np.concatenate(cnt_l).astype(np.int64)
                                 if cnt_l else np.zeros(0, np.int64)),
                "row_sums": self.row_sums_host.copy(),
                "observed": np.asarray([self.observed], dtype=np.int64),
            }
        D = self.n_shards
        keys_l, vals_l = [], []
        for d, ix in enumerate(self.indexes):
            k, sl = ix.keys_and_slots()
            if not len(k):
                continue
            keys_l.append(self._global_key(d, k))
            vals_l.append(local[d][sl])
        if self.indexes_w is not None:
            # Wide side-table cells merge into the same global-key
            # blob: the snapshot is dtype-free (int64 counts), and the
            # restoring run re-derives residency from its own threshold.
            local_w = self._local_slabs(self.cnt_w)
            for d, ix in enumerate(self.indexes_w):
                k, sl = ix.keys_and_slots()
                if not len(k):
                    continue
                keys_l.append(self._global_key(d, k))
                vals_l.append(local_w[d][sl])
        if keys_l:
            keys = np.concatenate(keys_l)
            vals = np.concatenate(vals_l)
            order = np.argsort(keys, kind="stable")
            keys, vals = keys[order], vals[order]
            nz = vals != 0
            keys, vals = keys[nz], vals[nz]
        else:
            keys = np.zeros(0, dtype=np.int64)
            vals = np.zeros(0, dtype=np.int64)
        return {
            "rows_key": keys,
            "rows_cnt": vals.astype(np.int64),
            "row_sums": self.row_sums_host.copy(),
            "observed": np.asarray([self.observed], dtype=np.int64),
        }

    def _restore_slabs(self, key: np.ndarray, vals: np.ndarray,
                       wide: bool) -> None:
        """Re-bucket one global-key cell blob onto THIS run's shard count
        and rebuild the matching slab pair (narrow or wide side-table).
        The checkpoint's --num-shards does not constrain the restoring
        mesh (state/store.rebucket_cells)."""
        from ..state.store import rebucket_cells

        D = self.n_shards
        indexes = self.indexes_w if wide else self.indexes
        cnt_dtype = np.int32 if wide else self._cnt_dtype
        need = 0
        per_shard = []
        for d, (lk, cv, dv) in enumerate(rebucket_cells(key, vals, D)):
            slots = indexes[d].rebuild_from_keys(lk)
            per_shard.append((slots, cv, dv))
            need = max(need, indexes[d].heap_end)
        cap = self.capacity_w if wide else self.capacity
        while cap < need:
            cap *= 2
        cnt_host = np.zeros((D, cap), dtype=cnt_dtype)
        dst_host = np.zeros((D, cap), dtype=np.int32)
        for d, (slots, cv, dv) in enumerate(per_shard):
            cnt_host[d, slots] = cv
            dst_host[d, slots] = dv.astype(np.int32)
        cnt_g = self._put_global(cnt_host, self.mesh, P(ITEM_AXIS, None))
        dst_g = self._put_global(dst_host, self.mesh, P(ITEM_AXIS, None))
        if wide:
            self.capacity_w = cap
            self.cnt_w, self.dst_w = cnt_g, dst_g
        else:
            self.capacity = cap
            self.cnt, self.dst = cnt_g, dst_g

    def _device_restore_state(self, st: dict) -> None:
        from ..state.wire import checked_narrow

        if "mh_rows_key" in st:
            return self._restore_multihost(st)
        key = st["rows_key"]
        cnt_vals = st["rows_cnt"].astype(np.int64)
        src = (key >> 32).astype(np.int64)
        dst = (key & 0xFFFFFFFF).astype(np.int64)
        max_id = int(max(src.max(initial=0), dst.max(initial=0)))
        if max_id >= self.items_cap:
            new_cap = int(_pow2ceil(np.asarray([max_id + 1]), 1024)[0])
            self.row_sums_host = np.zeros(new_cap, dtype=np.int64)
            self.items_cap = new_cap
            self._build_update()
        # Row sums land BEFORE the cell split: residency (narrow vs wide
        # side-table) is re-derived from this run's own threshold, so a
        # snapshot round-trips across cell dtypes.
        rs = np.asarray(st["row_sums"], dtype=np.int64)
        if len(rs) > self.items_cap and rs[self.items_cap:].any():
            raise ValueError("checkpoint row sums extend past its cells")
        self.row_sums_host[:] = 0
        m = min(len(rs), self.items_cap)
        self.row_sums_host[:m] = rs[:m]
        self.row_sums = self._put_global(
            self.row_sums_host.astype(np.int32), self.mesh, P())
        if self.indexes_w is not None:
            self.wide_rows = np.zeros(self.items_cap, dtype=bool)
            self.wide_rows[self.row_sums_host >= self.promote_threshold] \
                = True
            wmask = self.wide_rows[src]
            self._restore_slabs(
                key[~wmask],
                checked_narrow(cnt_vals[~wmask], self._cnt_dtype),
                wide=False)
            self._restore_slabs(key[wmask],
                                cnt_vals[wmask].astype(np.int32),
                                wide=True)
        else:
            self._restore_slabs(key, cnt_vals.astype(np.int32),
                                wide=False)
        self.observed = int(st["observed"][0])
        self._pending = None
        self._reset_deferred()

    def _restore_multihost(self, st: dict) -> None:
        """Restore a per-process snapshot (same process layout required).

        The file's key union rebuilds every shard's index (identical in all
        processes by construction); only the locally-owned shards' counts
        are in the file, and only they are uploaded — ``put_global``'s
        callback never asks a process for a remote shard's block. ``dst``
        values are derivable from the keys for every shard.
        """
        if jax.process_count() == 1:
            raise ValueError(
                "checkpoint was written by a multi-host sharded-sparse run "
                "(per-process slab blocks); restore it under the same "
                "process layout")
        if self.indexes_w is not None:
            raise ValueError(
                "multi-host sharded-sparse restore supports --cell-dtype "
                "int32 only (per-process snapshots carry no wide "
                "side-table blocks)")
        local_ids = sorted(self._local_slabs())
        saved_ids = st["mh_local_shards"].tolist()
        if saved_ids != local_ids:
            raise ValueError(
                f"checkpoint owns shards {saved_ids} but this process owns "
                f"{local_ids} — restore under the writing run's layout")
        D = self.n_shards
        key = st["mh_rows_key"]
        src = (key >> 32).astype(np.int64)
        dst = (key & 0xFFFFFFFF).astype(np.int64)
        max_id = int(max(src.max(initial=0), dst.max(initial=0)))
        if max_id >= self.items_cap:
            new_cap = int(_pow2ceil(np.asarray([max_id + 1]), 1024)[0])
            self.row_sums_host = np.zeros(new_cap, dtype=np.int64)
            self.items_cap = new_cap
            self._build_update()
        from ..state.store import rebucket_cells

        need = 0
        slots_by_shard = {}
        for d, (lk, _cv, dv) in enumerate(rebucket_cells(key, None, D)):
            slots_by_shard[d] = (self.indexes[d].rebuild_from_keys(lk), dv)
            need = max(need, self.indexes[d].heap_end)
        while self.capacity < need:
            self.capacity *= 2
        cnt_host = np.zeros((D, self.capacity), dtype=np.int32)
        dst_host = np.zeros((D, self.capacity), dtype=np.int32)
        for d, (slots, dv) in slots_by_shard.items():
            dst_host[d, slots] = dv.astype(np.int32)
        lo = 0
        cnt_local = st["mh_local_cnt"].astype(np.int32)
        for d in local_ids:
            slots, _ = slots_by_shard[d]
            cnt_host[d, slots] = cnt_local[lo: lo + len(slots)]
            lo += len(slots)
        self.cnt = self._put_global(cnt_host, self.mesh, P(ITEM_AXIS, None))
        self.dst = self._put_global(dst_host, self.mesh, P(ITEM_AXIS, None))
        rs = np.asarray(st["row_sums"], dtype=np.int64)
        self.row_sums_host[:] = 0
        m = min(len(rs), self.items_cap)
        self.row_sums_host[:m] = rs[:m]
        self.row_sums = self._put_global(
            self.row_sums_host.astype(np.int32), self.mesh, P())
        self.observed = int(st["observed"][0])
        self._pending = None
        self._reset_deferred()
