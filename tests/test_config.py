"""Config/CLI parsing tests (reference parity: ``Configuration.java:56-199``)."""

import pytest

from tpu_cooccurrence.config import Backend, Config, WindowUnit


def test_defaults_match_reference():
    cfg = Config.from_args(["-i", "x.csv", "-ws", "5"])
    assert cfg.item_cut == 500
    assert cfg.user_cut == 500
    assert cfg.top_k == 10
    assert cfg.window_unit is WindowUnit.MILLISECONDS
    assert cfg.buffer_timeout == 100
    assert cfg.skip_cuts is False
    assert cfg.seed is not None  # clock-derived like System.nanoTime()


def test_hex_seed():
    cfg = Config.from_args(["-i", "x", "-ws", "1", "-s", "0xC0FFEE"])
    assert cfg.seed == 0xC0FFEE
    cfg = Config.from_args(["-i", "x", "-ws", "1", "-s", "12345"])
    assert cfg.seed == 12345


def test_window_units():
    for name, millis in [("SECONDS", 1000), ("minutes", 60_000),
                         ("HOURS", 3_600_000), ("days", 86_400_000)]:
        cfg = Config.from_args(["-i", "x", "-ws", "2", "-wu", name])
        assert cfg.window_millis == 2 * millis


def test_unknown_window_unit_rejected():
    with pytest.raises(SystemExit):
        Config.from_args(["-i", "x", "-ws", "1", "-wu", "FORTNIGHTS"])


def test_input_required():
    with pytest.raises(SystemExit):
        Config.from_args(["-ws", "1"])


def test_window_size_required():
    with pytest.raises(SystemExit):
        Config.from_args(["-i", "x"])


def test_short_flags():
    cfg = Config.from_args(["-i", "x", "-ws", "1", "-ic", "7", "-uc", "9",
                            "-k", "3", "-sc", "-bt", "50"])
    assert cfg.item_cut == 7
    assert cfg.user_cut == 9
    assert cfg.top_k == 3
    assert cfg.skip_cuts is True
    assert cfg.buffer_timeout == 50


def test_top_k_positive_required():
    # Reference: ItemRowRescorerTwoInputStreamOperator.java:52-54.
    with pytest.raises(ValueError):
        Config(input="x", window_size=1, top_k=0)


def test_backend_parse():
    cfg = Config.from_args(["-i", "x", "-ws", "1", "--backend", "sharded",
                            "--num-shards", "4", "--num-items", "100"])
    assert cfg.backend is Backend.SHARDED
    assert cfg.num_shards == 4


def test_score_ladder_and_fixed_score_flags():
    from tpu_cooccurrence.config import Config

    cfg = Config.from_args(["-i", "x.csv", "-ws", "100",
                            "--backend", "sparse",
                            "--score-ladder", "16", "--fixed-score", "on"])
    assert cfg.score_ladder == 16
    assert cfg.fixed_score == "on"
    # Defaults: the registry's ladder (4), fixed-score auto.
    cfg2 = Config.from_args(["-i", "x.csv", "-ws", "100"])
    assert cfg2.score_ladder == 4
    assert cfg2.fixed_score == "auto"


def test_invalid_score_ladder_rejected_at_job_construction():
    import pytest

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    cfg = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                 score_ladder=3)
    with pytest.raises(ValueError, match="power of two"):
        CooccurrenceJob(cfg)


def test_fixed_score_conflicts_rejected():
    import pytest

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    # Explicit on + per-window emission: refuse, don't silently downgrade.
    cfg = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                 fixed_score="on", emit_updates=True)
    with pytest.raises(ValueError, match="emit-updates"):
        CooccurrenceJob(cfg)
    # Explicit on + sharded-sparse + emit-updates: refuse (the fused
    # rectangles are defer-only there too).
    cfg2 = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                  fixed_score="on", num_shards=2, emit_updates=True)
    with pytest.raises(ValueError, match="emit-updates"):
        CooccurrenceJob(cfg2)
    # Bogus value: descriptive error, not a KeyError.
    cfg3 = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                  fixed_score="yes")
    with pytest.raises(ValueError, match="auto|on|off"):
        CooccurrenceJob(cfg3)


def test_fixed_score_rejected_on_non_sparse_backends():
    import pytest

    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob

    cfg = Config(window_size=10, seed=1, backend=Backend.DEVICE,
                 num_items=16, fixed_score="on")
    with pytest.raises(ValueError, match="only applies"):
        CooccurrenceJob(cfg)


def test_pallas_flag_plumbed_to_sharded_backends():
    """--pallas reaches both sharded scorers (the kernels run per shard
    inside their shard_map bodies)."""
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.parallel.sharded import ShardedScorer
    from tpu_cooccurrence.parallel.sharded_sparse import ShardedSparseScorer

    cfg = Config(window_size=10, seed=1, backend=Backend.SHARDED,
                 num_items=64, num_shards=2, pallas="on")
    job = CooccurrenceJob(cfg)
    assert isinstance(job.scorer, ShardedScorer)
    assert job.scorer.use_pallas is True
    # With pallas the vocab pads to a kernel-tile multiple.
    assert job.scorer.num_items % job.scorer.PALLAS_TILE == 0
    sp = Config(window_size=10, seed=1, backend=Backend.SPARSE,
                num_shards=2, pallas="on")
    job2 = CooccurrenceJob(sp)
    assert isinstance(job2.scorer, ShardedSparseScorer)
    assert job2.scorer.use_pallas is True


def test_fixed_score_honored_under_hybrid_alias():
    """--backend hybrid is a full sparse alias: sparse-only flags must be
    accepted (the alias is applied before flag validation)."""
    from tpu_cooccurrence.config import Backend, Config
    from tpu_cooccurrence.job import CooccurrenceJob
    from tpu_cooccurrence.state.sparse_scorer import SparseDeviceScorer

    cfg = Config(window_size=10, seed=1, backend=Backend.HYBRID,
                 fixed_score="off")
    job = CooccurrenceJob(cfg)
    assert isinstance(job.scorer, SparseDeviceScorer)
    assert job.scorer.fixed_shapes is False


# -- gang supervision flags (ISSUE 10) ---------------------------------


def test_gang_workers_validation():
    ok = Config(window_size=10, seed=1, backend=Backend.SHARDED,
                num_shards=2, gang_workers=2)
    assert ok.gang_workers == 2
    with pytest.raises(ValueError, match="gang of one"):
        Config(window_size=10, seed=1, backend=Backend.SHARDED,
               gang_workers=1)
    with pytest.raises(ValueError, match="assigns"):
        Config(window_size=10, seed=1, backend=Backend.SHARDED,
               gang_workers=2, coordinator="h:1", num_processes=2,
               process_id=0)
    with pytest.raises(ValueError, match="process-continuously"):
        Config(window_size=10, seed=1, backend=Backend.SHARDED,
               gang_workers=2, process_continuously=True)
    with pytest.raises(ValueError, match="replica fleet"):
        Config(window_size=10, seed=1, backend=Backend.SHARDED,
               gang_workers=2, serve_port=0)


def test_gang_workers_needs_multihost_backend():
    with pytest.raises(ValueError, match="multi-controller"):
        Config(window_size=10, seed=1, gang_workers=2)  # device backend
    with pytest.raises(ValueError, match="multi-controller"):
        Config(window_size=10, seed=1, backend=Backend.SPARSE,
               gang_workers=2)  # sparse needs num_shards > 1
    Config(window_size=10, seed=1, backend=Backend.SPARSE, num_shards=4,
           gang_workers=2)


def test_gang_timing_flags_validation():
    with pytest.raises(ValueError, match="gang-heartbeat-s"):
        Config(window_size=10, seed=1, gang_heartbeat_s=0)
    with pytest.raises(ValueError, match="gang-stale-after-s"):
        Config(window_size=10, seed=1, gang_stale_after_s=-1)
    with pytest.raises(ValueError, match="collective-timeout-s"):
        Config(window_size=10, seed=1, collective_timeout_s=-1)


def test_gang_workers_with_restart_budget_and_watchdog():
    # The gang reuses --restart-on-failure as its attempt budget and
    # may run the journal-staleness watchdog without a single-process
    # supervisor.
    Config(window_size=10, seed=1, backend=Backend.SHARDED,
           num_shards=2, gang_workers=2, restart_on_failure=3,
           watchdog_stale_after_s=5.0, journal="/tmp/j.jsonl")
    with pytest.raises(ValueError, match="restart-on-failure"):
        Config(window_size=10, seed=1, watchdog_stale_after_s=5.0,
               journal="/tmp/j.jsonl")


def test_multihost_pipeline_now_accepted_partition_sampling_not():
    # ISSUE 10 relaxed the blanket multi-host pipeline rejection: the
    # scorer worker issues collectives serially in window order. The
    # partitioned sampler's sampling-thread allgather still conflicts.
    Config(window_size=10, seed=1, backend=Backend.SHARDED,
           coordinator="h:1", num_processes=2, process_id=0,
           pipeline_depth=2)
    with pytest.raises(ValueError, match="partition-sampling"):
        Config(window_size=10, seed=1, backend=Backend.SHARDED,
               coordinator="h:1", num_processes=2, process_id=0,
               pipeline_depth=2, partition_sampling=True)
