"""The program's own per-window stage seconds and counts over the
measured window: the last ``run.window["windows"]`` records of the job's
``StepTimer`` ring (``WindowStats.stages``, the journal's span carve,
and ``WindowStats.counts``, the scorer's per-window counts).

A program whose records carry no such field reads as nothing (None),
so a reader of a metric the program does not yet report stays silent.
"""

from __future__ import annotations

from typing import Optional


def _records(run) -> list:
    n = int(run.window["windows"])
    ring = list(run.job.step_timer.windows)
    return ring[-n:] if n > 0 else []


def _total(run, field: str, key: str) -> Optional[float]:
    """``key`` summed over the window's records that carry it in
    ``field``; None when none does."""
    vals = [getattr(w, field, None) or {} for w in _records(run)]
    vals = [v[key] for v in vals if key in v]
    return sum(vals) if vals else None


def stage_seconds(run, stage: str) -> Optional[float]:
    """Seconds of the core span ``stage`` over the measured window."""
    return _total(run, "stages", stage)


def count(run, name: str) -> Optional[int]:
    """The per-window count ``name`` summed over the measured window."""
    return _total(run, "counts", name)


def stage_share(run, stage: str) -> Optional[float]:
    """``stage`` seconds as a share of the window's wall time, in %."""
    secs = stage_seconds(run, stage)
    return None if secs is None else 100.0 * secs / run.window["wall_s"]


def score_fill(run) -> Optional[float]:
    """Cells of the rows actually scored over the cells the scoring
    programs were shaped for, in %."""
    live, shaped = count(run, "live_cells"), count(run, "score_cells")
    return None if not shaped or live is None else 100.0 * live / shaped


def launches_per_window(run) -> Optional[float]:
    """Device programs launched on the window path, per window."""
    launches = count(run, "launches")
    windows = run.window["windows"]
    return None if launches is None or not windows else launches / windows
