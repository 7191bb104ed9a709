"""launches_per_window.sparse: device programs the sparse scorer
launched on the window path (update, scoring, results table, compaction
and capacity steps), per window of the measured window (program
counter)."""

from benchmark import stages


def read(run):
    return stages.launches_per_window(run)
