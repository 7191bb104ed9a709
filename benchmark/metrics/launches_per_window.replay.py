"""launches_per_window.replay: device programs the dense scorer
launched on the window path (COO updates, scoring, results-table
scatters, capacity growth), per window of the measured window (program
counter)."""

from benchmark import stages


def read(run):
    return stages.launches_per_window(run)
