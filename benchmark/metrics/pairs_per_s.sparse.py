"""pairs_per_s.sparse: pairs_per_s of the sparse cell, a metric of its
own so that each cell's spread sets its own bound: observed
co-occurrences of every window fired in the measured window, over its
wall time; the window ends when all their results are on the device
(host clock)."""


def read(run):
    return run.window["pairs"] / run.window["wall_s"]
