"""pairs_per_s: observed co-occurrences of every window fired in the
measured window, over its wall time; the window ends when all their
results are on the device (host clock)."""


def read(run):
    return run.window["pairs"] / run.window["wall_s"]
