"""index_share.sparse: the program's own ``index`` stage (the scorer's
host bookkeeping before any upload: pair aggregation, the row-sum
mirror, the slab index and the fixed-plan bump) summed over the windows
of the measured window, as a share of its wall time (program span)."""

from benchmark import stages


def read(run):
    return stages.stage_share(run, "index")
