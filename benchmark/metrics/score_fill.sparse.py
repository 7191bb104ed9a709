"""score_fill.sparse: the cells of the rows the sparse scorer scored
(the sum of their lengths) over the cells its scoring programs were
shaped for (the sum of R x S over the rectangles dispatched, the fixed
plan's all-padding ones included), over the windows of the measured
window, in % (program counter)."""

from benchmark import stages


def read(run):
    return stages.score_fill(run)
