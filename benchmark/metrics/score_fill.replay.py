"""score_fill.replay: the cells of the rows the dense scorer scored
(rows x catalog width) over the cells its scoring programs were shaped
for (padded rows x catalog width), over the windows of the measured
window, in % (program counter)."""

from benchmark import stages


def read(run):
    return stages.score_fill(run)
