"""scatter_fill.chained: the live lanes of the dense chained update's
COO chunks (``scatter_live``) over the lanes its ``SCATTER_STEP``
scatter steps were shaped for (``scatter_lanes``), over the measured
window, in % (program counter). A program that does not count them, or
a window in which no chained update ran, reads nothing."""

from benchmark import stages


def read(run):
    live, lanes = (stages.count(run, "scatter_live"),
                   stages.count(run, "scatter_lanes"))
    return None if not lanes or live is None else 100.0 * live / lanes
