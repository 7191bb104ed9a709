"""expand_fill.baskets: the live lanes of the fused windows' basket
expansion (two per pair: ``expand_live``) over the lanes it was shaped
for (``expand_lanes``), over the measured window, in % (program
counter)."""

from benchmark import stages


def read(run):
    live, lanes = (stages.count(run, "expand_live"),
                   stages.count(run, "expand_lanes"))
    return None if not lanes or live is None else 100.0 * live / lanes
