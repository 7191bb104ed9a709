"""fused_share.baskets: windows of the measured window that the dense
scorer served with its one-program basket window (``fused_windows``
summed over the window's records) over the windows fired, in % (program
counter). A program that does not count fused windows reads nothing."""

from benchmark import stages


def read(run):
    fused, windows = stages.count(run, "fused_windows"), run.window["windows"]
    return None if fused is None or not windows else 100.0 * fused / windows
