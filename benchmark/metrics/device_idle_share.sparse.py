"""device_idle_share.sparse: as device_idle_share.replay, in the sparse cell
(it moves pairs_per_s.sparse there): 1 - (union of device-op intervals / the
traced window), in %, from the profiler trace (device trace)."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
