"""hbm_peak_gb: peak_bytes_in_use of the fullest chip after the window,
in GB (1e9 bytes), as the device's runtime reports it."""


def read(run):
    peak = run.peak_bytes
    return None if peak is None else peak / 1e9
