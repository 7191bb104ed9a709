"""sample_share.replay: the job's own sample clock (StepTimer
sample_seconds: windowing, cuts, sampling, pair expansion on the host)
summed over the windows of the measured window, as a share of its wall
time (program span)."""


def read(run):
    return 100.0 * run.window["sample_s"] / run.window["wall_s"]
