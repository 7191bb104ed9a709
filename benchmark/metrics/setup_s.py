"""setup_s: seconds from process start to the opening of the measured
window -- imports, stream generation, job and device state, warm-up with
its compiles or cache loads (host clock)."""


def read(run):
    return run.setup_s
