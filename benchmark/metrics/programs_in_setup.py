"""programs_in_setup: programs compiled or loaded from the persistent
cache before the measured window opens -- the distinct shapes the cell's
traffic needs, counted by the harness's jax.monitoring listener (program
counter). Each costs set-up time in every run."""


def read(run):
    return run.setup_programs
