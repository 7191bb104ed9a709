"""compiles_in_window.replay: programs compiled or loaded from the
persistent cache inside the measured window, counted by the harness's
jax.monitoring listener (program counter)."""


def read(run):
    return run.window["compiles"]
