"""compiles_in_window.sparse: as compiles_in_window.replay, in the sparse cell
(it moves pairs_per_s.sparse there): programs compiled or loaded from the
persistent cache inside the measured window, counted by the harness's
jax.monitoring listener (program counter)."""


def read(run):
    return run.window["compiles"]
