"""uplink_bytes_per_pair.sparse: as uplink_bytes_per_pair.replay, in
the sparse cell (it moves pairs_per_s.sparse there): host-to-device
bytes the scorers recorded in the TransferLedger during the measured window, per observed
co-occurrence (program counter)."""


def read(run):
    pairs = run.window["pairs"]
    return run.window["h2d_bytes"] / pairs if pairs else None
