"""uplink_bytes_per_pair.replay: host-to-device bytes the scorers
recorded in the TransferLedger during the measured window, per observed
co-occurrence (program counter)."""


def read(run):
    pairs = run.window["pairs"]
    return run.window["h2d_bytes"] / pairs if pairs else None
