"""shard_balance.sharded: how evenly the sharded dense scorer's
rescoring falls on its shards: the rows scored, summed over the shards
(``shard_rows``), over the shard count times the rows of the fullest
shard (``shard_rows_max``), each summed over the windows of the measured
window, in % (program counter). 100 is balanced: the fullest shard,
which every window's row-sum ``psum`` waits for, scores no more than its
share. A program that does not count them reads nothing."""

from benchmark import stages


def read(run):
    rows, most = (stages.count(run, "shard_rows"),
                  stages.count(run, "shard_rows_max"))
    shards = run.spec.config["job"].get("num_shards")
    if rows is None or not most or not shards:
        return None
    return 100.0 * rows / (shards * most)
