"""collective_share.sharded: the device time of the collectives
(all-reduce, all-gather, collective-permute, reduce-scatter, all-to-all)
over the busy time of all the cell's chips, in % (device trace): the
summed ``op_seconds`` of the ops of those opcodes over ``busy_s``
(averaged over the chips) times the chips."""

import re

#: A collective's HLO opcode, sync or async, in an op's trace text. The
#: instruction's name does not tell: JAX names it after its primitive
#: (``%psum.7 = s32[151552]{0} all-reduce(%x), ...``).
COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|collective-permute|"
                        r"reduce-scatter|all-to-all)(-start|-done)?\(")


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = t["busy_s"] * len(run.devices)
    if busy <= 0:
        return None
    secs = sum(s for op, s in t["op_seconds"].items()
               if COLLECTIVE.search(op.partition(" = ")[2]))
    return 100.0 * secs / busy
