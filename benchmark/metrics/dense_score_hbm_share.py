"""dense_score_hbm_share: the dense scoring kernel's share of the chip's
HBM bandwidth, in %. The least time its bytes need -- rows rescored in
the window x catalog width x the count cell's bytes, plus the row sums,
over HBM bandwidth -- over the summed device time of the kernel's events
in the trace. Not a full roofline: the LLR is elementwise work on the
vector unit, whose peak is not published, so no compute leg bounds it."""

from benchmark.trace import reduce, roofline

#: Device op names of the scoring kernel in the trace: the custom call
#: ``%pallas_score_topk.<n>`` (ops/pallas_score.py).
KERNEL = ("%pallas_score_topk",)
CELL_BYTES = {"int16": 2, "int32": 4}


def read(run):
    if run.trace is None:
        return None
    seconds = reduce.op_seconds(run.trace, KERNEL)
    rows = run.window["rows"]
    if seconds <= 0 or rows <= 0:
        return None
    job = run.spec.config["job"]
    nbytes = roofline.dense_score_bytes(rows, job["num_items"],
                                        CELL_BYTES[job["count_dtype"]])
    return roofline.hbm_share(nbytes, seconds, run.devices[0].device_kind)
