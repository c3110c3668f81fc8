"""Share of the roofline, in %, of the Pallas kernels in the traced
calls: the least time the chip needs for the rows those calls served
(work and bytes per row fixed per configuration in roofline.py, work
against the int8 peak, bytes against HBM bandwidth) over the kernels'
device time inside the calls' spans."""

import roofline


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    secs = t.kernel_time_s()
    rows = t.span_rows()
    if secs <= 0 or rows == 0:
        return None
    share, _ = roofline.roofline(rows * run.op.ops_per_row(run.cfg),
                                 rows * run.op.bytes_per_row(run.cfg),
                                 secs, run.peaks)
    return share
