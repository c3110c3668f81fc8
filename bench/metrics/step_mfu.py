"""The whole served path's share of the chip's int8 peak, in %: the
work of the rows that the traced calls served (roofline.py, fixed per
configuration) over the time from the first traced call's start to the
last one's end, times the peak.  Only calls that lie whole in the trace
count, so the time is theirs too.  It bounds the kernels' roofline
share from below whatever runs the work."""


def read(run):
    t = run.trace
    if t is None or run.peaks is None or not t.spans:
        return None
    window = t.window_s()
    if window <= 0:
        return None
    ops = t.span_rows() * run.op.ops_per_row(run.cfg)
    return 100.0 * ops / (window * run.peaks["int8_ops_per_s"])
