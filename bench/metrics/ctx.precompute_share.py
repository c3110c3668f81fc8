"""Share of the device's busy time inside the traced calls spent in the
Barrett context precompute, in %: the device time of the ops inside the
calls whose name holds `refine_` (the Newton refinement kernels, which
in a modular call run only to build contexts) over busy time inside
the calls.  Reads 0.0 where the traced calls computed no context."""

PRECOMPUTE_OPS = "refine_"


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = t.busy_s()
    if busy <= 0:
        return None
    secs = sum(e - s for name, s, e, _ in t.in_spans()
               if PRECOMPUTE_OPS in name) / 1e9
    return 100.0 * secs / busy
