"""Pallas kernel device time over device busy time inside the traced
calls, in %.  The rest is XLA glue: fold, unfold, pad and copies."""


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = t.busy_s()
    kern = t.kernel_time_s()
    return 100.0 * kern / busy if busy > 0 and kern > 0 else None
