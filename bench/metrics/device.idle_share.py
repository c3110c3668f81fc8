"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device-op intervals inside the calls) / (first
traced call's start to the last one's end)."""


def read(run):
    t = run.trace
    if t is None or not t.ops or t.window_s() <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())
