"""Pallas kernel events per service call in the traced part of the
window: kernel events inside the benchmark's call spans over the number
of those spans."""


def read(run):
    t = run.trace
    if t is None or not t.spans:
        return None
    n = t.kernel_count()
    return n / len(t.spans) if n else None
