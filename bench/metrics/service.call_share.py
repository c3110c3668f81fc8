"""Share of the window spent inside compiled bucket calls, in %: the
`bucket_seconds` sum (the compiled call and the blocking copy of its
result) over the window.  The rest is int <-> limb conversion, padding
and the frontend."""


def read(run):
    return 100.0 * run.call_seconds / run.window_s if run.calls else None
