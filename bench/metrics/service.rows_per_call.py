"""True (unpadded) rows per compiled bucket call, over the window
(`ServiceMetrics` true rows over the `bucket_seconds` count)."""


def read(run):
    return run.call_rows / run.calls if run.calls else None
