"""Barrett contexts computed per compiled bucket call over the window
(`ModArithService` ctx_misses over the `bucket_seconds` count).  The
seeded stream fixes how many keys a window's requests miss, so more a
call means more cold keys shared one precompute and one call."""


def read(run):
    if run.ctx_misses is None or not run.calls:
        return None
    return run.ctx_misses / run.calls
