"""Barrett context cache misses over lookups in the window, in %
(`ModArithService` ctx_hits and ctx_misses)."""


def read(run):
    if run.ctx_hits is None:
        return None
    total = run.ctx_hits + run.ctx_misses
    return 100.0 * run.ctx_misses / total if total else None
