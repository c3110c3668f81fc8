"""Requests the frontend coalesced per batch cycle, over the window
(`FrontendMetrics.coalesced`: sum over count)."""


def read(run):
    return run.coalesced / run.cycles if run.cycles else None
