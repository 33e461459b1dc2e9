"""Host<->device syncs of the stream executor per decode block dispatched
in the window (engine counters host_syncs / blocks_dispatched)."""


def read(run):
    c = run.summary["counters"]
    blocks = c.get("blocks_dispatched", 0)
    return c.get("host_syncs", 0) / blocks if blocks else None
