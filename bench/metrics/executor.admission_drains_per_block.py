"""Frontier drains that admission forced with decode blocks in flight,
per decode block dispatched in the window (engine counters
admission_drains / blocks_dispatched).  A program that does not count
them reads nothing."""


def read(run):
    c = run.summary["counters"]
    blocks = c.get("blocks_dispatched", 0)
    if "admission_drains" not in c or not blocks:
        return None
    return c["admission_drains"] / blocks
