"""Roofline share of the fused decode block: the least time the chip
could take for the work the slots needed (max of operations over peak
FLOP/s and bytes over peak bandwidth, per block), over the replayed decode
executable's device time in the traced window."""
from bench.harness.readings import decode_least, executable


def read(run):
    r = executable(run, "decode")
    if r is None or not run.blocks:
        return None
    least, _bound = decode_least(run)
    return 100.0 * least / r["seconds"] if least else None
