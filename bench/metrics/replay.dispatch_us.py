"""Median host time for a decode_block call on the replay channel to
return (the dispatch of one fused block), in microseconds."""
import statistics


def read(run):
    return 1e6 * statistics.median(run.dispatch_s) if run.dispatch_s \
        else None
