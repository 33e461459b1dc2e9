"""Device time of one run of the replayed prefill executable (trace)."""
from bench.harness.readings import prefill_ms


def read(run):
    return prefill_ms(run)
