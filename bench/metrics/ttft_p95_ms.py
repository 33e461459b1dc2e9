"""95th percentile of time to first token over the requests due in the
window: from the scheduled arrival (open loop) or the submission (closed
loop) to the frontier drain that commits the first token.  A request that
never gets one counts at the grace limit past the window."""


def read(run):
    return run.summary["ttft_p95_ms"]
