"""95th percentile of the gaps between successive deliveries of tokens to
one request (drains that grew its committed tokens), over the gaps that
end in the window."""


def read(run):
    return run.summary["itl_p95_ms"]
