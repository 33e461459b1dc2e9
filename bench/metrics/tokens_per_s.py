"""Output tokens committed to the host in the window, per window second."""


def read(run):
    return run.summary["tokens_per_s"]
