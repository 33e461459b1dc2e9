"""Seconds to boot the verified ReplayChannel: fetch, HMAC-verify,
preload and warm both executables (host clock around Workload.channel)."""


def read(run):
    return run.boot_s
