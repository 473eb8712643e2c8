"""Nearest-rank 95th percentile of the wall time of every job in the
window (from its first call to its output ready and unregistered)."""


def read(run):
    return run.window.p95_s
