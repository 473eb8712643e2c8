"""1 - (union of device-op intervals / traced window), mean over chips."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_pct
