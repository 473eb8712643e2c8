"""Device time of the plan's count pass per traced job, per chip: the
ops the program names ``sr_count`` (the partitioner and the histogram of
partition ids), clipped to the traced window. None where no such op
ran."""

from perfbench import phases


def read(run):
    return phases.scope_ms_per_job(run, "sr_count")
