"""Device time of the reduce-side sort's payload gather per traced job,
per chip: the ops the program names ``sr_sort_gather`` (the payload
words placed by the sorted index), clipped to the traced window. None
where no such op ran."""

from perfbench import phases


def read(run):
    return phases.scope_ms_per_job(run, "sr_sort_gather")
