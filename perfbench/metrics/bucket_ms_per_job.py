"""Device time of map-side bucketing per traced job, per chip: the ops
the program names ``sr_bucket`` (partition ids, the sort by partition,
the counts; slot fill and compaction have phases of their own), clipped
to the traced window. None where no such op ran."""

from perfbench import phases


def read(run):
    return phases.scope_ms_per_job(run, "sr_bucket")
