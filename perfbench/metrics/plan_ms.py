"""Median over jobs of the host time of sampling plus ``writer.stop``
(the plan and size exchange), from the benchmark's own spans."""

import statistics


def read(run):
    if not run.jobs:
        return None
    return 1e3 * statistics.median(j.plan_s for j in run.jobs)
