"""The measured window: a closed loop of jobs, and its arithmetic.

Closed loop, concurrency 1: the next job starts when the previous one
ends, as one Spark application runs its shuffle stages. The loop starts
jobs until ``seconds`` have passed since the first started, then lets
the last one finish; the window runs from the first job's start to the
last job's end. Every job in the window counts: the rate is all bytes
over all the time, and the tail is the tail of all jobs.
"""

from __future__ import annotations

import math
import time
from typing import Callable, List, Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the ``ceil(q * n)``-th smallest value."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    return s[max(1, math.ceil(q * len(s))) - 1]


def closed_loop(run_job: Callable[[int], object], seconds: float,
                max_jobs: Optional[int] = None,
                clock: Callable[[], float] = time.perf_counter) -> List:
    """Run ``run_job(i)`` back to back; each returns a record with
    ``start`` and ``end`` on ``clock``. Stops starting jobs once
    ``seconds`` have passed since the first job's start, or after
    ``max_jobs`` jobs."""
    records: List = []
    first = clock()
    while True:
        records.append(run_job(len(records)))
        if clock() - first >= seconds:
            break
        if max_jobs is not None and len(records) >= max_jobs:
            break
    return records


class Window:
    def __init__(self, starts: Sequence[float], ends: Sequence[float],
                 bytes_per_job: float, chips: int):
        if not starts or len(starts) != len(ends):
            raise ValueError("a window needs at least one whole job")
        self.durations = [e - s for s, e in zip(starts, ends)]
        self.seconds = ends[-1] - starts[0]
        self.jobs = len(starts)
        self.bytes = bytes_per_job * self.jobs
        self.chips = chips

    @property
    def gbps_per_chip(self) -> float:
        return self.bytes / self.seconds / self.chips / 1e9

    @property
    def p95_s(self) -> float:
        return nearest_rank(self.durations, 0.95)
