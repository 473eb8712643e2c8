"""Window arithmetic: the rate is all bytes over the whole window, the
tail is the nearest-rank 95th percentile of every job, and one stalled
job moves both."""

import itertools

import pytest

from perfbench.window import Window, closed_loop, nearest_rank


@pytest.mark.parametrize("values,q,want", [
    ([3.0], 0.95, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 0.5, 2.0),
    (list(range(1, 21)), 0.95, 19),
    (list(range(1, 101)), 0.95, 95),
    (list(range(100, 0, -1)), 0.95, 95),
])
def test_nearest_rank(values, q, want):
    assert nearest_rank(values, q) == want


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _jobs(durations, gap=0.0):
    """A closed loop over jobs of the given durations on a fake clock."""
    clock = FakeClock()
    it = iter(durations)

    def run_job(i):
        clock.t += gap
        start = clock.t
        clock.t += next(it)
        return type("R", (), {"start": start, "end": clock.t})()

    return clock, run_job


@pytest.mark.parametrize("stall", [0.0, 5.0])
def test_stalled_jobs_move_tail_and_rate(stall):
    # two stalls in about 30 jobs reach the 95th percentile; one would
    # move only the maximum
    durations = [1.0] * 10 + [1.0 + stall] * 2 + [1.0] * 100
    clock, run_job = _jobs(durations)
    recs = closed_loop(run_job, seconds=40.0, clock=clock)
    assert len(recs) == 40 - 2 * int(stall)   # started until 40 s passed
    w = Window([r.start for r in recs], [r.end for r in recs],
               bytes_per_job=1e9, chips=1)
    assert w.seconds == pytest.approx(sum(r.end - r.start for r in recs))
    assert w.gbps_per_chip == pytest.approx(w.jobs / w.seconds)
    if stall:
        assert w.p95_s == 1.0 + stall
        assert w.gbps_per_chip < 1.0
    else:
        assert w.p95_s == 1.0 and w.gbps_per_chip == pytest.approx(1.0)


def test_one_stall_in_thirty_moves_only_the_maximum():
    clock, run_job = _jobs([1.0] * 10 + [6.0] + [1.0] * 100)
    recs = closed_loop(run_job, seconds=40.0, clock=clock)
    w = Window([r.start for r in recs], [r.end for r in recs], 1e9, 1)
    assert w.p95_s == 1.0 and max(w.durations) == 6.0


def test_last_job_finishes_past_the_seconds():
    clock, run_job = _jobs([3.0] * 10)
    recs = closed_loop(run_job, seconds=4.0, clock=clock)
    assert len(recs) == 2 and recs[-1].end == 6.0


def test_gaps_between_jobs_count_in_the_window():
    clock, run_job = _jobs(itertools.repeat(1.0), gap=1.0)
    recs = closed_loop(run_job, seconds=10.0, clock=clock)
    w = Window([r.start for r in recs], [r.end for r in recs], 2e9, 2)
    assert w.gbps_per_chip == pytest.approx(
        w.jobs * 2e9 / w.seconds / 2 / 1e9)
    assert w.gbps_per_chip < 1.0


def test_max_jobs():
    clock, run_job = _jobs(itertools.repeat(1.0))
    assert len(closed_loop(run_job, float("inf"), max_jobs=6,
                           clock=clock)) == 6
