"""The stall watch says where the host sat while a job ran long, and
how long the garbage collector ran inside each job."""

import gc
import time

from perfbench.stall import StallWatch


def _slow_step():
    time.sleep(0.3)


def test_long_job_is_sampled_where_it_sits():
    watch = StallWatch(every_s=0.01, keep=3)
    try:
        watch.begin(0, 0.05)
        _slow_step()
        watch.end()
        watch.begin(1, 10.0)   # never passes its limit
        time.sleep(0.05)
        watch.end()
    finally:
        watch.close()
    got = watch.samples[0]
    assert len(got) == 3
    assert all(late >= 0.05 for late, _ in got)
    inner, outer = got[0][1].split(" < ")[:2]
    assert inner.endswith(":_slow_step")
    assert outer.endswith(":test_long_job_is_sampled_where_it_sits")
    assert 1 not in watch.samples


def test_no_limit_no_samples_and_gc_time_is_summed():
    watch = StallWatch(every_s=0.01)
    try:
        watch.begin(4, None)
        gc.collect()
        time.sleep(0.05)
        watch.end()
        gc.collect()   # outside any job: counted nowhere
    finally:
        watch.close()
    assert watch.samples == {}
    assert list(watch.gc_s) == [4] and watch.gc_s[4] > 0
