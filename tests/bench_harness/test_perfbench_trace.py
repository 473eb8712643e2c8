"""The trace reduction: interval arithmetic, op classes, gap labels, and
the whole reduction on a small trace recorded on a v5e chip."""

import os

import pytest

from perfbench import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench", "fixtures")


def test_union_and_gaps():
    busy = tr.union([(5, 10), (0, 3), (8, 12), (12, 14), (20, 25)])
    assert busy == [(0, 3), (5, 14), (20, 25)]
    assert tr.gaps(busy, 0, 30) == [(3, 5), (14, 20), (25, 30)]
    assert tr.gaps(busy, 6, 22) == [(14, 20)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


@pytest.mark.parametrize("text,cls", [
    ("%sort.16 = (u32[16]{0:T(1024)}, s32[16]{0:T(1024)S(1)}) "
     "sort(u32[16]{0:T(1024)} %bitcast.8), dimensions={0}", "sort"),
    ("%fusion.21 = u32[8,23]{0,1:T(8,128)} fusion(u32[16,23]{0,1:T(8,128)} "
     "%sort.3, s32[8] %all-to-all.2), kind=kCustom", "other"),
    ("%all-to-all.1 = u32[4,1,25,64]{3,2,1,0} all-to-all(u32[4,1,25,64] %x)",
     "a2a"),
    ("all-to-all-start", "a2a"),
    ("%custom-call.2 = u32[8] custom-call(u32[8] %p), "
     "custom_call_target=\"tpu_custom_call\" _ring_exchange_kernel", "a2a"),
    ("sort.12", "sort"),
    ("copy.4", "other"),
])
def test_classify(text, cls):
    assert tr.classify(text) == cls


def test_short_name_drops_layouts():
    assert tr.short_name(
        "%fusion.21 = u32[1048576,23]{0,1:T(8,128)} fusion(u32[16,23] %a)"
    ) == "fusion %fusion.21 u32[1048576,23]"


def test_label_innermost_span():
    spans = [("job", 0, 100), ("job:read", 10, 50),
             ("shuffle:exchange#s3", 20, 40)]
    assert tr.label(spans, 30) == "shuffle:exchange#s3"
    assert tr.label(spans, 45) == "job:read"
    assert tr.label(spans, 70) == "job"
    assert tr.label(spans, 150) == "host:between_jobs"


def test_reduce_recorded_chip_trace():
    """Six warm jobs of terasort_100b_1chip, traced on a v5e (PR 22)."""
    import jax

    s = tr.reduce(jax.profiler.ProfileData.from_file(os.path.join(
        FIXTURES, "terasort_100b_1chip.xplane.pb")))
    assert (s.chips, s.jobs) == (1, 6)
    assert s.window_s == pytest.approx(3.486063774)
    assert s.busy_s == pytest.approx(3.456735174)
    assert s.class_s == pytest.approx({"sort": 0.311545531,
                                       "other": 3.145189643})
    assert s.top_ops[0][0].startswith("sort:sort %sort.16")
    assert s.idle_gaps[0][0] == "job:sample"
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_pct < 100
    assert s.class_s.get("sort", 0) > 0
    assert sum(s.class_s.values()) >= s.busy_s * (1 - 1e-9)
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert all(v > 0 for _, v in s.idle_gaps)
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s + 1e-6
