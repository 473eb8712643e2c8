"""Device time by program phase: the XSpace wire reader against
``ProfileData`` on every recorded fixture, the phase of a name stack,
and the new readers on traces recorded on a v5e chip."""

import os
import shutil
from types import SimpleNamespace

import pytest

from perfbench import phases, registry
from perfbench import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "perfbench", "fixtures")
#: six jobs of terasort_100b_1chip before the program named its phases
UNSCOPED = "terasort_100b_1chip.xplane.pb"
#: three jobs of each cell with the phases named
SCOPED = {"terasort_100b_1chip": "terasort_100b_1chip.scoped.xplane.pb",
          "repartition256_1chip": "repartition256_1chip.scoped.xplane.pb"}
ALL = [UNSCOPED] + sorted(SCOPED.values())


def _path(name):
    return os.path.join(FIXTURES, name)


def _profile(name):
    import jax

    return jax.profiler.ProfileData.from_file(_path(name))


def _run(name):
    """A traced run's view as the harness hands it to a reader, its
    phases already parsed."""
    return SimpleNamespace(trace=tr.reduce(_profile(name)),
                           phases=phases.load(_path(name)))


def _read(metric, run):
    return registry.metric_reader(ROOT, metric).read(run)


@pytest.mark.parametrize("name", ALL)
def test_wire_reader_matches_profile_data(name):
    pd = _profile(name)
    t = phases.load(_path(name))
    want = [[(o.start, o.end) for o in ops] for ops in tr.device_ops(pd)]
    assert [[(o.start, o.end) for o in ops] for ops in t.chips] == want
    assert t.spans == tr.host_spans(pd)
    s = tr.reduce(pd)
    assert (t.jobs, t.window_s) == (s.jobs, s.window_s)


def test_unscoped_fixture_reads_as_trace_reduce_does():
    """The first recorded trace predates the scopes: every op is
    ``unscoped``, and the phases add up to ``trace_reduce``'s op
    classes."""
    run = _run(UNSCOPED)
    by_phase = phases.phase_s(run.phases)
    assert set(by_phase) == {phases.UNSCOPED}
    assert by_phase[phases.UNSCOPED] == pytest.approx(
        sum(run.trace.class_s.values()))
    for metric in ("gather_ms_per_job", "bucket_ms_per_job",
                   "count_ms_per_job"):
        assert _read(metric, run) is None, metric
    # the parent's plan span still labels its idle time
    assert _read("plan_idle_ms_per_job", run) == pytest.approx(1.219207,
                                                               rel=1e-6)


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(local_step)/sr_sort_gather/jit(_take)/gather:", "sr_sort_gather"),
    ("jit(local_step)/shard_map/sr_bucket/sr_combine/sort", "sr_combine"),
    ("jit(local_step)/sr_exchange/while/body/sr_slots/dynamic_slice",
     "sr_slots"),
    ("sr_sample/axis_index", "sr_sample"),
    ("jit(local_step)/sort:", phases.UNSCOPED),
    ("jit(sr_step)/sr_/add", phases.UNSCOPED),
    ("", phases.UNSCOPED),
])
def test_phase_of_is_innermost_scope(tf_op, phase):
    assert phases.phase_of(tf_op) == phase


@pytest.mark.parametrize("cell,scopes,floor", [
    ("terasort_100b_1chip", ("sr_sort_keys", "sr_sort_gather"), 0.90),
    ("repartition256_1chip", ("sr_bucket", "sr_count"), 0.90),
])
def test_scoped_fixture_names_the_busy_time(cell, scopes, floor):
    run = _run(SCOPED[cell])
    by_phase = phases.phase_s(run.phases)
    busy = sum(by_phase.values())
    assert by_phase.get(phases.UNSCOPED, 0.0) < 0.05 * busy
    assert sum(by_phase.get(s, 0.0) for s in scopes) >= floor * busy


@pytest.mark.parametrize("cell,metric,value", [
    ("terasort_100b_1chip", "gather_ms_per_job", 519.225762),
    ("terasort_100b_1chip", "plan_idle_ms_per_job", 1.210849),
    ("repartition256_1chip", "bucket_ms_per_job", 759.554268),
    ("repartition256_1chip", "count_ms_per_job", 413.726294),
    ("repartition256_1chip", "plan_idle_ms_per_job", 1.992029),
])
def test_reader_on_scoped_fixture(cell, metric, value):
    """Three warm jobs of each cell, traced on a v5e."""
    assert _read(metric, _run(SCOPED[cell])) == pytest.approx(value,
                                                               rel=1e-6)


@pytest.mark.parametrize("name", ALL)
def test_host_idle_partitions_chip0_idle(name):
    """The pieces of every idle gap add up to chip 0's idle time, and
    each carries the innermost span open in it."""
    run = _run(name)
    t = run.phases
    pieces = phases.host_idle(t)
    busy = tr.union([(max(o.start, t.lo), min(o.end, t.hi))
                     for o in t.chips[0] if o.end > t.lo and o.start < t.hi])
    assert sum(ns for _, ns in pieces) == \
        (t.hi - t.lo) - sum(e - s for s, e in busy)
    assert all(ns > 0 for _, ns in pieces)
    names = {sp[0] for sp in t.spans} | {"host:between_jobs"}
    assert {label for label, _ in pieces} <= names


@pytest.mark.parametrize("cell,metric", [
    ("terasort_100b_1chip", "bucket_ms_per_job"),
    ("repartition256_1chip", "gather_ms_per_job"),
])
def test_reader_of_a_bypassed_phase_is_none(cell, metric):
    assert _read(metric, _run(SCOPED[cell])) is None


def test_scope_time_is_per_job_and_clipped_to_the_window():
    run = _run(SCOPED["terasort_100b_1chip"])
    t = run.phases
    total = sum(min(o.end, t.hi) - max(o.start, t.lo)
                for o in t.chips[0]
                if o.phase == "sr_sort_gather" and o.end > t.lo
                and o.start < t.hi)
    assert _read("gather_ms_per_job", run) == pytest.approx(
        total / 1e6 / t.jobs)


def test_untraced_run_reads_nothing():
    run = SimpleNamespace(trace=None)
    for metric in ("gather_ms_per_job", "plan_idle_ms_per_job"):
        assert _read(metric, run) is None


def test_reader_finds_its_own_runs_trace(tmp_path, monkeypatch):
    """The newest trace under ``perfbench/out/trace`` is read, and only
    if it holds the window the harness reduced."""
    name = SCOPED["terasort_100b_1chip"]
    d = tmp_path / "trace" / "terasort_100b_1chip" / "plugins" / "x"
    d.mkdir(parents=True)
    shutil.copyfile(_path(name), d / "host.xplane.pb")
    monkeypatch.setattr(phases, "TRACE_ROOT", str(tmp_path / "trace"))
    want = _read("gather_ms_per_job", _run(name))
    run = SimpleNamespace(trace=tr.reduce(_profile(name)))
    assert _read("gather_ms_per_job", run) == want
    assert run.phases is not None           # parsed once, kept on the run
    other = SimpleNamespace(trace=tr.reduce(_profile(UNSCOPED)))
    assert _read("gather_ms_per_job", other) is None


def test_wire_reader_refuses_a_trace_without_jobs():
    with pytest.raises(ValueError, match="job"):
        phases.parse(b"")
