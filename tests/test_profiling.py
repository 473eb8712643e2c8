"""``profiling.phase`` names one host phase once: a TraceAnnotation for
the profiler's trace and, when a timeline is enabled, its begin/end
pair under the same name for the journal."""

import pytest

import jax

from sparkrdma_tpu.obs.timeline import NULL_TIMELINE, EventTimeline
from sparkrdma_tpu.utils import profiling


@pytest.fixture
def annotations(monkeypatch):
    """Names of the TraceAnnotations opened, in order, with each one's
    state at exit."""
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append([self.name, "open"])
            return self

        def __exit__(self, *exc):
            opened[-1][1] = "closed"

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return opened


def test_phase_emits_annotation_and_timeline_pair(annotations):
    tl = EventTimeline()
    with profiling.phase("shuffle:plan", tl, rounds=2) as at_end:
        assert annotations == [["shuffle:plan", "open"]]
        at_end["split"] = 1
    assert annotations == [["shuffle:plan", "closed"]]
    events = tl.drain()
    assert [(e["ph"], e["name"]) for e in events] == [
        ("B", "shuffle:plan"), ("E", "shuffle:plan")]
    assert events[0]["rounds"] == 2 and events[1]["split"] == 1


@pytest.mark.parametrize("timeline", [None, NULL_TIMELINE,
                                      EventTimeline(enabled=False)])
def test_phase_without_timeline_still_annotates(annotations, timeline):
    with profiling.phase("shuffle:exchange/dispatch", timeline) as at_end:
        at_end["rounds"] = 1
    assert annotations == [["shuffle:exchange/dispatch", "closed"]]


def test_phase_closes_both_records_when_the_phase_raises(annotations):
    tl = EventTimeline()
    with pytest.raises(RuntimeError):
        with profiling.phase("shuffle:exchange/dispatch", tl):
            raise RuntimeError("dispatch failed")
    assert annotations == [["shuffle:exchange/dispatch", "closed"]]
    assert [e["ph"] for e in tl.drain()] == ["B", "E"]


def test_annotate_span_carries_the_journal_span_id(annotations):
    with profiling.annotate_span("shuffle:exchange", 42):
        pass
    with profiling.annotate_span("shuffle:exchange"):
        pass
    assert [a[0] for a in annotations] == ["shuffle:exchange#s42",
                                           "shuffle:exchange"]
