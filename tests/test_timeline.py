"""In-span event timeline, stall watchdog, and the Chrome-trace exporter.

Pins the tentpole contracts of the sub-span observability layer:

- :class:`EventTimeline` semantics — bounded buffer with a drop marker,
  allocation-free disabled path (shared NULL singleton), drain-and-
  restart clock, the process-wide active-timeline hook used by
  components with no manager reference (host staging);
- :class:`StallWatchdog` — silent on fast waits, fires (log + journal
  ``stall`` line + metrics + timeline event) on a wait that outlives
  ``watchdog_timeout_s``, never interrupts the wait itself; the armed-
  waits table serves the SIGUSR1 on-demand dump;
- ``scripts/shuffle_trace.py`` — journals (including multi-host pairs
  and stall lines) convert to valid Chrome Trace Event Format JSON:
  B/E pairs become X slices, counters become C samples, unmatched B
  events degrade to instants instead of corrupting the track;
- the E2E acceptance paths: a streaming-regime read on the CPU mesh
  (small ``max_rounds_in_flight``) emits a span whose ``events`` carry
  per-chunk dispatch/fold and queue-block records and whose trace
  export is Perfetto-loadable; a deliberately blocked chunk produces a
  journaled ``stall`` entry while a healthy read produces none.
"""

import importlib.util
import io
import json
import time
from pathlib import Path

import numpy as np
import pytest

from sparkrdma_tpu import MeshRuntime, ShuffleConf
from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import modulo_partitioner
from sparkrdma_tpu.obs import (EventTimeline, ExchangeJournal,
                               MetricsRegistry, NULL_TIMELINE, StallWatchdog,
                               dump_armed, read_entries, read_journal,
                               record_active, set_active)

REPO = Path(__file__).resolve().parent.parent

# stdlib-only CLI, imported in-process (same pattern as shuffle_report
# in test_obs.py) so these stay in the fast tier
_spec = importlib.util.spec_from_file_location(
    "shuffle_trace", REPO / "scripts" / "shuffle_trace.py")
shuffle_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(shuffle_trace)


class TestEventTimeline:
    def test_event_shapes_and_order(self):
        tl = EventTimeline()
        tl.begin("phase", rounds=3)
        tl.event("tick", chunk=1)
        tl.counter("occ", 2)
        tl.end("phase")
        events = tl.drain()
        assert [e["ph"] for e in events] == ["B", "i", "C", "E"]
        assert events[0]["name"] == "phase" and events[0]["rounds"] == 3
        assert events[2]["v"] == 2
        # monotone offsets relative to the previous drain
        ts = [e["t"] for e in events]
        assert ts == sorted(ts) and all(t >= 0 for t in ts)

    def test_bounded_buffer_with_drop_marker(self):
        tl = EventTimeline(capacity=4)
        for i in range(10):
            tl.event("e", i=i)
        assert len(tl) == 4 and tl.dropped == 6
        events = tl.drain()
        assert len(events) == 5   # 4 kept + the drop marker
        assert events[-1]["name"] == "timeline:dropped"
        assert events[-1]["n"] == 6
        # the drop counter resets with the drain
        assert tl.dropped == 0 and tl.drain() == []

    def test_drain_restarts_clock(self):
        tl = EventTimeline()
        tl.event("a")
        time.sleep(0.02)
        tl.drain()
        tl.event("b")
        (b,) = tl.drain()
        assert b["t"] < 0.02, "post-drain events are relative to the drain"

    def test_disabled_is_noop(self):
        tl = EventTimeline(enabled=False)
        tl.event("x")
        tl.begin("y")
        tl.counter("z", 1)
        assert len(tl) == 0 and tl.drain() == []

    def test_null_singleton(self):
        NULL_TIMELINE.event("x")
        NULL_TIMELINE.counter("y", 1)
        NULL_TIMELINE.begin("z")
        assert len(NULL_TIMELINE) == 0
        assert NULL_TIMELINE.drain() == []
        assert not NULL_TIMELINE.enabled

    def test_rejects_bad_capacity(self):
        with pytest.raises(ValueError):
            EventTimeline(capacity=0)

    def test_reset_discards(self):
        tl = EventTimeline()
        tl.event("x")
        tl.reset()
        assert tl.drain() == []

    def test_active_timeline_hook(self):
        tl = EventTimeline()
        prev = set_active(tl)
        try:
            record_active("staging:spill", bytes=512)
            (e,) = tl.drain()
            assert e["name"] == "staging:spill" and e["bytes"] == 512
        finally:
            set_active(prev)
        # no active timeline: silently dropped
        prev = set_active(None)
        try:
            record_active("ignored")
        finally:
            set_active(prev)


class TestStallWatchdog:
    def test_disabled_by_default(self):
        wd = StallWatchdog()   # timeout 0 = off
        assert not wd.enabled
        with wd.armed("wait"):
            pass
        assert wd.stall_count == 0

    def test_fast_wait_is_silent(self):
        journal = ExchangeJournal(io.StringIO())
        wd = StallWatchdog(timeout_s=5.0, journal=journal)
        with wd.armed("queue:block", chunk=1):
            pass
        time.sleep(0.05)
        assert wd.stall_count == 0 and journal.emitted == 0

    def test_stall_fires_and_journals(self):
        buf = io.StringIO()
        journal = ExchangeJournal(buf)
        reg = MetricsRegistry()
        tl = EventTimeline()
        wd = StallWatchdog(timeout_s=0.05, journal=journal, metrics=reg,
                           timeline=tl)
        wd.set_context(span_id=11, shuffle_id=3)
        with wd.armed("queue:block", chunk=2, queue=4, pool_high_water=6):
            deadline = time.time() + 5.0
            while wd.stall_count == 0 and time.time() < deadline:
                time.sleep(0.01)
        assert wd.stall_count == 1
        stall = wd.last_stall
        assert stall["kind"] == "stall"
        assert stall["span_id"] == 11 and stall["shuffle_id"] == 3
        assert stall["chunk"] == 2 and stall["queue"] == 4
        assert stall["pool_high_water"] == 6
        assert stall["elapsed_s"] >= 0.05
        assert reg.counter("watchdog.stalls").value == 1
        # journal got the line while the wait was still in progress
        (line,) = buf.getvalue().splitlines()
        assert json.loads(line)["kind"] == "stall"
        # and the in-span timeline carries the event
        names = [e["name"] for e in tl.drain()]
        assert "stall" in names

    def test_fires_once_per_armed_wait(self):
        wd = StallWatchdog(timeout_s=0.03)
        with wd.armed("w"):
            time.sleep(0.2)
        assert wd.stall_count == 1

    def test_dump_armed_sees_in_flight_state(self):
        wd = StallWatchdog(timeout_s=60.0)
        wd.set_context(span_id=1)
        lines = []
        with wd.armed("queue:block", chunk=7):
            snap = dump_armed(sink=lines.append)
        mine = [r for r in snap if r.get("chunk") == 7]
        assert mine and mine[0]["desc"] == "queue:block"
        assert any("queue:block" in ln for ln in lines)
        # after the wait exits the table is clean again
        assert all(r.get("chunk") != 7 for r in dump_armed(sink=lambda s: None))


class TestTraceExporter:
    def _span(self, **kw):
        base = dict(span_id=1, shuffle_id=0, transport="xla", rounds=2,
                    dispatches=5, records=100, record_bytes=16,
                    plan_s=0.01, exchange_s=0.05, sort_s=0.02,
                    per_peer_records=[25, 25, 25, 25], ts=1000.0,
                    process_index=0, host_count=1, schema=2,
                    events=[
                        {"t": 0.01, "ph": "B", "name": "chunk", "chunk": 0},
                        {"t": 0.02, "ph": "i", "name": "chunk:dispatch",
                         "chunk": 0},
                        {"t": 0.03, "ph": "C", "name": "pool.outstanding",
                         "v": 2},
                        {"t": 0.04, "ph": "E", "name": "chunk"},
                    ])
        base.update(kw)
        return base

    def test_build_trace_structure(self):
        trace = shuffle_trace.build_trace({"j": [self._span()]})
        assert set(trace) == {"traceEvents", "displayTimeUnit"}
        evs = trace["traceEvents"]
        # must be JSON-serializable with integer microsecond timestamps
        json.dumps(trace)
        assert all(isinstance(e.get("ts", 0), int) for e in evs)
        phases = [e for e in evs if e["ph"] == "X" and e["tid"] == 1]
        assert {e["name"] for e in phases} == {"plan", "exchange", "sort"}
        # B/E pair folded into one X slice of ~30ms
        chunk = [e for e in evs if e["ph"] == "X" and e["name"] == "chunk"]
        assert len(chunk) == 1
        assert chunk[0]["dur"] == pytest.approx(0.03 * 1e6, abs=2)
        counters = [e for e in evs if e["ph"] == "C"]
        assert counters and counters[0]["args"]["value"] == 2
        insts = [e for e in evs if e["ph"] == "i"]
        assert any(e["name"] == "chunk:dispatch" for e in insts)

    def test_unmatched_begin_degrades_to_instant(self):
        # an error path can leave a B with no E (e.g. plan() raising);
        # the exporter must render it as an instant, not corrupt a track
        span = self._span(events=[{"t": 0.01, "ph": "B",
                                   "name": "stream:prep"}])
        evs = [e for e in shuffle_trace.build_trace(
                   {"j": [span]})["traceEvents"] if e.get("tid") == 2]
        assert not any(e["ph"] == "X" for e in evs)
        assert any(e["ph"] == "i" and e["name"] == "stream:prep"
                   for e in evs)

    def test_multi_host_tracks_and_stalls(self):
        j0 = [self._span(process_index=0)]
        j1 = [self._span(span_id=2, process_index=1),
              {"kind": "stall", "shuffle_id": 0, "span_id": 2,
               "process_index": 1, "ts": 1000.5, "elapsed_s": 1.0}]
        evs = shuffle_trace.build_trace({"a": j0, "b": j1})["traceEvents"]
        assert {e["pid"] for e in evs} == {0, 1}
        stall = [e for e in evs if e["name"] == "STALL"]
        assert len(stall) == 1 and stall[0]["pid"] == 1
        assert stall[0]["s"] == "p"
        # per-host process_name metadata for the Perfetto track labels
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"host 0", "host 1"}

    def test_cli_writes_valid_trace(self, tmp_path):
        journal = tmp_path / "j.jsonl"
        with open(journal, "w") as f:
            f.write(json.dumps(self._span()) + "\n")
        out = tmp_path / "trace.json"
        assert shuffle_trace.main([str(journal), "-o", str(out)]) == 0
        trace = json.loads(out.read_text())
        assert isinstance(trace["traceEvents"], list)
        assert trace["traceEvents"], "trace must not be empty"


def _streaming_conf(sink, **kw):
    """Small slots + tight in-flight budget force the streaming regime
    (plan.num_rounds > max_rounds_in_flight) on the 8-device CPU mesh."""
    return ShuffleConf(slot_records=8, max_rounds_in_flight=1,
                       queue_depth=2, metrics_sink=sink, **kw)


def _run_streaming_read(conf, rng, shuffle_id=80, block_hook=None):
    manager = ShuffleManager(MeshRuntime(conf), conf)
    try:
        mesh = manager.runtime.num_partitions
        handle = manager.register_shuffle(shuffle_id, mesh,
                                          modulo_partitioner(mesh))
        x = rng.integers(1, 2**32, size=(mesh * 96, 4), dtype=np.uint32)
        manager.get_writer(handle).write(
            manager.runtime.shard_records(x)).stop(True)
        if block_hook is not None:
            manager._exchange.block_hook = block_hook
        out, totals = manager.get_reader(handle).read()
        assert int(np.asarray(totals).sum()) == x.shape[0]
        return manager
    finally:
        manager.stop()


class TestStreamingTimelineE2E:
    def test_streaming_span_carries_chunk_events(self, tmp_path, rng):
        sink = tmp_path / "stream.jsonl"
        manager = _run_streaming_read(_streaming_conf(str(sink)), rng)
        (span,) = read_journal(str(sink))
        assert span.schema == 14
        assert span.rounds > 1, "must actually be the streaming regime"
        names = [e["name"] for e in span.events]
        assert "stream:prep" in names
        assert names.count("chunk:dispatch") == span.rounds
        assert names.count("chunk:fold") == span.rounds
        assert "queue:block" in names, "queue_depth=2 must make chunks wait"
        assert "pool:acquire" in names
        # every event is self-describing and drain-relative
        for e in span.events:
            assert set(e) >= {"t", "ph", "name"}
            assert e["t"] >= 0
        # identity fields for the multi-host merge
        assert span.process_index == 0 and span.host_count == 1

    def test_streaming_trace_export_is_valid(self, tmp_path, rng):
        sink = tmp_path / "stream.jsonl"
        _run_streaming_read(_streaming_conf(str(sink)), rng, shuffle_id=81)
        out = tmp_path / "trace.json"
        assert shuffle_trace.main([str(sink), "-o", str(out)]) == 0
        trace = json.loads(out.read_text())
        evs = trace["traceEvents"]
        x_names = {e["name"] for e in evs if e["ph"] == "X"}
        # phase slices AND folded timeline regions appear as durations
        assert {"plan", "exchange"} <= x_names
        assert "chunk" in x_names
        assert any(e["ph"] == "C" and e["name"] == "pool.outstanding"
                   for e in evs)

    def test_fused_regime_also_journals_events(self, tmp_path, rng):
        """A within-budget (fused) read still gets plan + fused-dispatch
        events — the timeline is regime-independent."""
        sink = tmp_path / "fused.jsonl"
        conf = ShuffleConf(slot_records=64, metrics_sink=str(sink))
        _run_streaming_read(conf, rng, shuffle_id=82)
        (span,) = read_journal(str(sink))
        names = [e["name"] for e in span.events]
        assert "shuffle:plan" in names
        assert "shuffle:exchange/dispatch" in names
        assert "chunk:dispatch" not in names


class TestWatchdogE2E:
    def test_blocked_chunk_journals_stall(self, tmp_path, rng):
        """A chunk wait artificially held past watchdog_timeout_s must
        produce a journaled stall entry carrying the in-flight state —
        written while the read is still blocked, then the read finishes
        normally (flight recorder, not circuit breaker)."""
        sink = tmp_path / "stall.jsonl"
        conf = _streaming_conf(str(sink), watchdog_timeout_s=0.05)
        manager = _run_streaming_read(conf, rng, shuffle_id=83,
                                      block_hook=lambda j: time.sleep(0.4))
        stalls = [e for e in read_entries(str(sink))
                  if e.get("kind") == "stall"]
        assert stalls, "the held wait must be reported"
        stall = stalls[0]
        assert stall["shuffle_id"] == 83
        assert stall["desc"] == "queue:block"
        assert stall["elapsed_s"] >= conf.watchdog_timeout_s
        assert "chunk" in stall and "queue" in stall
        assert "pool_high_water" in stall
        assert manager.watchdog.stall_count >= 1
        # the read still completed and emitted its span after the stall
        spans = read_journal(str(sink))
        assert len(spans) == 1 and spans[0].shuffle_id == 83
        assert "stall" in [e["name"] for e in spans[0].events]

    def test_healthy_read_is_stall_free(self, tmp_path, rng):
        sink = tmp_path / "healthy.jsonl"
        conf = _streaming_conf(str(sink), watchdog_timeout_s=30.0)
        manager = _run_streaming_read(conf, rng, shuffle_id=84)
        assert manager.watchdog.stall_count == 0
        assert all(e.get("kind") != "stall"
                   for e in read_entries(str(sink)))

    def test_watchdog_disabled_by_default(self, tmp_path, rng):
        sink = tmp_path / "off.jsonl"
        manager = _run_streaming_read(_streaming_conf(str(sink)), rng,
                                      shuffle_id=85)
        assert not manager.watchdog.enabled

    def test_negative_timeout_rejected(self):
        with pytest.raises(ValueError):
            ShuffleConf(watchdog_timeout_s=-1.0)
