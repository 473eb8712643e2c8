"""Observability stack: metrics registry, exchange journal, report CLI.

Covers the contracts the obs package promises:

- registry semantics (counters / gauges / bounded histograms) and the
  allocation-free disabled path (shared null singletons);
- journal schema round-trip (ExchangeSpan <-> dict <-> JSON line) and
  the lazy-sink rule (no file until a span is actually emitted);
- scripts/shuffle_report.py aggregation on a fixture journal (imported
  in-process — the CLI is stdlib-only by design);
- the E2E acceptance path: a real ShuffleManager write->read with
  ``metrics_sink`` set emits one span whose per-peer table, phase
  timings, and round count match the plan; the same run with the sink
  disabled emits nothing;
- ``utils.stats.barrier`` edge cases (0-d, empty, non-array leaves).
"""

import importlib.util
import io
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu import MeshRuntime, ShuffleConf
from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import modulo_partitioner
from sparkrdma_tpu.obs import (ExchangeJournal, ExchangeSpan, Histogram,
                               MetricsRegistry, ShuffleReadStats,
                               global_registry, next_span_id, read_entries,
                               read_journal, set_global_registry)
from sparkrdma_tpu.obs.journal import SCHEMA_VERSION
from sparkrdma_tpu.utils.stats import barrier

REPO = Path(__file__).resolve().parent.parent

# the report CLI is stdlib-only, so importing it in-process keeps these
# tests in the fast tier (no worker processes involved)
_spec = importlib.util.spec_from_file_location(
    "shuffle_report", REPO / "scripts" / "shuffle_report.py")
shuffle_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(shuffle_report)


def make_span(span_id=1, shuffle_id=0, peers=(10, 10, 10, 10), **kw):
    base = dict(span_id=span_id, shuffle_id=shuffle_id, transport="fused",
                rounds=1, dispatches=1, records=sum(peers), record_bytes=16,
                plan_s=0.01, exchange_s=0.05, sort_s=0.0,
                per_peer_records=list(peers))
    base.update(kw)
    return ExchangeSpan(**base)


class TestMetricsRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        c.inc()
        c.inc(41)
        assert c.value == 42
        assert reg.counter("x") is c, "same name must be same instrument"
        assert reg.snapshot()["x"] == 42

    def test_gauge_high_water(self):
        g = MetricsRegistry().gauge("pool")
        g.set(3)
        g.set(7)
        g.set(2)
        assert g.value == 2 and g.high_water == 7
        g.add(4)
        assert g.value == 6
        g.update_max(100)
        assert g.value == 6 and g.high_water == 100

    def test_histogram_buckets_and_stats(self):
        h = MetricsRegistry().histogram("lat", bounds=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["buckets"] == [1, 1, 1]    # <=1, <=10, overflow
        assert h.count == 3
        assert h.mean == pytest.approx(55.5 / 3)
        assert snap["min"] == 0.5 and snap["max"] == 50.0

    def test_histogram_rejects_unsorted_bounds(self):
        with pytest.raises(ValueError):
            Histogram("bad", bounds=(5.0, 1.0))

    def test_disabled_registry_shares_null_singletons(self):
        r1 = MetricsRegistry(enabled=False)
        r2 = MetricsRegistry(enabled=False)
        # allocation-free contract: every name, every registry -> the
        # same shared no-op instrument, and nothing accumulates
        assert r1.counter("a") is r2.counter("b")
        assert r1.gauge("a") is r2.gauge("b")
        assert r1.histogram("a") is r2.histogram("b")
        r1.counter("a").inc(10)
        r1.gauge("a").set(10)
        r1.histogram("a").observe(10)
        assert r1.counter("a").value == 0
        assert r1.gauge("a").value == 0 and r1.gauge("a").high_water == 0
        assert r1.histogram("a").count == 0
        assert r1.snapshot() == {}

    def test_global_registry_swap(self):
        fresh = MetricsRegistry()
        prev = set_global_registry(fresh)
        try:
            assert global_registry() is fresh
            global_registry().counter("g").inc()
            assert fresh.counter("g").value == 1
        finally:
            set_global_registry(prev)
        assert global_registry() is prev

    def test_stats_feed_registry(self):
        from sparkrdma_tpu.obs.stats import ExchangeRecord

        reg = MetricsRegistry()
        stats = ShuffleReadStats(enabled=True, registry=reg)
        stats.add(ExchangeRecord(
            shuffle_id=0, plan_s=0.1, exec_s=0.2, total_records=100,
            record_bytes=16, num_rounds=2,
            per_source_records=np.array([25, 25, 25, 25])))
        assert reg.counter("shuffle.exchanges").value == 1
        assert reg.counter("shuffle.records").value == 100
        assert reg.counter("shuffle.bytes").value == 1600
        assert reg.counter("shuffle.rounds").value == 2
        assert reg.histogram("shuffle.exec_s").count == 1


class TestSpillCounter:
    def test_count_spill_feeds_global_registry(self):
        from sparkrdma_tpu.hbm.host_staging import _count_spill, spill_count

        prev = set_global_registry(MetricsRegistry())
        try:
            assert spill_count() == 0
            _count_spill(1024)
            _count_spill(2048)
            assert spill_count() == 2
            g = global_registry()
            assert g.counter("staging.spill_bytes").value == 3072
        finally:
            set_global_registry(prev)


class TestJournal:
    def test_span_round_trip(self, tmp_path):
        span = make_span(span_id=7, shuffle_id=3, peers=(5, 0, 15, 20),
                         retry_count=1, pool_high_water=4, spill_count=2)
        d = span.to_dict()
        assert d["total_bytes"] == span.records * span.record_bytes
        assert d["schema"] == 14
        back = ExchangeSpan.from_dict(d)
        assert back == span

        path = tmp_path / "j.jsonl"
        j = ExchangeJournal(str(path))
        j.emit(span)
        j.close()
        (got,) = read_journal(str(path))
        assert got == span
        # the line itself is one JSON object per line
        lines = path.read_text().splitlines()
        assert len(lines) == 1 and json.loads(lines[0])["span_id"] == 7

    def test_from_dict_ignores_unknown_fields(self):
        span = make_span()
        d = span.to_dict()
        d["future_field"] = "whatever"
        assert ExchangeSpan.from_dict(d) == span

    def test_lazy_sink_creates_no_file_until_emit(self, tmp_path):
        path = tmp_path / "idle.jsonl"
        j = ExchangeJournal(str(path))
        assert j.enabled
        j.close()
        assert not path.exists(), "idle journal must leave no artifact"
        j.emit(make_span())
        assert path.exists() and j.emitted == 1
        j.close()

    def test_disabled_sink_is_a_noop(self):
        for sink in (None, ""):
            j = ExchangeJournal(sink)
            assert not j.enabled
            j.emit(make_span())
            assert j.emitted == 0
            j.close()

    def test_file_like_sink(self):
        buf = io.StringIO()
        j = ExchangeJournal(buf)
        j.emit(make_span(span_id=1))
        j.emit(make_span(span_id=2))
        j.close()   # must NOT close a sink it doesn't own
        assert not buf.closed
        ids = [json.loads(ln)["span_id"]
               for ln in buf.getvalue().splitlines()]
        assert ids == [1, 2]

    def test_bad_sink_rejected(self):
        with pytest.raises(TypeError):
            ExchangeJournal(42)

    def test_span_ids_monotone(self):
        a, b, c = next_span_id(), next_span_id(), next_span_id()
        assert a < b < c


#: the exact field set a schema-v1 journal line carried (PR 1); the
#: cross-version tests below pin the v1 <-> v2 compat contract to it
V1_FIELDS = ("span_id", "shuffle_id", "transport", "rounds", "dispatches",
             "records", "record_bytes", "plan_s", "exchange_s", "sort_s",
             "per_peer_records", "pool_high_water", "spill_count",
             "retry_count", "ts", "schema", "total_bytes")


class TestSchemaVersioning:
    def test_schema_version_is_thirteen(self):
        assert SCHEMA_VERSION == 14
        assert make_span().schema == 14

    def test_v1_line_parses_under_v2_reader(self):
        """A journal written before the timeline existed still reads:
        v2-only fields default (empty events, single-host identity) and
        the line's own schema stamp is preserved."""
        v1_line = {
            "span_id": 5, "shuffle_id": 2, "transport": "xla",
            "rounds": 3, "dispatches": 1, "records": 100,
            "record_bytes": 16, "plan_s": 0.1, "exchange_s": 0.2,
            "sort_s": 0.0, "per_peer_records": [25, 25, 25, 25],
            "pool_high_water": 2, "spill_count": 0, "retry_count": 0,
            "ts": 1700000000.0, "schema": 1, "total_bytes": 1600,
        }
        span = ExchangeSpan.from_dict(v1_line)
        assert span.schema == 1
        assert span.events == []
        assert span.process_index == 0 and span.host_count == 1
        assert span.records == 100 and span.rounds == 3

    def test_v2_line_parses_under_v1_reader(self):
        """The v1 reader was the same drop-unknown-keys from_dict over a
        smaller field set; emulate it and feed it a v2 line. Every v1
        field must still be present on a v2 line (no rename/removal),
        and the v2-only fields must be exactly the droppable extras."""
        d = make_span(process_index=1, host_count=2,
                      events=[{"t": 0.1, "ph": "i", "name": "x"}]).to_dict()
        missing = [f for f in V1_FIELDS if f not in d]
        assert not missing, f"v2 line lost v1 fields: {missing}"
        v1_view = {k: v for k, v in d.items() if k in V1_FIELDS}
        span = ExchangeSpan.from_dict(v1_view)   # what a v1 reader builds
        assert span.records == d["records"]
        assert span.per_peer_records == d["per_peer_records"]


#: the v8 field set (schema v8 = v9 minus the combine/pushdown wire
#: fields); pins the v8 <-> v9 interchange contract
V9_ONLY_FIELDS = ("combine_in_records", "combine_out_records",
                  "combine_in_bytes", "combine_out_bytes",
                  "combine_dup_ratio", "pushdown_rows_dropped",
                  "pushdown_words_dropped")


class TestCombineSchemaV9:
    """v8 <-> v9 journal interchange + the wire-reduction report/doctor
    surface over the new per-span combine/pushdown fields."""

    def test_v8_line_parses_under_v9_reader(self):
        """A pre-combine journal line: every new field defaults to zero
        (combine never ran, nothing pushed down) and the line's own
        schema stamp survives."""
        d = make_span().to_dict()
        for f in V9_ONLY_FIELDS:
            d.pop(f)
        d["schema"] = 8
        span = ExchangeSpan.from_dict(d)
        assert span.schema == 8
        assert span.combine_in_records == 0
        assert span.combine_out_bytes == 0
        assert span.combine_dup_ratio == 0.0
        assert span.pushdown_rows_dropped == 0
        assert span.pushdown_words_dropped == 0

    def test_v9_line_parses_under_v8_reader(self):
        """The v8 reader is the same drop-unknown-keys from_dict minus
        the v9 fields; a v9 line must lose nothing it relied on."""
        d = make_span(combine_in_records=100, combine_out_records=10,
                      combine_in_bytes=1600, combine_out_bytes=160,
                      combine_dup_ratio=0.9,
                      pushdown_rows_dropped=5,
                      pushdown_words_dropped=50).to_dict()
        v8_view = {k: v for k, v in d.items() if k not in V9_ONLY_FIELDS}
        span = ExchangeSpan.from_dict(v8_view)
        assert span.records == d["records"]
        assert span.per_peer_records == d["per_peer_records"]

    def test_report_wire_section(self):
        spans = [make_span(span_id=1, combine_in_records=400,
                           combine_out_records=40,
                           combine_in_bytes=6400, combine_out_bytes=640,
                           combine_dup_ratio=0.9).to_dict(),
                 make_span(span_id=2, pushdown_rows_dropped=7,
                           pushdown_words_dropped=21,
                           combine_dup_ratio=0.1).to_dict()]
        wire = shuffle_report.aggregate(spans)["wire"]
        assert wire["combine_in_bytes"] == 6400
        assert wire["combine_out_bytes"] == 640
        assert wire["combine_reduction_ratio"] == pytest.approx(10.0)
        assert wire["max_dup_ratio"] == pytest.approx(0.9)
        assert wire["pushdown_rows_dropped"] == 7
        assert wire["pushdown_words_dropped"] == 21

    def test_doctor_missed_combine_rule(self):
        """High sampled duplication with zero combined bytes: the span
        shipped duplicates it could have folded."""
        spans = [make_span(shuffle_id=6, combine_dup_ratio=0.8).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any('map_side_combine="on"' in f and "[6]" in f
                   for f in findings)
        # combine actually ran -> no finding
        ran = [make_span(shuffle_id=6, combine_dup_ratio=0.8,
                         combine_in_bytes=1600,
                         combine_out_bytes=320).to_dict()]
        assert not any("map_side_combine" in f
                       for f in shuffle_report.diagnose(ran, []))
        # low duplication -> no finding
        low = [make_span(shuffle_id=6, combine_dup_ratio=0.1).to_dict()]
        assert not any("map_side_combine" in f
                       for f in shuffle_report.diagnose(low, []))

    def test_doctor_combine_degradation_hint(self):
        spans = [make_span(degraded=["combine"]).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any("combine" in f and "uncombined" in f for f in findings)


class _ExplodingSink(io.StringIO):
    """File-like sink that fails after ``good`` successful writes."""

    def __init__(self, good: int = 0):
        super().__init__()
        self._good = good

    def write(self, s):
        if self._good <= 0:
            raise OSError(28, "No space left on device")
        self._good -= 1
        return super().write(s)


class TestJournalHardening:
    def test_emit_failure_never_raises_and_disables_sink(self):
        reg = MetricsRegistry()
        j = ExchangeJournal(_ExplodingSink(good=0), metrics=reg)
        j.emit(make_span())                   # must not raise
        assert j.write_errors == 1
        assert not j.enabled, "first failure kills the sink"
        assert reg.counter("journal.write_errors").value == 1
        j.emit(make_span())                   # dead sink: silent no-op
        assert j.write_errors == 1 and j.emitted == 0
        j.close()

    def test_emit_failure_on_unwritable_path(self, tmp_path):
        j = ExchangeJournal(str(tmp_path / "no" / "such" / "dir" / "j.jsonl"))
        j.emit(make_span())                   # open() fails -> disabled
        assert j.write_errors == 1 and not j.enabled
        j.close()

    def test_emit_raw_requires_kind(self):
        j = ExchangeJournal(io.StringIO())
        with pytest.raises(ValueError):
            j.emit_raw({"elapsed_s": 1.0})
        j.emit_raw({"kind": "stall", "shuffle_id": 1})
        j.close()

    def test_read_journal_skips_aux_lines(self, tmp_path):
        path = tmp_path / "mixed.jsonl"
        j = ExchangeJournal(str(path))
        j.emit(make_span(span_id=1))
        j.emit_raw({"kind": "stall", "shuffle_id": 0, "span_id": 2})
        j.emit(make_span(span_id=3))
        j.close()
        spans = read_journal(str(path))
        assert [s.span_id for s in spans] == [1, 3]
        entries = read_entries(str(path))
        assert len(entries) == 3
        assert entries[1]["kind"] == "stall"

    def test_close_registered_at_manager_stop(self, tmp_path):
        """stop() must flush borrowed sinks (buffered writers would
        otherwise lose the tail of the journal on exit)."""
        flushed = []

        class Sink(io.StringIO):
            def flush(self):
                flushed.append(True)
                return super().flush()

        conf = ShuffleConf(slot_records=64)
        manager = ShuffleManager(MeshRuntime(conf), conf)
        manager.journal = ExchangeJournal(Sink())
        manager.journal.emit(make_span())
        manager.stop()
        assert flushed, "manager.stop() must flush the journal sink"


class TestMultiJournalReport:
    """Cross-host merge + straggler section + --doctor rules."""

    def _host_journal(self, tmp_path, host, exchange_s, **kw):
        path = tmp_path / f"j_{host}.jsonl"
        j = ExchangeJournal(str(path))
        j.emit(make_span(span_id=10 + host, shuffle_id=0,
                         process_index=host, host_count=2,
                         exchange_s=exchange_s, **kw))
        j.close()
        return path

    def test_multi_journal_merge_and_stragglers(self, tmp_path, capsys):
        p0 = self._host_journal(tmp_path, 0, exchange_s=0.1)
        p1 = self._host_journal(tmp_path, 1, exchange_s=0.4)
        assert shuffle_report.main([str(p0), str(p1)]) == 0
        text = capsys.readouterr().out
        assert "2 spans across 1 shuffles" in text
        assert "cross-host stragglers (2 hosts)" in text
        assert "slowest host 1" in text
        assert "spread 4.00x" in text

    def test_host_breakdown_json(self, tmp_path, capsys):
        p0 = self._host_journal(tmp_path, 0, exchange_s=0.2)
        p1 = self._host_journal(tmp_path, 1, exchange_s=0.2)
        assert shuffle_report.main([str(p0), str(p1), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["hosts"]["hosts"] == [0, 1]
        sh = rep["hosts"]["per_shuffle"]["0"]
        assert sh["spread"] == pytest.approx(1.0)

    def test_doctor_skew_rule(self):
        # 4 peers cap max/mean at 4.0 exactly; 8 peers with one hot
        # spot give 93/12.5 = 7.4x — solidly past the 4x threshold
        spans = [make_span(shuffle_id=4,
                           peers=(93, 1, 1, 1, 1, 1, 1, 1)).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any("geometry_classes" in f and "[4]" in f
                   for f in findings)

    def test_doctor_spill_rule(self):
        spans = [make_span(spill_count=3).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any("prealloc" in f for f in findings)

    def test_doctor_stall_and_retry_rules(self):
        spans = [make_span(shuffle_id=7, retry_count=2).to_dict()]
        stalls = [{"kind": "stall", "shuffle_id": 9, "elapsed_s": 2.0}]
        findings = shuffle_report.diagnose(spans, stalls)
        assert any("stall" in f and "[9]" in f for f in findings)
        assert any("retries" in f and "[7]" in f for f in findings)

    def test_doctor_healthy(self):
        spans = [make_span().to_dict()]
        assert shuffle_report.diagnose(spans, []) == [
            "no issues detected: skew, spills, stalls, retries and "
            "degradations all within normal bounds"]

    def test_serde_codec_path_split(self):
        """v8 split: legacy serde fields are TOTALS across both codec
        paths; the report derives the pickle share by difference and
        gives each path its own bound verdict."""
        spans = [make_span(
            records=5000, record_bytes=1000, exchange_s=0.05,
            serde_encode_bytes=3_000_000, serde_encode_s=0.05,
            serde_decode_bytes=3_000_000, serde_decode_s=0.05,
            serde_columnar_encode_bytes=2_000_000,
            serde_columnar_encode_s=0.001,
            serde_columnar_decode_bytes=2_000_000,
            serde_columnar_decode_s=0.001).to_dict()]
        sd = shuffle_report.aggregate(spans)["serde"]
        assert sd["encode_bytes"] == 3_000_000          # total, both paths
        assert sd["columnar"]["encode_bytes"] == 2_000_000
        assert sd["pickle"]["encode_bytes"] == 1_000_000
        assert sd["columnar"]["encode_mbps"] == pytest.approx(2000.0)
        assert sd["pickle"]["encode_mbps"] == pytest.approx(
            1_000_000 / 0.049 / 1e6, rel=1e-3)
        fabric = sd["fabric_mbps"]
        assert fabric == pytest.approx(100.0)           # 5 MB / 0.05 s
        # per-path verdicts: fast columnar is fabric-bound while the
        # slow pickle slice is codec-bound on the SAME fabric rate
        assert shuffle_report._bound_verdict(
            sd["columnar"], fabric=fabric).startswith("fabric")
        assert shuffle_report._bound_verdict(
            sd["pickle"], fabric=fabric).startswith("CODEC")

    def test_doctor_pickle_fallback_suggests_schema(self):
        spans = [make_span(
            records=5000, record_bytes=1000, exchange_s=0.05,
            serde_encode_bytes=3_000_000, serde_encode_s=0.05,
            serde_decode_bytes=3_000_000, serde_decode_s=0.05,
            serde_columnar_encode_bytes=2_000_000,
            serde_columnar_encode_s=0.001,
            serde_columnar_decode_bytes=2_000_000,
            serde_columnar_decode_s=0.001).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any("codec-bound on the pickle codec" in f
                   for f in findings)
        assert not any("codec-bound on the columnar codec" in f
                       for f in findings)
        assert any("declare a RowSchema" in f and
                   "part of the byte-payload serde work" in f
                   for f in findings)
        # pickle-only journal (no columnar bytes): the suggestion covers
        # ALL the serde work
        solo = [make_span(
            records=5000, record_bytes=1000, exchange_s=0.05,
            serde_encode_bytes=3_000_000, serde_encode_s=0.05,
            serde_decode_bytes=3_000_000, serde_decode_s=0.05).to_dict()]
        findings = shuffle_report.diagnose(solo, [])
        assert any(f.startswith("the byte-payload serde work")
                   and "declare a RowSchema" in f for f in findings)

    def test_doctor_columnar_degradation_hint(self):
        spans = [make_span(degraded=["serde_columnar"]).to_dict()]
        findings = shuffle_report.diagnose(spans, [])
        assert any("serde_columnar" in f and "v1 row codec" in f
                   for f in findings)

    def test_doctor_cli_flag(self, tmp_path, capsys):
        p0 = self._host_journal(tmp_path, 0, exchange_s=0.1,
                                peers=(93, 1, 1, 1, 1, 1, 1, 1))
        assert shuffle_report.main([str(p0), "--doctor"]) == 0
        text = capsys.readouterr().out
        assert "doctor:" in text and "geometry_classes" in text


class TestShuffleReport:
    def _fixture_journal(self, tmp_path):
        path = tmp_path / "fix.jsonl"
        j = ExchangeJournal(str(path))
        j.emit(make_span(span_id=1, shuffle_id=0, peers=(10, 10, 10, 10),
                         rounds=2, plan_s=0.1, exchange_s=0.3, sort_s=0.1))
        j.emit(make_span(span_id=2, shuffle_id=1, peers=(90, 10, 0, 0),
                         rounds=3, retry_count=1, pool_high_water=5))
        j.close()
        return path

    def test_aggregate(self, tmp_path):
        path = self._fixture_journal(tmp_path)
        rep = shuffle_report.aggregate(shuffle_report.load_spans(str(path)))
        assert rep["spans"] == 2 and rep["shuffles"] == 2
        assert rep["total_records"] == 140
        assert rep["total_bytes"] == 140 * 16
        assert rep["rounds"] == 5
        assert rep["retries"] == 1
        assert rep["pool_high_water"] == 5
        # per-peer table sums across spans
        assert rep["per_peer_records"] == {
            "0": 100, "1": 20, "2": 10, "3": 10}
        assert rep["phases"]["plan_s"] == pytest.approx(0.11)
        assert sum(rep["phase_share"].values()) == pytest.approx(1.0)
        # skew: span 2 is 90/25 = 3.6x and must rank first
        assert rep["skew"][0]["span_id"] == 2
        assert rep["skew"][0]["skew"] == pytest.approx(3.6)
        assert rep["per_shuffle"]["1"]["max_skew"] == pytest.approx(3.6)

    def test_cli_json_and_text(self, tmp_path, capsys):
        path = self._fixture_journal(tmp_path)
        assert shuffle_report.main([str(path), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["spans"] == 2
        assert shuffle_report.main([str(path), "--top", "1"]) == 0
        text = capsys.readouterr().out
        assert "2 spans across 2 shuffles" in text
        assert "peer   0" in text
        assert "3.60x" in text

    def test_empty_and_malformed_lines(self, tmp_path, capsys):
        path = tmp_path / "bad.jsonl"
        path.write_text("\n{not json}\n" + json.dumps(make_span().to_dict())
                        + "\n")
        spans = shuffle_report.load_spans(str(path))
        assert len(spans) == 1, "bad lines skipped, good ones kept"
        assert "bad JSON line skipped" in capsys.readouterr().err
        assert shuffle_report.aggregate([]) == {"spans": 0}


class TestManagerJournalE2E:
    """The acceptance path: real write->read emits a faithful span."""

    def _run_shuffle(self, conf, rng, shuffle_id=90):
        manager = ShuffleManager(MeshRuntime(conf), conf)
        try:
            mesh = manager.runtime.num_partitions
            handle = manager.register_shuffle(
                shuffle_id, mesh, modulo_partitioner(mesh))
            x = rng.integers(1, 2**32, size=(mesh * 16, 4), dtype=np.uint32)
            writer = manager.get_writer(handle)
            plan = writer.write(manager.runtime.shard_records(x)).stop(True)
            out, totals = manager.get_reader(handle).read()
            assert int(np.asarray(totals).sum()) == x.shape[0]
            return manager, plan
        finally:
            manager.stop()

    def test_journal_span_matches_plan(self, tmp_path, rng):
        sink = tmp_path / "journal.jsonl"
        conf = ShuffleConf(slot_records=64, metrics_sink=str(sink),
                           collect_shuffle_read_stats=True)
        manager, plan = self._run_shuffle(conf, rng)
        (span,) = read_journal(str(sink))
        assert span.shuffle_id == 90
        assert span.schema == 14
        assert span.transport == conf.transport
        assert span.rounds == plan.num_rounds
        assert span.records == plan.total_records
        assert span.record_bytes == 4 * 4          # W=4 uint32 words
        # per-peer receive table == the plan's per-source row sums
        assert span.per_peer_records == \
            [int(c) for c in plan.counts.sum(axis=1)]
        assert span.plan_s > 0 and span.exchange_s > 0
        assert span.sort_s == 0.0                  # full-range read: fused
        assert span.retry_count == 0
        assert span.dispatches >= 1
        assert span.pool_high_water >= 0 and span.spill_count >= 0
        # the registry saw the same exchange
        snap = manager.metrics.snapshot()
        assert snap["shuffle.exchanges"] == 1
        assert snap["exchange.plans"] >= 1

    def test_journal_even_without_read_stats(self, tmp_path, rng):
        """metrics_sink alone turns the journal on — the two knobs are
        independent (stats in memory vs spans on disk)."""
        sink = tmp_path / "only_sink.jsonl"
        conf = ShuffleConf(slot_records=64, metrics_sink=str(sink),
                           collect_shuffle_read_stats=False)
        manager, _ = self._run_shuffle(conf, rng, shuffle_id=91)
        (span,) = read_journal(str(sink))
        assert span.shuffle_id == 91
        assert not manager.stats.records, "in-memory stats stay off"

    def test_disabled_sink_emits_nothing(self, tmp_path, rng):
        """Same run, sink disabled: zero lines, zero files."""
        before = set(tmp_path.iterdir())
        conf = ShuffleConf(slot_records=64, metrics_sink="",
                           collect_shuffle_read_stats=True)
        manager, _ = self._run_shuffle(conf, rng, shuffle_id=92)
        assert manager.journal.emitted == 0
        assert not manager.journal.enabled
        assert set(tmp_path.iterdir()) == before
        # stats still work without the journal
        assert len(manager.stats.records) == 1

    def test_fully_disabled_manager_uses_null_instruments(self):
        conf = ShuffleConf(slot_records=64, metrics_sink="",
                           collect_shuffle_read_stats=False)
        manager = ShuffleManager(MeshRuntime(conf), conf)
        try:
            assert not manager.metrics.enabled
            disabled = MetricsRegistry(enabled=False)
            assert manager.metrics.counter("x") is disabled.counter("y")
            assert manager.metrics.snapshot() == {}
        finally:
            manager.stop()

    def test_retry_count_lands_in_span(self, tmp_path, rng):
        """An injected fault consumed by the retry loop shows up as
        retry_count in the span, not as a separate span."""
        sink = tmp_path / "retry.jsonl"
        conf = ShuffleConf(slot_records=64, metrics_sink=str(sink),
                           collect_shuffle_read_stats=True,
                           max_retry_attempts=3)
        manager = ShuffleManager(MeshRuntime(conf), conf)
        try:
            mesh = manager.runtime.num_partitions
            handle = manager.register_shuffle(93, mesh,
                                              modulo_partitioner(mesh))
            x = rng.integers(1, 2**32, size=(mesh * 16, 4), dtype=np.uint32)
            manager.get_writer(handle).write(
                manager.runtime.shard_records(x)).stop(True)
            fails = [True]   # first attempt faults, the retry succeeds
            manager._exchange.fault_hook = \
                lambda: fails.pop() if fails else False
            manager.get_reader(handle).read()
        finally:
            manager.stop()
        (span,) = read_journal(str(sink))
        assert span.retry_count == 1
        assert manager.metrics.counter("exchange.faults").value == 1


class TestBarrierEdgeCases:
    def test_zero_dim_array(self):
        barrier(jnp.asarray(3.5))              # 0-d

    def test_empty_array(self):
        barrier(jnp.zeros((0,), jnp.uint32))   # zero-size
        barrier(jnp.zeros((4, 0), jnp.uint32))

    def test_non_array_leaves(self):
        barrier(3, None, "str", [1, 2])        # skipped, not an error

    def test_regular_arrays(self):
        barrier(jnp.arange(8), np.arange(3).reshape(1, 3))
