"""Critical-path attribution (obs/critical_path.py) + the v9 <-> v10
journal interchange contract.

- the self-time sweep over synthetic timelines with KNOWN durations:
  nesting charges the innermost phase, ``admission:wait`` instants
  contribute their ``ms`` directly, unmapped structural events charge
  whatever encloses them;
- the partition invariant: ``sum(phase_s.values()) == wall_s`` exactly
  (``other`` absorbs the remainder; over-attributed streams scale);
- verdict flips: the same attribution machinery must answer
  codec-bound / fabric-bound / spill-bound / admission-bound /
  straggler-bound depending only on where the time (or the sync-fetch
  evidence) sits;
- schema pins: v10 fields, v9 line under the v10 reader and back;
- the E2E path: a real CPU-mesh shuffle's journal span carries a
  non-empty attribution summing to its wall-clock within 5%.
"""

import math

import numpy as np
import pytest

from sparkrdma_tpu import MeshRuntime, ShuffleConf
from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import modulo_partitioner
from sparkrdma_tpu.obs import ExchangeSpan, MetricsRegistry, read_journal
from sparkrdma_tpu.obs import critical_path as cp
from sparkrdma_tpu.obs.journal import SCHEMA_VERSION


def B(t, name, **kw):
    return {"t": t, "ph": "B", "name": name, **kw}


def E(t, name, **kw):
    return {"t": t, "ph": "E", "name": name, **kw}


def I(t, name, **kw):  # noqa: E743  (mirrors the trace-event phase letter)
    return {"t": t, "ph": "i", "name": name, **kw}


def total(phase_s):
    return sum(phase_s.values())


class TestAttribute:
    def test_single_interval_plus_other(self):
        ph = cp.attribute([B(0.0, "shuffle:plan"), E(0.1, "shuffle:plan")],
                          wall_s=0.3)
        assert ph["plan"] == pytest.approx(0.1)
        assert ph["other"] == pytest.approx(0.2)
        assert total(ph) == pytest.approx(0.3)

    def test_nesting_charges_innermost(self):
        """A queue:block inside a chunk charges queue_block; the rest
        of the chunk charges dispatch (Chrome-trace self-time)."""
        events = [B(0.0, "chunk"), B(0.02, "queue:block"),
                  E(0.05, "queue:block"), E(0.10, "chunk")]
        ph = cp.attribute(events, wall_s=0.1)
        assert ph["dispatch"] == pytest.approx(0.07)
        assert ph["queue_block"] == pytest.approx(0.03)
        assert total(ph) == pytest.approx(0.1)

    def test_admission_instant_contributes_ms(self):
        ph = cp.attribute([I(0.0, "admission:wait", ms=50.0)], wall_s=0.2)
        assert ph["admission_wait"] == pytest.approx(0.05)
        assert ph["other"] == pytest.approx(0.15)

    def test_unmapped_events_charge_enclosing_phase(self):
        """Structural events (pool acquires, counter tracks, faults)
        are not phases — time around them stays with the open phase."""
        events = [B(0.0, "serde:encode"), I(0.01, "fault:injected"),
                  I(0.02, "pool:acquire"), E(0.04, "serde:encode")]
        ph = cp.attribute(events, wall_s=0.04)
        assert ph["encode"] == pytest.approx(0.04)
        assert ph["other"] == 0.0

    def test_unmapped_outside_any_interval_lands_in_other(self):
        events = [I(0.0, "stall"), I(0.05, "stall")]
        ph = cp.attribute(events, wall_s=0.05)
        assert set(ph) == {"other"}
        assert ph["other"] == pytest.approx(0.05)

    def test_overattributed_stream_scales_to_wall(self):
        """Timelines can cover more than the span (writer-side spills
        recorded between reads) — attribution scales to partition."""
        events = [B(0.0, "spill:write"), E(1.5, "spill:write"),
                  B(1.5, "chunk"), E(2.0, "chunk")]
        ph = cp.attribute(events, wall_s=1.0)
        assert total(ph) == pytest.approx(1.0, abs=1e-5)
        # proportions survive the scale: 1.5 : 0.5 -> 0.75 : 0.25
        assert ph["spill"] == pytest.approx(0.75, abs=1e-5)
        assert ph["dispatch"] == pytest.approx(0.25, abs=1e-5)

    def test_unclosed_interval_counts_self_time_only(self):
        events = [B(0.0, "shuffle:plan"), I(0.02, "stall")]   # plan never ends
        ph = cp.attribute(events, wall_s=0.1)
        assert ph["plan"] == pytest.approx(0.02)
        assert ph["other"] == pytest.approx(0.08)

    def test_partition_invariant_on_dense_stream(self):
        """The headline property: whatever the stream shape, the
        attribution partitions the wall-clock exactly."""
        rng = np.random.default_rng(42)
        names = list(cp.PHASE_OF)
        t = 0.0
        events = []
        for _ in range(200):
            name = names[int(rng.integers(len(names)))]
            dt = float(rng.uniform(0.0001, 0.01))
            if name == "admission:wait":
                events.append(I(t, name, ms=dt * 1e3))
            else:
                events.append(B(t, name))
                events.append(E(t + dt, name))
            t += dt
        for wall in (t, t * 2.0, t * 0.5):
            ph = cp.attribute(events, wall_s=wall)
            assert total(ph) == pytest.approx(wall, abs=1e-4)
            assert set(ph) <= cp.PHASES

    def test_empty_events(self):
        ph = cp.attribute([], wall_s=0.25)
        assert ph == {"other": 0.25}


class TestVerdict:
    def test_codec_bound(self):
        assert cp.verdict({"encode": 0.3, "decode": 0.2,
                           "dispatch": 0.1}) == "codec-bound"

    def test_fabric_bound_default(self):
        assert cp.verdict({}) == "fabric-bound"
        assert cp.verdict({"dispatch": 0.3, "encode": 0.1}) == \
            "fabric-bound"

    def test_spill_bound_by_dominant_time(self):
        assert cp.verdict({"spill": 0.5, "encode": 0.2,
                           "dispatch": 0.1}) == "spill-bound"

    def test_spill_bound_by_sync_fetch_evidence(self):
        """A read that blocked on disk is spill-bound even when the
        codec owns more attributed time — spilling is the remediable
        cause."""
        events = [I(0.0, "spill:fetch", sync=True)]
        assert cp.verdict({"encode": 0.9, "spill": 0.01},
                          events) == "spill-bound"
        # async prefetch hits are NOT evidence
        events = [I(0.0, "spill:fetch", sync=False)]
        assert cp.verdict({"encode": 0.9, "spill": 0.01},
                          events) == "codec-bound"

    def test_admission_bound(self):
        assert cp.verdict({"admission_wait": 0.5, "encode": 0.2,
                           "dispatch": 0.1}) == "admission-bound"
        # below the data-path shares it defers to codec/fabric
        assert cp.verdict({"admission_wait": 0.05, "dispatch": 0.5}) == \
            "fabric-bound"

    def test_verdict_flips_with_the_dominant_phase(self):
        """The A/B the acceptance demands: same machinery, verdict
        follows wherever the time moves."""
        base = {"dispatch": 0.1, "encode": 0.1}
        for phase, want in (("decode", "codec-bound"),
                            ("fold", "fabric-bound"),
                            ("spill", "spill-bound"),
                            ("admission_wait", "admission-bound")):
            ph = dict(base)
            ph[phase] = 1.0
            assert cp.verdict(ph) == want, phase


class TestEnrich:
    def _span(self, **kw):
        base = dict(span_id=1, shuffle_id=0, transport="fused", rounds=1,
                    dispatches=1, records=40, record_bytes=16,
                    plan_s=0.01, exchange_s=0.05, sort_s=0.0,
                    per_peer_records=[10, 10, 10, 10])
        base.update(kw)
        return ExchangeSpan(**base)

    def test_enrich_sets_v10_fields(self):
        span = self._span(events=[B(0.0, "chunk"), E(0.04, "chunk")])
        cp.enrich(span)
        assert span.bottleneck == "fabric-bound"
        assert total(span.phase_s) == pytest.approx(0.06)
        assert span.phase_s["dispatch"] == pytest.approx(0.04)

    def test_enrich_counts_attributions(self):
        reg = MetricsRegistry()
        cp.enrich(self._span(), metrics=reg)
        cp.enrich(self._span(), metrics=reg)
        assert reg.counter("critical_path.attributions").value == 2


class TestCrossHostMerge:
    def _host_span(self, pidx, exchange_s, bottleneck):
        return {"process_index": pidx, "exchange_s": exchange_s,
                "bottleneck": bottleneck,
                "phase_s": {"dispatch": exchange_s}}

    def test_merge_phases_sums_and_filters(self):
        merged = cp.merge_phases([
            {"phase_s": {"dispatch": 0.1, "encode": 0.2}},
            {"phase_s": {"dispatch": 0.3, "bogus": 9.0}},
            {"phase_s": None},
        ])
        assert merged == {"dispatch": pytest.approx(0.4),
                          "encode": pytest.approx(0.2)}

    def test_straggler_delta(self):
        spans = [self._host_span(0, 0.1, "fabric-bound"),
                 self._host_span(0, 0.1, "fabric-bound"),
                 self._host_span(1, 0.4, "fabric-bound")]
        delta, ratio, slowest = cp.straggler_delta(spans)
        assert delta == pytest.approx(0.3)
        assert ratio == pytest.approx(4.0)
        assert slowest == 1

    def test_straggler_delta_single_host_is_zero(self):
        spans = [self._host_span(0, 0.1, "fabric-bound")] * 3
        assert cp.straggler_delta(spans) == (0.0, 0.0, None)

    def test_shuffle_verdict_majority_then_straggler(self):
        spans = [self._host_span(0, 0.1, "codec-bound"),
                 self._host_span(0, 0.11, "codec-bound"),
                 self._host_span(1, 0.12, "fabric-bound")]
        assert cp.shuffle_verdict(spans) == "codec-bound"
        # widen the cross-host spread past STRAGGLER_RATIO: flips
        spans[2] = self._host_span(1, 0.5, "fabric-bound")
        assert cp.shuffle_verdict(spans) == "straggler-bound"
        assert cp.shuffle_verdict([]) == ""


#: the fields only a schema-v10 line carries (v10 = v9 + the critical-
#: path attribution); pins the v9 <-> v10 interchange contract
V10_ONLY_FIELDS = ("phase_s", "bottleneck")


class TestSchemaV10:
    def _make(self, **kw):
        base = dict(span_id=1, shuffle_id=0, transport="fused", rounds=1,
                    dispatches=1, records=40, record_bytes=16,
                    plan_s=0.01, exchange_s=0.05, sort_s=0.0,
                    per_peer_records=[10, 10, 10, 10])
        base.update(kw)
        return ExchangeSpan(**base)

    def test_schema_version_is_thirteen(self):
        assert SCHEMA_VERSION == 14
        assert self._make().schema == 14

    def test_v9_line_parses_under_v10_reader(self):
        """A pre-attribution journal line: the new fields default to
        empty (no attribution ran) and the line's own schema stamp
        survives."""
        d = self._make().to_dict()
        for f in V10_ONLY_FIELDS:
            d.pop(f)
        d["schema"] = 9
        span = ExchangeSpan.from_dict(d)
        assert span.schema == 9
        assert span.phase_s == {}
        assert span.bottleneck == ""

    def test_v10_line_parses_under_v9_reader(self):
        """The v9 reader is the same drop-unknown-keys from_dict minus
        the v10 fields; a v10 line must lose nothing it relied on."""
        d = self._make(phase_s={"dispatch": 0.04, "other": 0.02},
                       bottleneck="fabric-bound").to_dict()
        assert d["phase_s"] == {"dispatch": 0.04, "other": 0.02}
        assert d["bottleneck"] == "fabric-bound"
        v9_view = {k: v for k, v in d.items()
                   if k not in V10_ONLY_FIELDS}
        span = ExchangeSpan.from_dict(v9_view)   # what a v9 reader builds
        assert span.records == d["records"]
        assert span.per_peer_records == d["per_peer_records"]

    def test_round_trip_preserves_attribution(self):
        span = cp.enrich(self._make(
            events=[B(0.0, "chunk"), E(0.04, "chunk")]))
        back = ExchangeSpan.from_dict(span.to_dict())
        assert back.phase_s == span.phase_s
        assert back.bottleneck == span.bottleneck


class TestE2EAttribution:
    def test_real_span_attribution_sums_to_wall(self, tmp_path, rng):
        """Acceptance: a real CPU-mesh shuffle's journal span carries a
        non-empty verdict and an attribution summing to the span's
        wall-clock within 5% (rounding is the only slack)."""
        sink = tmp_path / "journal.jsonl"
        conf = ShuffleConf(slot_records=64, metrics_sink=str(sink),
                           collect_shuffle_read_stats=True)
        manager = ShuffleManager(MeshRuntime(conf), conf)
        try:
            mesh = manager.runtime.num_partitions
            x = (rng.integers(0, 2**32, size=(mesh * 128, 4),
                              dtype=np.uint32))
            handle = manager.register_shuffle(
                90, mesh, modulo_partitioner(mesh))
            manager.get_writer(handle).write(
                manager.runtime.shard_records(x)).stop(True)
            manager.get_reader(handle).read()
        finally:
            manager.stop()
        (span,) = read_journal(str(sink))
        assert span.schema == 14
        assert span.bottleneck in cp.VERDICTS
        wall = span.plan_s + span.exchange_s + span.sort_s
        assert wall > 0
        assert math.isclose(total(span.phase_s), wall,
                            rel_tol=0.05, abs_tol=1e-4)
        assert set(span.phase_s) <= cp.PHASES
