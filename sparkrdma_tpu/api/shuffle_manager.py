"""ShuffleManager-shaped public API — the Spark SPI surface, TPU-native.

The reference integrates with Spark through five SPI methods
(src/main/scala/org/apache/spark/shuffle/rdma/RdmaShuffleManager.scala:
``registerShuffle``, ``getWriter``, ``getReader``, ``unregisterShuffle``,
``stop``); this module exposes the same five so a user of the reference
finds the same workflow:

    manager = ShuffleManager(runtime)
    handle  = manager.register_shuffle(0, num_parts=8, partitioner=part)
    manager.get_writer(handle).write(records)         # map stage
    out, totals = manager.get_reader(handle).read()   # reduce stage
    manager.unregister_shuffle(0); manager.stop()

Differences forced (and earned) by SPMD:

- One writer/reader pair drives ALL partitions at once (a compiled SPMD
  program), not one per task. ``get_reader``'s partition-range arguments
  become a partition *filter* applied after exchange.
- ``RdmaWrapperShuffleWriter`` delegates the actual write to stock Spark
  and then mmaps+registers the files (§write/§stop); here ``write()``
  keeps the records resident in HBM (they never need to leave) and
  publishes the size table to the registry — publication *is* the
  ``RdmaMapTaskOutput`` fill.
- ``RdmaShuffleReader.read`` wraps the fetch in deserialization, optional
  aggregation, and optional key-ordering sort; ``read()`` here mirrors
  that: exchange, then optional combine-by-key (``aggregator=``) or
  key-ordering sort (``key_ordering=``), fused into the exchange program
  on full-range reads.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from sparkrdma_tpu import faults as _faults
from sparkrdma_tpu.config import ShuffleConf
from sparkrdma_tpu.exchange.errors import (FetchFailedError,
                                           UnrecoverableShuffleError)
from sparkrdma_tpu.exchange.protocol import ShuffleExchange, ShufflePlan
from sparkrdma_tpu.hbm.tiered_store import TieredStore, store_totals
from sparkrdma_tpu.kernels.sort import sort_by_key_cols, sort_by_lead_cols
from sparkrdma_tpu.meta.checkpoint import MapOutputStore
from sparkrdma_tpu.meta.map_output import MapOutputRegistry
from sparkrdma_tpu.obs import critical_path
from sparkrdma_tpu.obs import trace as _trace
from sparkrdma_tpu.obs.alerts import AlertEvaluator
from sparkrdma_tpu.obs.baseline import BaselineStore
from sparkrdma_tpu.obs.journal import ExchangeJournal, ExchangeSpan, next_span_id
from sparkrdma_tpu.obs.metrics import MetricsRegistry, global_registry
from sparkrdma_tpu.obs.probe import ProbeServer
from sparkrdma_tpu.obs.tsdb import NULL_TELEMETRY, TelemetryStore
from sparkrdma_tpu.obs.rollup import HeartbeatEmitter, RollupAggregator, span_latency_ms
from sparkrdma_tpu.obs.timeline import (EventTimeline, scoped_active,
                                        set_active)
from sparkrdma_tpu.obs.watchdog import StallWatchdog, install_state_dump
from sparkrdma_tpu.runtime.mesh import MeshRuntime
from sparkrdma_tpu.utils.profiling import annotate, annotate_span
from sparkrdma_tpu.utils.stats import (ExchangeRecord, ShuffleReadStats,
                                       Timer, barrier)

log = logging.getLogger("sparkrdma_tpu.api")


@dataclasses.dataclass
class ShuffleHandle:
    """Opaque ticket returned by register_shuffle (Spark's ShuffleHandle)."""

    shuffle_id: int
    num_parts: int
    partitioner: Callable


def _partition_windows(plan: ShufflePlan, mesh: int, num_parts: int,
                       partition: int) -> list:
    """Locate ORIGINAL partition ``p`` inside the raw exchange output.

    Returns a list of ``(device, start_within_device, length)`` windows
    — one per sub-partition when the plan was skew-split
    (``split_factor`` sub-partitions ``p + num_parts*j``, all owned by
    the SAME device as ``p``), a single window otherwise. The output
    stream on device ``d`` is its local (sub-)partitions in ascending
    global id, each a contiguous segment of ``sum(counts[:, sp])``
    records — the single source of truth for this layout math (used by
    ``read_partition``, ``OutputView.partition`` and the skew-split
    range filter). The reference serves the same lookup from its
    ``RdmaMapTaskOutput`` tables (RdmaMappedFile §getRdmaBlockLocation);
    sub-partitions are this design's plan-time artifact, so they are
    mapped back to their parent here, invisibly to readers.
    """
    d = partition % mesh
    owned = plan.counts.sum(axis=0)
    windows = []
    for j in range(plan.split_factor):
        sp = partition + num_parts * j
        q = sp // mesh
        start = sum(int(owned[qq * mesh + d]) for qq in range(q))
        windows.append((d, start, int(owned[sp])))
    return windows


class ShuffleWriter:
    """Map-side: publish records for exchange (RdmaWrapperShuffleWriter).

    ``write`` accepts the global sharded record array; ``stop(success)``
    mirrors the reference's contract where the mmap/register/publish work
    happens in §stop, not §write.
    """

    def __init__(self, manager: "ShuffleManager", handle: ShuffleHandle):
        self._m = manager
        self._h = handle
        self._records: Optional[jax.Array] = None
        self._plan: Optional[ShufflePlan] = None

    def write(self, records: jax.Array) -> "ShuffleWriter":
        if self._records is not None:
            raise RuntimeError("writer already holds records (one write per "
                               "map stage, like one SortShuffleWriter.write)")
        self._records = records
        return self

    def stop(self, success: bool = True) -> Optional[ShufflePlan]:
        """On success: plan (size-exchange) + publish metadata.

        With ``spill_to_host`` and a configured store, the published map
        output is also persisted host-side — the analogue of shuffle
        files surviving on disk (a restarted job resumes via
        :meth:`ShuffleManager.resume_shuffle` without re-running the map).
        """
        if not success or self._records is None:
            self._records = None
            return None
        with self._m._tenant_scope():
            self._plan = self._m._exchange.plan(
                self._records, self._h.partitioner, self._h.num_parts
            )
        with annotate("shuffle:plan/publish"):
            self._m._registry.publish_map_output(self._h.shuffle_id,
                                                 self._plan.counts)
        if self._m.store is not None and self._m.conf.spill_to_host:
            self._m.checkpoint_shuffle(self._h, writer=self)
        log.debug("shuffle %d map published: %d records, %d rounds",
                  self._h.shuffle_id, self._plan.total_records,
                  self._plan.num_rounds)
        return self._plan

    # internal accessors for the reader
    @property
    def records(self) -> Optional[jax.Array]:
        return self._records

    @property
    def plan(self) -> Optional[ShufflePlan]:
        return self._plan


class ShuffleReader:
    """Reduce-side: run the exchange, optionally key-sort (RdmaShuffleReader)."""

    def __init__(self, manager: "ShuffleManager", handle: ShuffleHandle,
                 start_partition: int = 0,
                 end_partition: Optional[int] = None,
                 key_ordering: bool = False,
                 aggregator: Optional[str] = None,
                 float_payload: bool = False,
                 row_filter: Optional[Callable] = None,
                 keep_words: Optional[Tuple[int, ...]] = None,
                 combine_hint: Optional[Tuple[bool, float]] = None):
        self._m = manager
        self._h = handle
        self.start_partition = start_partition
        self.end_partition = (handle.num_parts if end_partition is None
                              else end_partition)
        if not 0 <= self.start_partition < self.end_partition <= \
                handle.num_parts:
            raise ValueError(
                f"invalid partition range [{self.start_partition}, "
                f"{self.end_partition}) for {handle.num_parts} partitions"
            )
        if aggregator is not None and aggregator not in ("sum", "min",
                                                         "max"):
            raise ValueError(f"unsupported aggregator {aggregator!r}")
        if float_payload and aggregator is None:
            raise ValueError("float_payload requires an aggregator")
        if (row_filter is not None or keep_words is not None) and \
                (start_partition, self.end_partition) != (0,
                                                          handle.num_parts):
            # the partition-range window math slices the output stream
            # by the PLAN's pre-filter counts; a pushdown shrinks the
            # stream underneath those windows, so the combination is
            # rejected rather than silently mis-sliced
            raise ValueError(
                "row_filter/keep_words pushdown requires a full "
                "partition range (partition-ranged reads slice by the "
                "plan's pre-filter counts)")
        self.key_ordering = key_ordering
        self.aggregator = aggregator
        self.float_payload = float_payload
        self.row_filter = row_filter
        self.keep_words = keep_words
        #: plan-time hoisted combine-gate decision ``(use, dup_ratio)``
        #: (``ShuffleExchange.plan_combine``) — when set, the exchange
        #: skips its in-line duplicate-ratio sampling and consumes this
        #: instead (the query planner's per-node hoist)
        self.combine_hint = combine_hint

    def read(self, record_stats: bool = True) -> Tuple[jax.Array, jax.Array]:
        """Execute the planned exchange; return ``(records, totals)``.

        ``records``: columnar ``uint32[W, mesh * out_capacity]`` sharded
        over the record axis; each device's columns = its received
        partitions, grouped by (local partition, source), zero-padded to
        ``totals`` per device. Use ``runtime.host_rows`` for a row view.
        A partition range narrower than the full handle keeps only those
        partitions' rows per device (totals shrink accordingly) — the
        reduce-task partition-range view of Spark's getReader. With
        ``key_ordering`` each device's kept prefix is lexsorted (the
        ExternalSorter stage of RdmaShuffleReader.read).

        With ``aggregator`` set ("sum"/"min"/"max"), each device's kept
        rows are combined by key first (Spark's Aggregator stage in
        RdmaShuffleReader.read): output columns become unique keys with
        reduced payloads, key-sorted, and ``totals`` counts unique keys.

        ``record_stats=False`` suppresses the stats record (used for
        warmup/compile passes so throughput histograms stay honest).
        CONTRACT: it also skips the hard device sync, so an async backend
        failure from such a read surfaces later — at the caller's first
        sync — OUTSIDE this method's FetchFailed/retry wrap. Un-recorded
        reads trade retry protection for dispatch pipelining; issue a
        final ``record_stats=True`` read (as the bench loop does) or
        sync and handle ``jax.errors.JaxRuntimeError`` yourself.
        """
        # in-flight accounting wraps the whole read so heartbeat lines
        # (and shuffle_top) can tell a host mid-read from an idle one
        self._m._read_started()
        try:
            with self._m._tenant_scope():
                return self._read(record_stats)
        finally:
            self._m._read_finished()

    def _read(self, record_stats: bool) -> Tuple[jax.Array, jax.Array]:
        writer = self._m._recover_writer(self._h)
        adm = self._m.admission
        if adm is None:
            return self._read_attempts(writer, record_stats)
        # Admission control (service mode): one ticket per read(),
        # weighted by the plan's round count so the deficit-round-robin
        # scheduler shares exchange ROUNDS, not read calls — a tenant of
        # 16-round shuffles cannot starve a tenant of 2-round ones. An
        # over-quota/over-capacity tenant QUEUES here (journaled as an
        # `admission` wait line) rather than failing.
        with adm.admit(self._m.tenant,
                       cost=max(int(writer.plan.num_rounds), 1)):
            return self._read_attempts(writer, record_stats)

    def _read_attempts(self, writer: ShuffleWriter,
                       record_stats: bool) -> Tuple[jax.Array, jax.Array]:
        ex = self._m._exchange
        conf = self._m.conf
        # one journal span per read() call (not per attempt — retries are
        # a field of the span, not separate spans); its id also names the
        # XProf annotations so trace regions and journal lines correlate
        journal_on = self._m.journal.enabled and record_stats
        span_id = next_span_id() if journal_on else 0
        # stall reports from this read carry the span/shuffle identity so
        # a journaled `stall` line correlates with its (eventual) span
        self._m.watchdog.set_context(span_id=span_id,
                                     shuffle_id=self._h.shuffle_id)
        post_s = 0.0   # separate filter/agg/sort program wall-clock
        attempt = 0
        # retry hardening: a wall-clock deadline across ALL attempts (the
        # bound that makes "max_retry_attempts with backoff" finite in
        # time, not just in count) plus per-attempt exponential backoff
        # with deterministic jitter (faults.backoff_ms). Both default off.
        deadline = (time.monotonic() + conf.retry_deadline_s
                    if conf.retry_deadline_s > 0 else None)
        backoffs: list = []   # per-attempt sleeps taken, ms (span field)
        while True:
            attempt += 1
            try:
                # Timer covers only this attempt, so exec_s excludes
                # failed attempts and checkpoint reloads — the stats stay
                # a statement about exchange throughput.
                filtered = (self.start_partition, self.end_partition) != (
                    0, self._h.num_parts)
                # Full-range reads fuse sort/aggregation into the
                # exchange program (one dispatch); a partition filter
                # must apply first, so those stay separate programs there.
                fuse_sort = self.key_ordering and not filtered
                fuse_agg = (self.aggregator or "") if not filtered else ""
                with Timer() as t:
                    try:
                        with annotate_span("shuffle:exchange", span_id):
                            out, totals, incoming = ex.exchange(
                                writer.records, self._h.partitioner,
                                writer.plan, self._h.num_parts,
                                shuffle_id=self._h.shuffle_id,
                                sort_key_words=(conf.key_words if fuse_sort
                                                else 0),
                                aggregator=fuse_agg,
                                float_payload=(self.float_payload
                                               if fuse_agg else False),
                                row_filter=self.row_filter,
                                keep_words=self.keep_words,
                                combine_hint=(self.combine_hint
                                              if fuse_agg else None),
                                keyed_after=filtered and bool(
                                    self.key_ordering or self.aggregator),
                            )
                        if filtered:
                            with Timer() as ts, annotate_span(
                                    "shuffle:filter+agg+sort", span_id):
                                if writer.plan.split_factor > 1:
                                    # sub-partition segments of a parent
                                    # are scattered through the stream;
                                    # a rank-keyed compaction regroups
                                    # them (no refusal mode — the
                                    # reference serves any range)
                                    out, totals = self._m._filtered_split(
                                        out, totals, writer.plan,
                                        self._h.num_parts,
                                        self.start_partition,
                                        self.end_partition)
                                else:
                                    out, totals = self._m._filtered(
                                        out, totals, writer.plan,
                                        self._h.num_parts,
                                        self.start_partition,
                                        self.end_partition)
                                if self.aggregator:
                                    out, totals = self._m._aggregated(
                                        out, totals, writer.plan,
                                        self.aggregator,
                                        self.float_payload)
                                elif self.key_ordering:
                                    out = self._m._sorted(out, totals,
                                                          writer.plan)
                            # dispatch wall-clock of the separate
                            # filter/agg/sort programs; 0.0 when those
                            # stages are fused into the exchange program
                            post_s = ts.elapsed
                        if record_stats:
                            # the hard sync exists to time exec_s and to
                            # surface device failures inside the retry
                            # wrap; un-recorded reads (warmup, steady-
                            # state loops) stay async so dispatches
                            # pipeline without a host round-trip each
                            with annotate("shuffle:read/barrier"):
                                barrier(out)
                    except jax.errors.JaxRuntimeError as e:
                        # A real transport/device failure surfaces as a
                        # backend runtime error; map it to the retryable
                        # fetch failure exactly like error CQEs become
                        # FetchFailedException in the reference
                        # (RdmaShuffleFetcherIterator failure path).
                        raise FetchFailedError(
                            self._h.shuffle_id,
                            f"backend failure during exchange: {e}",
                            attempt,
                        ) from e
                break
            except FetchFailedError as e:
                # Spark's contract: FetchFailed -> stage retry from
                # still-available map outputs, bounded by attempts.
                if attempt >= conf.max_retry_attempts:
                    raise FetchFailedError(
                        self._h.shuffle_id,
                        f"giving up after {attempt} attempts",
                        attempt,
                    ) from e
                if deadline is not None and time.monotonic() >= deadline:
                    # terminal, not retry-forever: the deadline converts
                    # a persistent fault into ONE clean failure
                    raise FetchFailedError(
                        self._h.shuffle_id,
                        f"retry deadline {conf.retry_deadline_s}s "
                        f"exceeded after {attempt} attempts",
                        attempt,
                    ) from e
                log.warning(
                    "shuffle %d fetch failed (attempt %d/%d): %s; "
                    "retrying", self._h.shuffle_id, attempt,
                    conf.max_retry_attempts, e)
                self._m.timeline.event("retry", attempt=attempt,
                                       shuffle=self._h.shuffle_id)
                delay_ms = _faults.backoff_ms(attempt,
                                              conf.retry_backoff_ms,
                                              span_id)
                if delay_ms > 0:
                    if deadline is not None:
                        # never sleep past the deadline itself
                        delay_ms = min(delay_ms, max(
                            (deadline - time.monotonic()) * 1e3, 0.0))
                    backoffs.append(round(delay_ms, 3))
                    self._m.timeline.event("retry:backoff",
                                           attempt=attempt,
                                           ms=round(delay_ms, 3))
                    time.sleep(delay_ms / 1e3)
                writer = self._m._recover_writer(self._h)
        plan = writer.plan
        if record_stats:
            # per-source totals for the histogram (received metadata table)
            per_source = plan.counts.sum(axis=1)
            plan_s = plan.plan_s
            self._m.stats.add(ExchangeRecord(
                shuffle_id=self._h.shuffle_id,
                plan_s=plan_s,
                exec_s=t.elapsed,
                total_records=plan.total_records,
                record_bytes=out.shape[0] * 4,
                num_rounds=plan.num_rounds,
                per_source_records=per_source,
            ))
            if journal_on:
                from sparkrdma_tpu.api.serde import codec_totals
                from sparkrdma_tpu.hbm.host_staging import spill_count

                serde = codec_totals()
                st_totals = store_totals()
                pool = self._m.runtime.pool
                span = ExchangeSpan(
                    span_id=span_id,
                    shuffle_id=self._h.shuffle_id,
                    tenant=self._m.tenant,
                    transport=ex.transport(),
                    rounds=plan.num_rounds,
                    dispatches=ex.last_dispatches,
                    records=plan.total_records,
                    record_bytes=out.shape[0] * 4,
                    plan_s=plan_s,
                    # t covers the whole attempt through the hard sync;
                    # the separate filter/agg/sort block is reported on
                    # its own (sort_s), so subtract its dispatch time
                    exchange_s=max(t.elapsed - post_s, 0.0),
                    sort_s=post_s,
                    per_peer_records=[int(c) for c in per_source],
                    pool_high_water=(pool.outstanding_high_water
                                     if pool is not None else 0),
                    spill_count=spill_count(),
                    retry_count=attempt - 1,
                    backoff_ms=backoffs,
                    degraded=_faults.active_degradations(),
                    serde_encode_bytes=serde["serde_encode_bytes"],
                    serde_encode_s=serde["serde_encode_s"],
                    serde_decode_bytes=serde["serde_decode_bytes"],
                    serde_decode_s=serde["serde_decode_s"],
                    serde_columnar_encode_bytes=serde[
                        "serde_columnar_encode_bytes"],
                    serde_columnar_encode_s=serde[
                        "serde_columnar_encode_s"],
                    serde_columnar_decode_bytes=serde[
                        "serde_columnar_decode_bytes"],
                    serde_columnar_decode_s=serde[
                        "serde_columnar_decode_s"],
                    store_spill_bytes=st_totals[0],
                    store_fetch_bytes=st_totals[1],
                    store_prefetch_hits=st_totals[2],
                    store_sync_fetches=st_totals[3],
                    process_index=self._m.runtime.process_index,
                    host_count=self._m.runtime.process_count,
                    # drain restarts the timeline clock, so the next
                    # span's events are relative to this emit (a
                    # sampled-away span still drains — and discards)
                    events=self._m.timeline.drain(),
                    # schema v9: measured combine/pushdown wire deltas
                    # of this read's exchange (per-span, not cumulative)
                    **ex.wire_stats(),
                )
                # schema v12: job-trace coordinates of whatever job /
                # stage scope this read ran under (defaults outside one)
                tctx = _trace.current_trace()
                if tctx is not None:
                    span.trace_id = tctx.trace_id
                    span.job = tctx.job
                    span.stage = tctx.stage
                    span.stage_attempt = tctx.stage_attempt
                # schema v10: phase attribution + bottleneck verdict,
                # derived from the drained events before sampling so
                # the rollup observes the enriched span too
                critical_path.enrich(span, metrics=self._m.metrics)
                # feed the attribution back into the job's stage profile
                _trace.observe_active_span(span)
                # sampling decides whether the full span lands; the
                # rollup folds the read either way, so window totals
                # stay exact under any journal_sample
                weight = self._m.sampler.keep_weight(
                    span_id, span_latency_ms(span) / 1e3)
                if self._m.rollup is not None:
                    self._m.rollup.observe(span, kept=weight > 0)
                if weight > 0:
                    span.sample_weight = weight
                    self._m.journal.emit(span)
                else:
                    self._m.metrics.counter("journal.sampled_out").inc()
        del incoming
        return out, totals

    def read_view(self) -> "OutputView":
        """Run the exchange and return a REF-COUNTED view over the
        output — the ``RdmaRegisteredBuffer`` consumer contract: one
        received buffer sliced into per-partition views with independent
        lifetimes, returned to the buffer pool on the last release.

        ``view.partition(p)`` gives partition ``p``'s records as a
        device-array slice without re-running the exchange (each call
        retains; release each view, then the base, and the buffer pages
        go back to the :class:`~sparkrdma_tpu.hbm.slot_pool.SlotPool`
        for a later exchange to donate).

        Per-partition slicing needs the raw (local partition, source)
        layout, so the view always reads full-range and unsorted
        regardless of this reader's options (same rule and reason as
        :meth:`read_partition`). On a skew-split plan a partition's
        records span its sub-partitions' segments, so ``partition(p)``
        concatenates them (a small device copy instead of a zero-copy
        slice).
        """
        out, totals = ShuffleReader(self._m, self._h).read()
        plan = self._m._writers[self._h.shuffle_id].plan
        return OutputView(self._m, self._h, out, totals, plan)

    def read_partition(self, partition: int) -> np.ndarray:
        """Materialize one partition's records on host (debug/small data).

        The SPMD exchange produces all partitions; this is the per-task
        view Spark's reader iterator would have returned.
        """
        if not self.start_partition <= partition < self.end_partition:
            raise ValueError(
                f"partition {partition} outside reader range "
                f"[{self.start_partition}, {self.end_partition})"
            )
        # Segment offsets assume the raw full-range (local partition,
        # source) layout, so read full-range and unsorted even if this
        # reader filters/sorts — slices are cut from the raw layout via
        # the shared _partition_windows math (which maps skew-split
        # sub-partitions back to their parent).
        out, totals = ShuffleReader(self._m, self._h).read()
        mesh = self._m.runtime.num_partitions
        plan = self._m._writers[self._h.shuffle_id].plan
        cap = plan.out_capacity
        arr = np.asarray(out)      # ONE full D2H, windows slice from it
        pieces = []
        for d, start, length in _partition_windows(
                plan, mesh, self._h.num_parts, partition):
            dev_cols = arr[:, d * cap:(d + 1) * cap]
            pieces.append(dev_cols[:, start:start + length].T)
        return np.ascontiguousarray(np.concatenate(pieces, axis=0))


class OutputView:
    """Ref-counted exchange output + per-partition slicing — the
    ``RdmaRegisteredBuffer`` analogue on the consumer side.

    The reference slices one registered fetch buffer into per-block
    ``ByteBuffer`` views handed to Spark, each holding a reference;
    the buffer returns to ``RdmaBufferManager`` on the last release.
    Here the exchange output is DETACHED (copied) from the pool's
    donation chain into a :class:`~sparkrdma_tpu.hbm.slot_pool.Slot`,
    ``partition(p)`` retains and slices, and the last ``release``
    returns the pages to the pool via ``put_shaped`` for a later
    same-shape exchange to reuse.
    """

    def __init__(self, manager: "ShuffleManager", handle: ShuffleHandle,
                 out: jax.Array, totals: jax.Array, plan: ShufflePlan):
        from sparkrdma_tpu.hbm.slot_pool import Slot

        # detach: the raw output is recycled by the NEXT same-geometry
        # exchange; a refcounted view must own its pages
        self._arr = jnp.array(out)
        self.totals = np.asarray(totals)
        self._plan = plan
        self._handle = handle
        self._m = manager
        self._pool = manager.runtime.pool
        self._sharding = manager.runtime.sharding(
            None, manager.runtime.axis_name)
        self._slot = Slot(self._arr, self._arr.shape[1],
                          self._arr.shape[0], self)
        self._mesh = manager.runtime.num_partitions
        self._cap = plan.out_capacity

    # Slot's pool-protocol hook: called on the LAST release
    def _put(self, slot) -> None:
        if self._pool is not None and not slot.array.is_deleted():
            self._pool.put_shaped(slot.array, self._sharding)

    def retain(self) -> "OutputView":
        self._slot.retain()
        return self

    def release(self) -> None:
        self._slot.release()

    def partition(self, p: int) -> jax.Array:
        """Columnar records of partition ``p`` (valid rows only — the
        reference's per-block view granularity). On a skew-split plan
        the partition's sub-partition segments are concatenated (a
        small device copy; single-segment plans stay zero-copy
        slices)."""
        if not 0 <= p < self._handle.num_parts:
            raise ValueError(f"partition {p} out of range")
        slices = []
        for d, start, length in _partition_windows(
                self._plan, self._mesh, self._handle.num_parts, p):
            s = start + d * self._cap
            slices.append(lax.slice_in_dim(self._arr, s, s + length,
                                           axis=1))
        if len(slices) == 1:
            return slices[0]
        return jnp.concatenate(slices, axis=1)


class ShuffleManager:
    """The SPI root object — one per process, like RdmaShuffleManager."""

    def __init__(self, runtime: Optional[MeshRuntime] = None,
                 conf: Optional[ShuffleConf] = None,
                 store: Optional[MapOutputStore] = None, *,
                 tenant: str = "",
                 tiered: Optional[TieredStore] = None,
                 journal: Optional[ExchangeJournal] = None,
                 admission=None,
                 account=None,
                 telemetry=None):
        self.runtime = runtime or MeshRuntime(conf)
        self.conf = conf or self.runtime.conf
        # Service mode (tiered= provided): this manager is a TENANT
        # SESSION handed out by a ShuffleService daemon. The runtime,
        # tiered store and journal are process singletons owned by the
        # daemon — shared, never closed here — and per-tenant state
        # (fault plane, timeline) installs thread-locally via
        # _tenant_scope() instead of into the process-wide slots, so one
        # tenant's chaos schedule or trace never bleeds into another's.
        self.tenant = tenant
        self.account = account
        self.admission = admission
        self._service_mode = tiered is not None
        if store is None and self.conf.spill_dir:
            store = MapOutputStore(
                self.conf.spill_dir,
                use_native=self.conf.use_native_staging,
                compression=self.conf.compression,
                compression_level=self.conf.compression_level)
        self.store = store
        # tiered out-of-core store (hbm/tiered_store.py): HBM slot tier +
        # pinned host leases + CRC'd disk segments. Always constructed —
        # the host tier is useful even without a disk root (eviction just
        # refuses when neither spill_tier_dir nor spill_dir is set) — and
        # handed to the exchange so round buffers are acquired through it
        # and eviction/prefetch I/O overlaps the exchange rounds.
        self.tiered = (tiered if tiered is not None
                       else TieredStore(self.conf, pool=self.runtime.pool))
        # unified observability root: either knob turns the registry on
        # (collect_shuffle_read_stats for in-memory stats, metrics_sink
        # for the journal); off, every instrument is a shared no-op
        self.metrics = MetricsRegistry(
            enabled=(self.conf.collect_shuffle_read_stats
                     or bool(self.conf.metrics_sink)))
        # multi-host: a shared sink path would interleave hosts' lines;
        # the {process} placeholder gives each host its own journal file
        # (merged later by shuffle_report.py / shuffle_trace.py)
        if journal is not None:
            self.journal = journal       # daemon-owned, shared, not closed
            self._sink_path = ""         # daemon's probe serves its sink
        else:
            sink = self.conf.metrics_sink
            if isinstance(sink, str) and "{process}" in sink:
                sink = sink.replace("{process}",
                                    str(self.runtime.process_index))
            self.journal = ExchangeJournal(
                sink, metrics=self.metrics,
                max_bytes=self.conf.journal_max_bytes)
            self._sink_path = sink if isinstance(sink, str) else ""
        # span sampling: which reads get a full journal line (the rest
        # still feed metrics + rollups; see obs.journal.SamplingPolicy)
        self.sampler = self.conf.sampling_policy()
        # live telemetry store (obs/tsdb.py): windowed view of the
        # registry + per-shuffle rollup history. Service mode shares the
        # daemon-owned store (telemetry=); standalone managers own (and
        # stop) their own. Disabled → the allocation-free null store.
        if telemetry is not None:
            self.telemetry = telemetry   # daemon-owned, not stopped here
        elif (self.metrics.enabled and self.conf.telemetry_window_s > 0):
            # fold the process-global registry into every sample: the
            # tiered store / staging / degradation ladders record there
            # (store.*, staging.*, degrade.*), and the alert rules that
            # watch those series query THIS store
            self.telemetry = TelemetryStore(
                self.metrics, window_s=self.conf.telemetry_window_s,
                history=self.conf.telemetry_history,
                extra_sources=(lambda: global_registry().snapshot(),))
            self.telemetry.start()
        else:
            self.telemetry = NULL_TELEMETRY
        # windowed rollups: exact per-shuffle aggregates regardless of
        # sampling, one {"kind":"rollup"} line per window
        self.rollup = (RollupAggregator(
            self.journal, window_s=self.conf.rollup_window_s,
            process_index=self.runtime.process_index,
            store=(self.telemetry if self.telemetry.enabled else None))
            if self.journal.enabled and self.conf.rollup_window_s > 0
            else None)
        # liveness: reads currently executing (heartbeat + shuffle_top)
        self._reads_in_flight = 0
        self.heartbeat = None
        # service mode: the daemon owns THE heartbeat (with the
        # per-tenant usage probe); sessions never start their own
        if (not self._service_mode and self.journal.enabled
                and self.conf.heartbeat_s > 0):
            pool = self.runtime.pool
            self.heartbeat = HeartbeatEmitter(
                self.journal, self.conf.heartbeat_s,
                identity=self.runtime.process_identity(),
                probes={
                    "in_flight": lambda: self._reads_in_flight,
                    "pool_outstanding": (
                        lambda: pool.outstanding if pool is not None
                        else 0),
                    "host_tier_mb": (
                        lambda: self.tiered.occupancy()["host_bytes"]
                        // (1 << 20)),
                    "disk_tier_mb": (
                        lambda: self.tiered.occupancy()["disk_bytes"]
                        // (1 << 20)),
                })
            self.heartbeat.start()
        # alerting (obs/alerts.py + obs/baseline.py): service mode the
        # daemon owns THE evaluator (per-tenant rules need the shared
        # usage rings); a standalone manager runs its own against its
        # own telemetry store.
        self.baselines = None
        self.alerts = None
        if (not self._service_mode and self.telemetry.enabled
                and self.conf.alert_eval_s > 0):
            self.baselines = (BaselineStore(self.conf.baseline_dir)
                              if self.conf.baseline_dir else None)
            self.alerts = AlertEvaluator(
                telemetry=self.telemetry,
                metrics=self.metrics,
                journal=self.journal,
                baselines=self.baselines,
                heartbeat=self.heartbeat,
                interval_s=self.conf.alert_eval_s,
                fire_after=self.conf.alert_fire_breaches,
                resolve_after=self.conf.alert_resolve_windows,
                geometry=f"w{self.runtime.num_partitions}")
            self.alerts.start()
        # probe endpoint (obs/probe.py): read-only wire snapshots for
        # shuffle_top --connect. Service mode: the daemon owns THE probe
        # (with tenant usage); standalone managers start their own.
        # Bind failure is logged, never fatal — telemetry must not take
        # down the shuffle it observes.
        self.probe = None
        if not self._service_mode and self.conf.probe_port >= 0:
            try:
                self.probe = ProbeServer(
                    self.conf.probe_port,
                    metrics=self.metrics,
                    telemetry=self.telemetry,
                    identity=self.runtime.process_identity(),
                    journal_path=self._sink_path,
                    rollups=(self.rollup.peek
                             if self.rollup is not None else None),
                    alerts=(self.alerts.active
                            if self.alerts is not None else None),
                    health=(self.alerts.health
                            if self.alerts is not None else None),
                    jobs=self.telemetry.job_lines)
                self.probe.start()
            except OSError:
                log.warning("probe endpoint failed to bind port %d",
                            self.conf.probe_port, exc_info=True)
        # per-span event timeline: events accumulate across plan+read and
        # drain into the span's `events` array at emit time
        self.timeline = EventTimeline(enabled=self.journal.enabled)
        if not self._service_mode:
            # the process-wide timeline slot belongs to the standalone
            # manager; tenant sessions install theirs thread-locally
            # inside _tenant_scope() instead
            set_active(self.timeline)
        self.watchdog = StallWatchdog(self.conf.watchdog_timeout_s,
                                      journal=self.journal,
                                      metrics=self.metrics,
                                      timeline=self.timeline)
        if self.watchdog.enabled:
            install_state_dump()   # SIGUSR1 armed-wait dump (best effort)
        # chaos plane: deterministic fault schedules from conf.fault_spec,
        # installed process-wide (module-level sites — staging, serde,
        # checkpoint — reach it without a handle through every signature)
        self.faults = _faults.FaultPlane(self.conf.fault_spec)
        # blast-radius isolation: a tenant session's plane reaches the
        # module-level fault sites through the thread-local overlay
        # (faults.scoped_plane) only while that tenant's calls run, so
        # its schedule/degradations never fire inside another tenant's
        # shuffle. Standalone managers keep the process-wide install.
        self._prev_plane = None
        self._plane_installed = not self._service_mode
        if self._plane_installed:
            self._prev_plane = _faults.set_active_plane(
                self.faults if self.faults.enabled else None)
        # the runtime's SlotPool serves exchange recv/output buffers
        # (RdmaBufferManager wiring: the node owns the pool, channels use it)
        if self.runtime.pool is not None and not self._service_mode:
            # service mode: the pool is a shared singleton already wired
            # to the daemon's registries — a session must not re-point it
            self.runtime.pool.metrics = self.metrics
            self.runtime.pool.timeline = self.timeline
        self.stats = ShuffleReadStats(self.conf.collect_shuffle_read_stats,
                                      registry=self.metrics)
        self._exchange = ShuffleExchange(self.runtime.mesh,
                                         self.runtime.axis_name, self.conf,
                                         pool=self.runtime.pool,
                                         metrics=self.metrics,
                                         stats=self.stats,
                                         timeline=self.timeline,
                                         watchdog=self.watchdog,
                                         journal=self.journal,
                                         rollup=self.rollup,
                                         identity=(
                                             self.runtime.process_index,
                                             self.runtime.process_count),
                                         store=self.tiered,
                                         tenant=self.tenant,
                                         account=self.account)
        ids = tuple(self.runtime.manager_id(i)
                    for i in range(self.runtime.num_partitions))
        self._registry = MapOutputRegistry(ids, metrics=self.metrics)
        self._writers: dict[int, ShuffleWriter] = {}
        self._sort_cache: dict[tuple, Callable] = {}
        self._filter_cache: dict[tuple, Callable] = {}

    # --- SPI ----------------------------------------------------------
    def register_shuffle(self, shuffle_id: int, num_parts: int,
                         partitioner: Callable) -> ShuffleHandle:
        self._registry.register(shuffle_id, num_parts, partitioner)
        return ShuffleHandle(shuffle_id, num_parts, partitioner)

    def get_writer(self, handle: ShuffleHandle) -> ShuffleWriter:
        w = ShuffleWriter(self, handle)
        self._writers[handle.shuffle_id] = w
        return w

    def get_reader(self, handle: ShuffleHandle, start_partition: int = 0,
                   end_partition: Optional[int] = None,
                   key_ordering: bool = False,
                   aggregator: Optional[str] = None,
                   float_payload: bool = False,
                   row_filter: Optional[Callable] = None,
                   keep_words: Optional[Tuple[int, ...]] = None,
                   combine_hint: Optional[Tuple[bool, float]] = None
                   ) -> ShuffleReader:
        """``row_filter``/``keep_words`` push a predicate / projection
        into the exchange program itself (full partition range only):
        filtered rows never occupy a slot, projected-away payload words
        never hit the wire (they come back zero-filled).
        ``combine_hint`` feeds a plan-time hoisted combine-gate decision
        (``ShuffleExchange.plan_combine``) to an aggregator read. See
        :meth:`ShuffleExchange.exchange`."""
        return ShuffleReader(self, handle, start_partition, end_partition,
                             key_ordering, aggregator, float_payload,
                             row_filter, keep_words, combine_hint)

    def job(self, name: str) -> "_trace.JobTrace":
        """Open a job trace over the exchanges that follow::

            with manager.job("tpcds_q64") as job:
                with job.stage("item_join"):
                    ...register / write / read...

        Every span, rollup window, heartbeat and admission line emitted
        inside the context is stamped with the trace coordinates
        (journal schema v12); at exit one ``{"kind": "job"}`` summary
        line lands in the journal — per-stage critical-path profiles,
        ``stage:idle`` time, the per-job verdict — and feeds the
        telemetry store's per-job history ring (probe ``/jobs``).
        See :mod:`sparkrdma_tpu.obs.trace`.
        """
        return _trace.JobTrace(
            name, tenant=self.tenant, journal=self.journal,
            store=self.telemetry,
            process_index=self.runtime.process_index)

    def unregister_shuffle(self, shuffle_id: int) -> None:
        with annotate("shuffle:unregister"):
            self._registry.unregister(shuffle_id)
            self._writers.pop(shuffle_id, None)
            # dispose: recycled output buffers go back to the pool
            # (callers must have consumed this shuffle's reads by now —
            # the reference frees registered buffers on unregisterShuffle
            # the same way)
            self._exchange.release_shuffle(shuffle_id)
            # tiered-store teardown: drop this shuffle's remaining
            # segments (host leases AND disk files). Without this,
            # segments published via put(..., shuffle=)/adopt() outlived
            # their shuffle until close() — pinned host bytes and .seg
            # files leaking across the manager's lifetime.
            self.tiered.delete_shuffle(shuffle_id, tenant=self.tenant)
            if self.store is not None:  # shuffle files removed too
                self.store.delete(shuffle_id)

    # --- durability (checkpoint / resume) -----------------------------
    def checkpoint_shuffle(self, handle: ShuffleHandle,
                           writer: Optional[ShuffleWriter] = None) -> None:
        """Persist the published map output host-side (explicit spill).

        ``writer`` lets a caller checkpoint its own state directly (the
        stop() path uses this) so a writer displaced from the manager's
        table by a later ``get_writer`` still checkpoints what it
        published. Multi-host: when the records span devices this
        process cannot address, each process spills only its OWN shards
        (``MapOutputStore.save_shards``) — the reference's per-executor
        shuffle files, where no executor writes another's map output.
        """
        if self.store is None:
            raise RuntimeError("no MapOutputStore configured "
                               "(set conf.spill_dir or pass store=)")
        if writer is None:
            writer = self._writers.get(handle.shuffle_id)
        if writer is None or writer.records is None or writer.plan is None:
            raise RuntimeError(
                f"shuffle {handle.shuffle_id}: nothing published to "
                "checkpoint")
        if not writer.records.is_fully_addressable:
            records = writer.records
            n = records.shape[1]
            shard_len = n // self.runtime.num_partitions
            shards = []
            for sh in records.addressable_shards:
                coord = int(sh.index[1].start) // shard_len
                shards.append((coord, np.asarray(sh.data)))
            self.store.save_shards(
                handle.shuffle_id, shards, writer.plan, handle.num_parts,
                records.shape, jax.process_index(), jax.process_count())
            return
        self.store.save(handle.shuffle_id, np.asarray(writer.records),
                        writer.plan, handle.num_parts)

    def resume_shuffle(self, handle: ShuffleHandle) -> ShuffleWriter:
        """Rebuild a writer's published state from the host checkpoint.

        The restarted job re-registers the shuffle (with the same
        partitioner — functions are not serialized, matching how a
        restarted Spark job re-creates its lineage) and this reloads the
        map output so the map stage is skipped.
        """
        if self.store is None:
            raise RuntimeError("no MapOutputStore configured "
                               "(set conf.spill_dir or pass store=)")
        meta = self.store.load_meta(handle.shuffle_id)
        plan = self.store.plan_from_meta(meta)
        num_parts = int(meta["num_parts"])
        if num_parts != handle.num_parts:
            raise ValueError(
                f"checkpoint has num_parts={num_parts}, handle says "
                f"{handle.num_parts}")
        mesh_now = self.runtime.num_partitions
        if plan.counts.shape[0] != mesh_now:
            # A stale plan on a resized mesh would silently overflow the
            # round geometry (fill_round_slots drops the excess).
            raise ValueError(
                f"checkpoint was taken on a {plan.counts.shape[0]}-device "
                f"mesh; current mesh has {mesh_now} devices — re-run the "
                "map stage instead of resuming")
        shape = tuple(meta["shape"])
        shard_len = shape[1] // mesh_now
        try:
            if meta.get("sharded"):
                # per-process reload: the callback is only ever invoked
                # for this process's addressable shards, so each process
                # touches only its own files (executor-local shuffle
                # files)
                store, sid = self.store, handle.shuffle_id

                def read(idx):
                    coord = int(idx[1].start or 0) // shard_len
                    return store.read_shard(
                        sid, coord, (shape[0], shard_len))[idx[0], :]

                records = jax.make_array_from_callback(
                    shape,
                    self.runtime.sharding(None, self.runtime.axis_name),
                    read)
            else:
                records_np = self.store.read_records(handle.shuffle_id,
                                                     meta)
                records = jax.make_array_from_callback(
                    records_np.shape,
                    self.runtime.sharding(None, self.runtime.axis_name),
                    lambda idx: records_np[idx])
        except OSError as e:
            # the checkpoint failed CRC verification (or is unreadable)
            # even after the storage layer's bounded re-read: the live
            # map output is gone AND the persisted copy is bad, so a
            # retry would re-read the same corrupt bytes — terminal.
            raise UnrecoverableShuffleError(
                handle.shuffle_id, f"checkpoint unreadable: {e}") from e
        w = ShuffleWriter(self, handle)
        # checkpoints store the columnar [W, N] batch; reshard over N
        # (make_array_from_callback: works when some devices are
        # non-addressable, unlike a global device_put)
        w._records = records
        w._plan = plan
        self._writers[handle.shuffle_id] = w
        self._registry.publish_map_output(handle.shuffle_id, plan.counts)
        log.info("shuffle %d resumed from checkpoint: %d records",
                 handle.shuffle_id, plan.total_records)
        return w

    def checkpoint_segments(self, shuffle_id: int, segments,
                            plan: Optional[ShufflePlan],
                            num_parts: int,
                            extra_meta: Optional[dict] = None) -> None:
        """Persist chunked map output as independent CRC'd segment files
        (see :meth:`MapOutputStore.save_segments`) — the durable twin of
        the tiered store's chunk keys, enabling :meth:`resume_segments`.
        ``plan`` is None for exchange-OUTPUT checkpoints (the query
        planner's reuse cache), which resume from the manifest alone.
        ``extra_meta`` adds caller fields to the manifest (the planner
        records its full exchange fingerprint as ``plan_fp`` so resume
        can reject a shuffle-id collision).
        """
        if self.store is None:
            raise RuntimeError("no MapOutputStore configured "
                               "(set conf.spill_dir or pass store=)")
        self.store.save_segments(shuffle_id, segments, plan, num_parts,
                                 extra_meta=extra_meta)

    def resume_segments(self, shuffle_id: int) -> list:
        """Restart path for chunked shuffles: adopt a segment-level
        checkpoint into the tiered store, replaying ONLY the segments
        missing from it. Already-resident segments (host or disk tier)
        are left untouched; adopted ones are registered without reading
        — the prefetcher pulls them in lazily as the exchange consumes
        them. Returns the adopted (i.e. previously missing) keys.
        """
        if self.store is None:
            raise RuntimeError("no MapOutputStore configured "
                               "(set conf.spill_dir or pass store=)")
        meta = self.store.load_segment_meta(shuffle_id)
        adopted = []
        for key, entry in meta["segments"].items():
            if self.tiered.contains(key):
                continue
            self.tiered.adopt(key,
                              self.store.segment_path(shuffle_id, entry),
                              entry["shape"], entry["dtype"],
                              tenant=self.tenant, shuffle=shuffle_id)
            adopted.append(key)
        log.info("shuffle %d segment resume: %d/%d segments replayed",
                 shuffle_id, len(adopted), len(meta["segments"]))
        return adopted

    def _recover_writer(self, handle: ShuffleHandle) -> ShuffleWriter:
        """Live writer if its map output is intact, else checkpoint."""
        writer = self._writers.get(handle.shuffle_id)
        if (writer is not None and writer.records is not None
                and writer.plan is not None):
            return writer
        if self.store is not None and self.store.contains(handle.shuffle_id):
            return self.resume_shuffle(handle)
        raise RuntimeError(
            f"shuffle {handle.shuffle_id}: no published map output (and "
            "no checkpoint); call get_writer(handle).write(records).stop() "
            "first"
        )

    def stop(self) -> None:
        if self._plane_installed and _faults.active_plane() is self.faults:
            _faults.set_active_plane(self._prev_plane)
        if self.stats.enabled and self.stats.records:
            self.stats.print_histogram()
        if self.heartbeat is not None:
            self.heartbeat.stop()       # emits one final beat
        if self.alerts is not None:
            self.alerts.stop()          # persists dirty baselines
            self.alerts = None
        if self.probe is not None:
            self.probe.stop()
            self.probe = None
        if self.rollup is not None:
            self.rollup.flush()         # close the open window
        # recycled round/output buffers (incl. the donation chain's tail)
        # go back to the pool before any teardown that might retire it
        self._exchange.release_all()
        if self._service_mode:
            # tenant session teardown: every segment this tenant still
            # holds in the shared store is dropped (host leases, disk
            # files, quota charges) — but the daemon's singletons
            # (journal, tiered store, runtime, pool) stay up for the
            # other tenants.
            self.tiered.delete_tenant(self.tenant)
            self._writers.clear()
            return
        # daemon-shared telemetry is stopped by the daemon; a
        # standalone manager owns its store
        self.telemetry.stop()
        self.journal.close()
        self.tiered.close()
        self._writers.clear()
        self.runtime.stop()

    def _read_started(self) -> None:
        self._reads_in_flight += 1
        self.metrics.gauge("reads.in_flight").set(self._reads_in_flight)

    def _read_finished(self) -> None:
        self._reads_in_flight -= 1
        self.metrics.gauge("reads.in_flight").set(self._reads_in_flight)

    def _tenant_scope(self) -> contextlib.ExitStack:
        """Thread-local tenant overlay for the duration of one SPI call.

        In service mode this installs the session's fault plane and
        event timeline into the CALLING THREAD only
        (``faults.scoped_plane`` / ``timeline.scoped_active``), so
        module-level fault sites and ``record_active`` reach tenant-
        scoped state without a handle — and, critically, WITHOUT the
        process-wide install a standalone manager uses, which would let
        one tenant's chaos schedule fire inside a concurrent tenant's
        shuffle. Standalone managers return an empty stack (the globals
        are already theirs).
        """
        stack = contextlib.ExitStack()
        if self._service_mode:
            stack.enter_context(_faults.scoped_plane(
                self.faults if self.faults.enabled else None))
            stack.enter_context(scoped_active(self.timeline))
        return stack

    # --- helpers ------------------------------------------------------
    def _filtered(self, out: jax.Array, totals: jax.Array,
                  plan: ShufflePlan, num_parts: int,
                  start: int, end: int) -> Tuple[jax.Array, jax.Array]:
        """Keep only partitions in ``[start, end)`` per device.

        A device's rows are contiguous segments per local partition in
        ascending global-id order, so the kept set is one contiguous
        window: roll it to the front, zero the tail, shrink totals. The
        window geometry comes from the plan (static), passed as data so
        one compiled program serves every range.
        """
        mesh = self.runtime.num_partitions
        cap = plan.out_capacity
        owned = plan.counts.sum(axis=0)  # [num_parts]
        offs = np.zeros((mesh, 2), np.int32)
        for d in range(mesh):
            for q in range(num_parts // mesh):
                p = q * mesh + d
                if p < start:
                    offs[d, 0] += int(owned[p])
                elif p < end:
                    offs[d, 1] += int(owned[p])
        window = self.runtime.shard_rows(offs)

        key = (cap, out.shape[0])
        fn = self._filter_cache.get(key)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            ax = self.runtime.axis_name

            def local_filter(cols, win):
                off, ln = win[0, 0], win[0, 1]
                rolled = jnp.roll(cols, -off, axis=1)
                valid = jnp.arange(cap) < ln
                return (jnp.where(valid[None, :], rolled, jnp.uint32(0)),
                        ln[None].astype(jnp.int32))

            fn = jax.jit(shard_map(
                local_filter, mesh=self.runtime.mesh,
                in_specs=(P(None, ax), P(ax)),
                out_specs=(P(None, ax), P(ax)),
            ))
            self._filter_cache[key] = fn
        return fn(out, window)

    def _filtered_split(self, out: jax.Array, totals: jax.Array,
                        plan: ShufflePlan, num_parts: int,
                        start: int, end: int) -> Tuple[jax.Array, jax.Array]:
        """Partition-range filter for SKEW-SPLIT plans.

        Under a split plan the records of original partition ``p`` are
        scattered across ``split_factor`` sub-partition segments of the
        device stream, so the kept set is not one contiguous window
        (:meth:`_filtered`'s trick). Instead every segment gets a host-
        computed RANK — ``(parent - start) * split + j`` for kept
        segments, the all-ones sentinel for dropped ones — each row
        inherits its segment's rank via one ``searchsorted`` against the
        segment-boundary cumsum, and a single stable rank-keyed sort
        compacts kept rows to the front GROUPED BY PARENT partition
        (then sub-partition, then stream order): exactly the layout an
        unsplit range read produces. Wide records route through the
        (rank, index)-sort + one-gather path, so a W=25 filtered read
        never meets the 25-operand compile wall. Rank/length tables are
        device data, so ONE compiled program per geometry serves every
        range.
        """
        mesh = self.runtime.num_partitions
        cap = plan.out_capacity
        k = plan.split_factor
        owned = plan.counts.sum(axis=0)          # [num_parts * k]
        s_total = (num_parts * k) // mesh        # segments per device
        seg_len = np.zeros((mesh, s_total), np.int32)
        seg_rank = np.full((mesh, s_total), 0xFFFFFFFF, np.uint32)
        for d in range(mesh):
            for q in range(s_total):
                sp = q * mesh + d
                seg_len[d, q] = int(owned[sp])
                parent, j = sp % num_parts, sp // num_parts
                if start <= parent < end:
                    seg_rank[d, q] = (parent - start) * k + j
        lens = self.runtime.shard_rows(seg_len)
        ranks = self.runtime.shard_rows(seg_rank)

        w = out.shape[0]
        strategy = self.conf.sort_strategy(w)
        key = ("splitfilter", cap, w, s_total, strategy)
        fn = self._filter_cache.get(key)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            ax = self.runtime.axis_name
            sentinel = jnp.uint32(0xFFFFFFFF)

            def local_filter(cols, sl, rk):
                sl, rk = sl[0], rk[0]                       # [S]
                bounds = jnp.cumsum(sl)                     # incl. ends
                r = jnp.arange(cap, dtype=jnp.int32)
                s_ix = jnp.minimum(
                    jnp.searchsorted(bounds, r, side="right"), s_total - 1)
                rank = jnp.where(r < bounds[-1], jnp.take(rk, s_ix),
                                 sentinel)
                ln = jnp.sum(rank != sentinel).astype(jnp.int32)
                live = (r < ln)
                _, packed = sort_by_lead_cols(cols, rank, strategy)
                packed = packed * live[None].astype(packed.dtype)
                return packed, ln[None]

            fn = jax.jit(shard_map(
                local_filter, mesh=self.runtime.mesh,
                in_specs=(P(None, ax), P(ax), P(ax)),
                out_specs=(P(None, ax), P(ax)),
            ))
            self._filter_cache[key] = fn
        return fn(out, lens, ranks)

    def _aggregated(self, out: jax.Array, totals: jax.Array,
                    plan: ShufflePlan, op: str,
                    float_payload: bool) -> Tuple[jax.Array, jax.Array]:
        """Per-device combine-by-key of the valid prefix (post-filter).

        The full-range path fuses this into the exchange program; a
        partition-filtered read applies it here instead, compiled per
        geometry like :meth:`_sorted`.
        """
        from sparkrdma_tpu.kernels.aggregate import combine_by_key_cols

        key_words = self.conf.key_words
        cap = plan.out_capacity
        key = ("agg", cap, out.shape[0], key_words, op, float_payload)
        fn = self._filter_cache.get(key)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            ax = self.runtime.axis_name
            strategy = self.conf.sort_strategy(out.shape[0])

            def local_agg(cols, total):
                valid = jnp.arange(cap) < total[0]
                combined, nuniq = combine_by_key_cols(
                    cols, valid, key_words, op, float_payload, strategy)
                return combined, nuniq[None]

            fn = jax.jit(shard_map(
                local_agg, mesh=self.runtime.mesh,
                in_specs=(P(None, ax), P(ax)),
                out_specs=(P(None, ax), P(ax)),
            ))
            self._filter_cache[key] = fn
        return fn(out, totals)

    def _sorted(self, out: jax.Array, totals: jax.Array,
                plan: ShufflePlan) -> jax.Array:
        """Per-device lexsort of the valid prefix, compiled per geometry."""
        key_words = self.conf.key_words
        cap = plan.out_capacity
        w = out.shape[0]
        key = (cap, w, key_words)
        fn = self._sort_cache.get(key)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            ax = self.runtime.axis_name
            strategy = self.conf.sort_strategy(w)

            def local_sort(cols, total):
                valid = jnp.arange(cap) < total[0]
                return sort_by_key_cols(cols, key_words, valid, strategy,
                                        stable=self.conf.stable_key_sort)

            fn = jax.jit(shard_map(
                local_sort, mesh=self.runtime.mesh,
                in_specs=(P(None, ax), P(ax)),
                out_specs=P(None, ax),
            ))
            self._sort_cache[key] = fn
            self.metrics.counter("exchange.programs_built.sort").inc()
        return fn(out, totals)

    def __enter__(self) -> "ShuffleManager":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


__all__ = ["ShuffleManager", "ShuffleHandle", "ShuffleWriter",
           "ShuffleReader", "OutputView"]
