"""The slotted all-to-all exchange — the data plane.

This module is the TPU-native re-design of SparkRDMA's entire fetch path
(SURVEY.md §3.3): where ``RdmaShuffleFetcherIterator`` groups needed blocks
per remote executor, RDMA-READs each executor's ``RdmaMapTaskOutput`` table,
aggregates adjacent blocks up to ``maxAggBlock``, throttles bytes in flight,
and posts one-sided READs into pooled registered buffers
(src/main/scala/org/apache/spark/shuffle/rdma/RdmaShuffleFetcherIterator
.scala §fetchBlocks / §next), here the same job is a small number of
compiled SPMD programs:

1. **Size exchange** — a [P]-vector ``all_to_all`` of per-destination record
   counts. This *is* the metadata fetch: one-sided, no driver hot spot,
   ~16B x P per chip (the reference reads RdmaMapTaskOutput tables by RDMA
   READ for the same reason — SURVEY.md §2.3 design point).
2. **Data rounds** — fixed-shape ``all_to_all``s of ``[P, capacity, W]``
   slot tensors. Fixed capacity is the XLA-legal form of block aggregation
   (``maxAggBlock``); partitions bigger than one slot stream across rounds
   exactly like the reference's chunked READs through bounded buffers.
3. **Compaction** — received slots are squeezed into one dense local
   partition (the result-queue drain + stream concat).

Execution has two regimes, switched on ``conf.max_rounds_in_flight`` (the
bytes-in-flight throttle of the reference's fetcher):

- ``num_rounds <= max_rounds_in_flight``: ONE fused program (bucket, size
  exchange, all rounds, compaction, optional fused sort/aggregation) —
  one dispatch, XLA overlaps packing with collectives.
- more rounds than that: **streaming** — a prep program (bucket + size
  exchange), then round *chunks* of ``max_rounds_in_flight`` rounds each
  dispatched as separate programs whose recv buffers come from the
  :class:`~sparkrdma_tpu.hbm.slot_pool.SlotPool` and are folded into a
  donated output accumulator as they complete. Live slot memory is
  bounded by ``conf.queue_depth`` outstanding chunks (the recvQueueDepth
  analogue): the host blocks on chunk ``j - queue_depth`` before
  dispatching chunk ``j``.

The number of rounds is data-dependent, so a shuffle is *planned* first
(:func:`plan_shuffle` — one tiny compiled step + host reduction) and then
*executed* with static geometry (:meth:`ShuffleExchange.exchange`). This
two-phase structure is the reference's own: fetch metadata, then size and
issue the reads.

Buffer reuse contract (``RdmaRegisteredBuffer`` semantics): when the
exchange was constructed with a pool, the output array of
:meth:`ShuffleExchange.exchange` is recycled as the donated output buffer
of the NEXT same-geometry exchange — consume (or copy) it before then,
exactly as the reference's fetch results are pooled buffers released back
to ``RdmaBufferManager`` after the reader drains them.

Partitions-per-device: ``num_parts`` must equal the mesh axis size times an
integer ``parts_per_device``; partition ``p`` lives on device ``p %
mesh_size`` (round-robin, like Spark's reduce-task placement across
executors).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.config import (ShuffleConf, size_class,
                                  size_class_fine)
from sparkrdma_tpu.kernels.bucketing import (_UNROLL_LIMIT, bucket_records,
                                             bucket_sorted_counts,
                                             compact_segments,
                                             fill_round_slots,
                                             fill_round_slots_dest_major,
                                             histogram_pids)

from sparkrdma_tpu.obs.metrics import MetricsRegistry
from sparkrdma_tpu.obs.stats import ExchangeRecord, ShuffleReadStats
from sparkrdma_tpu.obs.timeline import NULL_TIMELINE, EventTimeline
from sparkrdma_tpu.obs.watchdog import StallWatchdog
from sparkrdma_tpu.runtime.mesh import mesh_interpret


@dataclasses.dataclass(frozen=True)
class ShufflePlan:
    """Host-side execution plan — what the metadata fetch tells the reducer.

    ``counts[s, p]`` = records device ``s`` will send to partition ``p``
    (the global RdmaMapTaskOutput table). ``num_rounds`` and
    ``out_capacity`` are the static geometry derived from it.

    ``split_factor > 1`` records hot-partition splitting (SURVEY.md §7
    hard-part 2): every partition was split into that many position-based
    sub-partitions owned by the SAME device, so ``counts`` has
    ``num_parts * split_factor`` columns. Records of an original
    partition stay on their device but are no longer contiguous in its
    output stream (they appear once per sub-partition) — full-range
    reads (sort/aggregate/repartition) are unaffected; partition-range
    views refuse split plans.
    """

    counts: np.ndarray          # int64 [mesh, num_parts * split_factor]
    num_rounds: int
    out_capacity: int           # per-device compacted output capacity
    capacity: int               # slot capacity used for planning
    split_factor: int = 1

    @property
    def total_records(self) -> int:
        return int(self.counts.sum())


def split_partitioner(partitioner: Callable, num_parts: int,
                      k: int) -> Callable:
    """Wrap ``partitioner`` to spread each partition over ``k``
    same-device sub-partitions ``p + num_parts * j``.

    ``j`` cycles by record position (``iota % k``): deterministic across
    the plan's count pass and the exchange's bucket pass (both see the
    same per-device layout), uniform even when every key is identical —
    the failure mode key-hash splitting cannot handle. Because
    ``num_parts`` is a multiple of the mesh size, ``(p + num_parts*j) %
    mesh == p % mesh``: ownership is unchanged, only the per-(src, dst)
    round pressure drops by ~k (Spark gets this relief from
    many-tasks-per-core; AQE-style skew splitting is the same move).
    """

    def wrapped(records):
        base = partitioner(records).astype(jnp.int32)
        j = lax.iota(jnp.int32, records.shape[1]) % k
        return base + num_parts * j

    wrapped.cache_key = ("split", k, num_parts,
                         getattr(partitioner, "cache_key", id(partitioner)))
    return wrapped


def _device_partition_counts(counts_local, num_parts, mesh_size, axis_name):
    """[num_parts] per-dest counts -> [mesh, parts_per_device] for a2a.

    Partition p is owned by device p % mesh_size; column-group g of the
    result holds the partitions owned by device g.
    """
    ppd = num_parts // mesh_size
    # reorder columns so owner-device blocks are contiguous: dest device d
    # owns partitions d, d+mesh, d+2*mesh, ...
    idx = jnp.arange(num_parts).reshape(ppd, mesh_size).T.reshape(-1)
    return jnp.take(counts_local, idx, axis=0).reshape(mesh_size, ppd)


def _make_count_fn(mesh: Mesh, axis_name: str, num_parts: int,
                   partitioner: Callable) -> Callable:
    """Build the planning step: global records -> global counts matrix.

    Records are columnar ``[W, N]`` sharded over ``N`` (see
    ``MeshRuntime.shard_records``).
    """

    def local_counts(records):
        pids = partitioner(records).astype(jnp.int32)
        counts = histogram_pids(pids, num_parts)   # scatter-free
        # all_gather -> replicated [mesh, P] so EVERY process can read the
        # table locally (multi-host: a sharded output would leave other
        # processes' rows non-addressable). This is the one-sided
        # metadata-table read of the reference, made collective.
        return jax.lax.all_gather(counts, axis_name)

    return jax.jit(
        shard_map(
            local_counts,
            mesh=mesh,
            in_specs=(P(None, axis_name),),
            out_specs=P(),
            check_vma=False,  # VMA can't infer all_gather replication
        )
    )


class ShuffleExchange:
    """Compiled-exchange factory + cache — the ``RdmaChannel`` cache analogue.

    One instance per :class:`~sparkrdma_tpu.runtime.mesh.MeshRuntime`.
    Where ``RdmaNode.getRdmaChannel`` caches one connection per peer, this
    caches one *compiled program* per exchange geometry
    ``(num_parts, capacity, rounds, out_capacity, record_words)`` — the
    thing that is expensive to set up and reusable across shuffles on TPU.
    """

    def __init__(self, mesh: Mesh, axis_name: str,
                 conf: Optional[ShuffleConf] = None,
                 pool=None,
                 metrics: Optional[MetricsRegistry] = None,
                 stats: Optional[ShuffleReadStats] = None,
                 timeline: Optional[EventTimeline] = None,
                 watchdog: Optional[StallWatchdog] = None,
                 journal=None,
                 rollup=None,
                 identity: Tuple[int, int] = (0, 1),
                 store=None,
                 tenant: str = "",
                 account=None):
        self.mesh = mesh
        self.axis_name = axis_name
        self.conf = conf or ShuffleConf()
        self.mesh_size = int(mesh.shape[axis_name])
        #: Pallas kernels of this exchange (ring transport, merge sort)
        #: run interpreted: follows the mesh's devices, never the
        #: default backend
        self.interpret = mesh_interpret(mesh)
        # multi-tenant service identity: spans carry it, exec-cache and
        # collective-id keys fold it in (two tenants' identically-shaped
        # exchanges must not alias), and the account meters HBM buffers
        self.tenant = tenant
        self.account = account
        # tiered out-of-core store (hbm/tiered_store.py): when present,
        # round buffers are acquired/released through it so its
        # per-acquisition service() poke overlaps host->disk eviction
        # with the exchange rounds; the HBM tier IS the slot pool, so a
        # store-only caller inherits its pool.
        self.store = store
        if store is not None and pool is None:
            pool = store.pool
        self.pool = pool
        # disabled registry by default: instrumentation sites stay
        # unconditional (null instruments are no-ops)
        self.metrics = metrics if metrics is not None \
            else MetricsRegistry(enabled=False)
        # in-span event timeline + stall watchdog (obs layer); both
        # default to no-ops so instrumentation sites stay unconditional
        self.timeline = timeline if timeline is not None else NULL_TIMELINE
        self.watchdog = watchdog if watchdog is not None \
            else StallWatchdog(self.conf.watchdog_timeout_s)
        #: test hook: called (with the chunk index) INSIDE the armed
        #: watchdog region before each streaming queue wait — lets tests
        #: simulate a wedged collective without wedging a collective
        self.block_hook: Optional[Callable[[int], None]] = None
        # optional read-stats accumulator so DIRECT exchange users (the
        # ring / hierarchical transport paths driven without a
        # ShuffleManager) still populate ExchangeRecord spans when
        # conf.collect_shuffle_read_stats is on; shuffle() feeds it.
        if stats is not None:
            self.stats = stats
        else:
            self.stats = ShuffleReadStats(
                enabled=self.conf.collect_shuffle_read_stats,
                registry=self.metrics)
        # optional journal + rollup aggregator so DIRECT exchange users
        # (same population as above) emit sampled spans and exact window
        # rollups too; shuffle() feeds them. ``identity`` is the
        # (process_index, host_count) pair stamped into those spans —
        # the manager passes the real mesh identity, standalone users
        # default to single-host.
        self.journal = journal
        self.rollup = rollup
        self.sampler = self.conf.sampling_policy()
        self.identity = identity
        self._exec_cache: Dict[Tuple, Callable] = {}
        self._count_cache: Dict[Tuple, Callable] = {}
        # previous output per (shuffle_id, geometry), recycled as the next
        # donated output buffer of a REPEAT read of the same shuffle, and
        # released to the pool on release_shuffle (unregisterShuffle ->
        # dispose -> RdmaBufferManager.put in the reference). Keying on
        # shuffle_id keeps concurrent shuffles' outputs independent (a
        # join legitimately holds two same-geometry outputs at once).
        self._out_prev: Dict[Tuple, Tuple[jax.Array, object]] = {}
        #: programs dispatched by the most recent exchange() — observability
        #: for the in-flight machinery (1 = fused path)
        self.last_dispatches = 0
        # Fault injection (SURVEY.md §5: the reference has no fault
        # tooling in-repo; the build adds the hook the exchange loop
        # needs for testing job-level retry). ``fault_hook`` (tests)
        # takes priority over the random ``fault_injection_rate``.
        self.fault_hook: Optional[Callable[[], bool]] = None
        self._fault_rng = np.random.default_rng(0xFA17)
        #: wall-clock of the most recent plan() — folded into spans
        self.last_plan_s = 0.0
        # graceful-degradation ladder, transport rung: when a ring /
        # hierarchical transport fails to construct and
        # conf.transport_fallback is on, the exchange permanently (per
        # instance) falls back to the plain xla all_to_all. Sticky —
        # flapping between transports would thrash the compile cache.
        self._transport_override: Optional[str] = None
        # combine rung of the same ladder: sticky per-instance
        # combine-off after a map-side-combine program fails to build
        self._combine_override = False
        # wire accounting of the most recent exchange() — the measured
        # pre/post-combine + pushdown byte deltas the journal spans and
        # the future AQE loop consume (see wire_stats())
        self._last_wire: Optional[Tuple] = None
        self._last_wire_stats: Dict[str, float] = {}

    def transport(self) -> str:
        """The transport actually in use (conf choice, or the sticky
        ``xla`` fallback after a transport degradation)."""
        return self._transport_override or self.conf.transport

    def _get_buf(self, shape, sharding):
        """A device round buffer — through the tiered store when present
        (its per-acquisition ``service()`` poke lets eviction I/O overlap
        the round), straight from the pool otherwise. Caller guarantees
        ``self.pool is not None``."""
        if self.store is not None:
            return self.store.acquire_device(shape, jnp.uint32, sharding,
                                             account=self.account)
        return self.pool.get_shaped(shape, jnp.uint32, sharding,
                                    account=self.account)

    def _put_buf(self, arr, sharding) -> None:
        if self.store is not None:
            self.store.release_device(arr, sharding, account=self.account)
        else:
            self.pool.put_shaped(arr, sharding, account=self.account)

    def _degrade_transport(self, exc: BaseException) -> None:
        if not self.conf.transport_fallback:
            raise exc
        from sparkrdma_tpu import faults as _faults

        self._transport_override = "xla"
        # compiled programs embed the dead transport; rebuild on demand
        self._exec_cache.clear()
        self.metrics.counter("exchange.transport_fallbacks").inc()
        _faults.note_degradation(
            "transport", reason=f"{self.conf.transport}: {exc}")

    def _degrade_combine(self, exc: BaseException) -> None:
        """Combine rung of the degradation ladder: sticky per-instance
        combine-off after a map-side-combine program fails to build or
        trace (mirrors the transport rung — flapping would thrash the
        compile cache; the reader-side combine still runs, so results
        are unchanged, only wire bytes grow back)."""
        from sparkrdma_tpu import faults as _faults

        self._combine_override = True
        # compiled programs embed the dead combine pass; rebuild on demand
        self._exec_cache.clear()
        self.metrics.counter("combine.fallbacks").inc()
        _faults.note_degradation("combine", reason=str(exc))

    def _sampled_dup_ratio(self, records) -> float:
        """Duplicate-key ratio estimate (``1 - unique/sample``) from up
        to ``conf.combine_sample_rows`` leading rows of the first
        addressable shard — one tiny D2H read, no compiled pass."""
        k = self.conf.combine_sample_rows
        if k <= 0:
            return 1.0           # sampling disabled: assume duplicates
        kw = self.conf.key_words
        try:
            shard = records.addressable_shards[0].data
        except (AttributeError, IndexError):
            shard = records
        sample = np.asarray(jax.device_get(shard[:kw, :k]))
        n = sample.shape[1]
        if n == 0:
            return 0.0
        uniq = len({tuple(col) for col in sample.T.tolist()})
        return 1.0 - uniq / n

    def _combine_gate(self, records, aggregator: str) -> Tuple[bool, float]:
        """The plan-time combine gate: decide map-side combine for this
        exchange from the sampled duplicate-ratio estimate.

        The estimate is computed whenever an aggregator is present —
        even with combine off — so every aggregator span journals the
        duplication signal ``shuffle_report --doctor``'s missed-combine
        rule reads."""
        use, ratio = self.plan_combine(records, aggregator)
        if aggregator:
            self.metrics.counter(
                "combine.gate_on" if use else "combine.gate_off").inc()
        return use, ratio

    def plan_combine(self, records, aggregator: str) -> Tuple[bool, float]:
        """PLAN-TIME combine gate: the same decision as the in-exchange
        gate, computed off the exchange's critical path (the query
        planner hoists it per reduce node and hands the result back as
        :meth:`exchange`'s ``combine_hint``). Does NOT bump the gate
        counters — the exchange that consumes the decision does, so
        hoisted and inline decisions count identically."""
        if not aggregator:
            return False, 0.0
        ratio = self._sampled_dup_ratio(records)
        mode = self.conf.map_side_combine
        if mode == "off" or self._combine_override:
            use = False
        elif mode == "on":
            use = True
        else:
            use = ratio >= self.conf.combine_min_dup_ratio
        return use, ratio

    def _note_wire(self, records, incoming, combined: bool,
                   filtered: bool, keep_words, dup_ratio: float) -> None:
        """Stash the raw operands of :meth:`wire_stats` — summing
        ``incoming`` syncs with the device, so it is deferred until a
        span is actually emitted."""
        w = records.shape[0]
        w_eff = len(keep_words) if keep_words is not None else w
        self._last_wire_stats = {}
        self._last_wire = (int(records.shape[1]), w, w_eff, incoming,
                           bool(combined), bool(filtered),
                           float(dup_ratio))

    def wire_stats(self) -> Dict[str, float]:
        """Combine/pushdown wire accounting of the most recent
        :meth:`exchange` — the journal span's schema-v9 fields.

        ``combine_{in,out}_{records,bytes}`` measure the pre-exchange
        reduction (populated only when map-side combine ran; a filter
        pushdown running under combine is folded into the same delta).
        ``pushdown_rows_dropped`` counts filter-dropped rows when
        combine did NOT run; ``pushdown_words_dropped`` counts
        projected-away payload words actually kept off the wire.
        ``combine_dup_ratio`` is the gate's sampled estimate (present
        for every aggregator exchange, combine on or off — the
        ``--doctor`` missed-combine signal)."""
        if self._last_wire is None:
            return {}
        if self._last_wire_stats:
            return self._last_wire_stats
        n_in, w, w_eff, incoming, combined, filtered, ratio = \
            self._last_wire
        out_rec = n_in
        if combined or filtered:
            out_rec = int(np.asarray(jax.device_get(incoming)).sum())
        s: Dict[str, float] = {"combine_dup_ratio": ratio}
        if combined:
            s.update(combine_in_records=n_in,
                     combine_out_records=out_rec,
                     combine_in_bytes=n_in * w * 4,
                     combine_out_bytes=out_rec * w_eff * 4)
        elif filtered:
            s["pushdown_rows_dropped"] = n_in - out_rec
        if w_eff != w:
            s["pushdown_words_dropped"] = (w - w_eff) * out_rec
        self._last_wire_stats = s
        return s

    def _maybe_inject_fault(self, shuffle_id: int = -1) -> None:
        from sparkrdma_tpu import faults as _faults
        from sparkrdma_tpu.exchange.errors import FetchFailedError

        if _faults.fire("exchange.dispatch") == "fail":
            # the plane already counted + journaled the injection
            self.metrics.counter("exchange.faults").inc()
            raise FetchFailedError(
                shuffle_id, "injected fault (fault_spec: exchange.dispatch)")
        if self.fault_hook is not None:
            if self.fault_hook():
                self.metrics.counter("exchange.faults").inc()
                self.timeline.event("fault:injected", shuffle=shuffle_id)
                raise FetchFailedError(shuffle_id, "injected fault (hook)")
        elif self.conf.fault_injection_rate > 0.0:
            if self._fault_rng.random() < self.conf.fault_injection_rate:
                self.metrics.counter("exchange.faults").inc()
                self.timeline.event("fault:injected", shuffle=shuffle_id)
                raise FetchFailedError(shuffle_id, "injected fault (rate)")

    # ------------------------------------------------------------------
    # phase 1: plan (the metadata fetch)
    # ------------------------------------------------------------------
    def plan(
        self,
        records: jax.Array,
        partitioner: Callable,
        num_parts: Optional[int] = None,
        capacity: Optional[int] = None,
    ) -> ShufflePlan:
        """Compute the global counts matrix and derive static geometry.

        One compiled step (scatter-free histogram + all-gather of the [mesh,
        num_parts] matrix to host) followed by two host reductions. The
        host round-trip is tiny and is exactly the reference's "read the
        map-output table before issuing READs" step.
        """
        t0 = time.perf_counter()
        self.timeline.begin("plan")
        num_parts = num_parts or self.mesh_size
        explicit_capacity = capacity
        if num_parts % self.mesh_size:
            raise ValueError(
                f"num_parts {num_parts} not a multiple of mesh size "
                f"{self.mesh_size}"
            )

        classer = (size_class_fine
                   if self.conf.geometry_classes == "fine" else size_class)

        def measure(part_fn, parts):
            key = (parts, getattr(part_fn, "cache_key", id(part_fn)))
            fn = self._count_cache.get(key)
            if fn is None:
                fn = _make_count_fn(self.mesh, self.axis_name, parts,
                                    part_fn)
                self._count_cache[key] = fn
            counts = np.asarray(jax.device_get(fn(records))).astype(np.int64)
            if int(counts.sum()) != records.shape[1]:
                # histogram_pids drops out-of-range ids (its documented
                # precondition); catching the shortfall HERE — the one
                # host-visible point every shuffle passes through — turns
                # a buggy user partitioner into a loud error instead of
                # quiet record loss downstream (round-3 advisor finding)
                raise ValueError(
                    f"partitioner produced out-of-range partition ids: "
                    f"counted {int(counts.sum())} of {records.shape[1]} "
                    f"records over {parts} partitions (ids must lie in "
                    f"[0, num_parts))")
            per_pair_max = int(counts.max(initial=0))
            if explicit_capacity is not None:
                cap = explicit_capacity
            else:
                # Auto-size the slot to the measured worst (src, dst)
                # pair, capped by conf.slot_records (the maxAggBlock
                # ceiling): a balanced shuffle then pads almost nothing,
                # while skew streams in slot_records-sized rounds.
                # Power-of-two classes bound the number of compiled
                # geometries (same rule as the buffer pools).
                cap = min(classer(max(1, per_pair_max)),
                          self.conf.slot_records)
            return counts, cap, max(1, math.ceil(per_pair_max / cap))

        counts, capacity, num_rounds = measure(partitioner, num_parts)
        split = 1
        if num_rounds > self.conf.max_rounds:
            # Hot-partition mitigation (SURVEY.md §7 hard-part 2): split
            # every partition into k same-device sub-partitions so the
            # worst (src, dst) pair shrinks by ~k, instead of refusing.
            split = math.ceil(num_rounds / self.conf.max_rounds)
            sp = split_partitioner(partitioner, num_parts, split)
            counts, capacity, num_rounds = measure(sp, num_parts * split)
        if num_rounds > self.conf.max_rounds:
            # defensive only: position-based splitting is uniform per
            # (src, partition), so the re-measured rounds land within the
            # budget for any input (covered by the extreme-skew test);
            # kept as a guard against future non-uniform split schemes
            raise ValueError(
                f"partition skew needs {num_rounds} rounds > max_rounds "
                f"{self.conf.max_rounds} even after {split}-way partition "
                "splitting; raise slot_records or max_rounds"
            )
        # records received by device d = sum over sources of counts[:, p]
        # for the partitions p owned by d (p % mesh == d)
        owned = counts.sum(axis=0)  # [num_parts * split]
        per_device_in = np.array(
            [owned[d::self.mesh_size].sum() for d in range(self.mesh_size)]
        )
        out_capacity = classer(max(1, int(per_device_in.max())))
        self.last_plan_s = time.perf_counter() - t0
        self.metrics.counter("exchange.plans").inc()
        self.metrics.histogram("exchange.plan_s").observe(self.last_plan_s)
        self.timeline.end("plan", rounds=num_rounds, capacity=capacity,
                          split=split)
        return ShufflePlan(
            counts=counts,
            num_rounds=num_rounds,
            out_capacity=out_capacity,
            capacity=capacity,
            split_factor=split,
        )

    # ------------------------------------------------------------------
    # transports
    # ------------------------------------------------------------------
    def _ring_fused_active(self) -> bool:
        """Is the fused multi-round ring kernel the dispatch path?"""
        return (self.transport() == "pallas_ring"
                and self.conf.ring_fused)

    def _make_ring_exchange(self, num_rounds: int, collective_id: int):
        """Construct the fused kernel, or ``None`` after degradation.

        Construction failure (pallas import, lowering rejection) walks
        the same ladder as the per-round transports: sticky fallback to
        ``xla`` when ``transport_fallback`` allows, re-raise otherwise.
        The caller falls through to the plain per-round path on None.
        """
        try:
            from sparkrdma_tpu.exchange.ring import make_ring_exchange

            return make_ring_exchange(self.mesh, self.axis_name,
                                      num_rounds,
                                      collective_id=collective_id,
                                      metrics=self.metrics)
        except Exception as exc:  # degradation ladder (or re-raise)
            self._degrade_transport(exc)
            return None

    def _data_a2a(self, collective_id: int = 7) -> Callable:
        """The configured data-round transport: dest-major slot tensor
        ``[mesh, ...]`` -> source-major received tensor.

        ``collective_id`` names the barrier semaphore of the pallas
        transports; derived per exec-cache key (see
        :func:`~sparkrdma_tpu.exchange.ring.derive_collective_id`) so
        concurrent shuffles never share a barrier."""
        ax = self.axis_name
        if self.transport() == "pallas_ring":
            try:
                from sparkrdma_tpu.exchange.ring import make_ring_all_to_all

                return make_ring_all_to_all(self.mesh, ax,
                                            collective_id=collective_id,
                                            metrics=self.metrics)
            except Exception as exc:  # degradation ladder (or re-raise)
                self._degrade_transport(exc)
        if self.transport() == "hierarchical":
            try:
                from sparkrdma_tpu.exchange.hierarchical import (
                    make_hierarchical_all_to_all)

                return make_hierarchical_all_to_all(
                    self.mesh, ax, self.conf.hierarchy_hosts,
                    metrics=self.metrics)
            except Exception as exc:  # degradation ladder (or re-raise)
                self._degrade_transport(exc)

        def a2a(slots):
            return lax.all_to_all(slots, ax, split_axis=0,
                                  concat_axis=0, tiled=True)

        return a2a

    def _uses_fast_sort(self, out_capacity: int, sort_key_words: int,
                        aggregator: str) -> bool:
        """Will the fused tail run the Pallas merge-path sort? (Programs
        embedding it must disable vma checking, like the ring transport —
        pallas kernels mix varying refs with unvarying grid indices.)"""
        from sparkrdma_tpu.kernels.merge_sort import supports_fast_sort

        return (bool(sort_key_words) and not aggregator
                and self.conf.fast_sort
                and not self.conf.stable_key_sort  # kernel is unstable
                and supports_fast_sort(out_capacity,
                                       self.conf.fast_sort_run))

    def _fuse_tail(self, out, total, out_capacity, sort_key_words,
                   aggregator, float_payload, tight_out=False):
        """Optional fused reduce-side stages (sort / combine-by-key).

        ``tight_out``: the plan proved every device's output is exactly
        full (totals == out_capacity), so the sort can drop its
        validity lead operand — one fewer array through the comparator
        network."""
        mode = self.sort_mode(out.shape[0])
        if aggregator:
            from sparkrdma_tpu.kernels.aggregate import combine_by_key_cols

            valid = jnp.arange(out_capacity) < total
            out, total = combine_by_key_cols(
                out, valid, self.conf.key_words, aggregator, float_payload,
                wide=(mode == "wide"),
                ride_words=self.conf.wide_sort_ride_words,
                pack=(mode == "pack"))
        elif sort_key_words:
            from sparkrdma_tpu.kernels.merge_sort import merge_sort_cols
            from sparkrdma_tpu.kernels.sort import (lexsort_cols,
                                                    packed_lexsort_cols)
            from sparkrdma_tpu.kernels.wide_sort import sort_wide_cols

            valid = (None if tight_out
                     else jnp.arange(out_capacity) < total)
            if self._uses_fast_sort(out_capacity, sort_key_words,
                                    aggregator):
                # Pallas merge-path sort: full-record order (sorted by
                # the key words; payload words break ties), not stable —
                # the ExternalSorter contract Spark actually gives for
                # sortByKey. Stable arrival order within equal keys is
                # opt-in via conf.stable_key_sort (which disables this
                # kernel and the unstable fallback below).
                out = merge_sort_cols(out, valid,
                                      run=self.conf.fast_sort_run,
                                      interpret=self.interpret)
            elif mode == "pack":
                out = packed_lexsort_cols(
                    out, sort_key_words, valid,
                    stable=self.conf.stable_key_sort)
            elif mode == "wide":
                out = sort_wide_cols(out, sort_key_words, valid,
                                     ride_words=self.conf.wide_sort_ride_words)
            else:
                # key-ordering only: Spark's sortByKey promises no
                # secondary order, so the cheaper unstable network is
                # contract-accurate by default; stable_key_sort restores
                # arrival-order ties for callers that need them
                out = lexsort_cols(out, sort_key_words, valid,
                                   stable=self.conf.stable_key_sort)
        return out, total

    def _wide_sort(self, record_words: int) -> bool:
        """Payload wide enough for the key+index sort + placement path?
        (Only reached when packing is off — see :meth:`sort_mode`.)"""
        t = self.conf.wide_sort_min_payload
        return bool(t) and record_words - self.conf.key_words >= t

    def _pack_sort(self, record_words: int) -> bool:
        """Payload wide enough for u64 operand packing? Takes precedence
        over the ride/gather wide path (round-5 measured winner)."""
        t = self.conf.pack_sort_min_payload
        return bool(t) and record_words - self.conf.key_words >= t

    def sort_mode(self, record_words: int) -> str:
        """THE precedence rule for full-record sorts at this geometry:
        ``"pack"`` (u64 operand packing) > ``"wide"`` (key+index sort +
        gather placement) > ``"plain"`` (monolithic variadic sort).
        Every site that picks a sort strategy — fused tail, map-side
        bucket, combine/group/densify/filter compactions — asks here,
        so the rule cannot silently diverge between paths."""
        if self._pack_sort(record_words):
            return "pack"
        if self._wide_sort(record_words):
            return "wide"
        return "plain"

    # ------------------------------------------------------------------
    # map-side front half (shared by both regimes)
    # ------------------------------------------------------------------
    def _map_side(self, records, partitioner, num_parts: int,
                  combine: bool, aggregator: str, float_payload: bool,
                  row_filter, kw_idx):
        """Shared map-side pass, traced inside the local step of BOTH
        regimes: partition, predicate pushdown (filtered rows take the
        out-of-range sentinel pid ``num_parts`` and never occupy a
        slot), projection pushdown (``kw_idx`` gathers the kept words —
        payload shrinks before bucketing, so dropped words never hit
        the wire), then either the map-side combine pass — whose
        (partition, key) sort already IS the bucketing sort, so its
        compacted counts come from one :func:`bucket_sorted_counts`
        histogram — or the plain bucketing sort.

        Returns ``(sr, counts, offsets)`` in ``bucket_records``'s
        contract; counts are post-filter/post-combine, so the existing
        size-exchange lane carries the ragged compacted rounds with no
        wire change."""
        from sparkrdma_tpu.kernels.aggregate import map_side_combine_cols

        pids = partitioner(records).astype(jnp.int32)
        if row_filter is not None:
            pids = jnp.where(row_filter(records), pids,
                             jnp.int32(num_parts))
        recs = (records if kw_idx is None
                else jnp.take(records, kw_idx, axis=0))
        mode = self.sort_mode(recs.shape[0])
        if combine:
            sr, spids, _ = map_side_combine_cols(
                recs, pids, num_parts, self.conf.key_words, aggregator,
                float_payload, wide=(mode == "wide"),
                ride_words=self.conf.wide_sort_ride_words,
                pack=(mode == "pack"))
            counts, offs = bucket_sorted_counts(spids, num_parts)
            return sr, counts, offs
        # bucket_records' num_parts==1 shortcut skips the histogram (it
        # counts the whole batch) — under a filter the sentinel rows
        # must still be counted OUT, so bucket over 2 partitions and
        # slice the real one back (a no-op slice otherwise)
        np_eff = num_parts if (num_parts > 1 or row_filter is None) else 2
        sr, counts, offs = bucket_records(
            recs, pids, np_eff,
            wide=(mode == "wide"),
            ride_words=self.conf.wide_sort_ride_words,
            pack=(mode == "pack"))
        return sr, counts[:num_parts], offs[:num_parts]

    # ------------------------------------------------------------------
    # phase 2, regime A: one fused program
    # ------------------------------------------------------------------
    def _build_exec(self, num_parts: int, capacity: int, num_rounds: int,
                    out_capacity: int, record_words: int,
                    partitioner: Callable,
                    sort_key_words: int = 0,
                    aggregator: str = "",
                    float_payload: bool = False,
                    donate_out: bool = False,
                    tight_out: bool = False,
                    collective_id: int = 7,
                    combine: bool = False,
                    row_filter: Optional[Callable] = None,
                    keep_words: Optional[Tuple[int, ...]] = None
                    ) -> Callable:
        """``sort_key_words > 0`` fuses the reduce-side key-ordering sort
        into the same compiled program (one dispatch, one XLA schedule —
        the RdmaShuffleReader's ExternalSorter stage inlined).
        ``aggregator`` ("sum"/"min"/"max") fuses the reduce-side combine
        the same way (the optional Aggregator stage of
        RdmaShuffleReader.read); output rows become unique keys with
        reduced payloads (key-sorted, so it subsumes ``sort_key_words``)
        and ``totals`` becomes the unique-key count. ``float_payload``
        bitcasts payload words to float32 for the reduction.
        ``donate_out``: program takes a same-shape output buffer to donate
        (pool-served; the full-overwrite write-through lets XLA alias).

        Pre-exchange reduction (the wire-shrinking pass, all fused into
        the same program): ``combine`` runs the map-side combine before
        bucketing; ``row_filter`` (jit-safe ``records -> bool[n]``) is
        the predicate pushdown; ``keep_words`` the projection pushdown —
        the program moves ``len(keep_words)`` words per record and
        re-widens (zero-fills) on the reduce side, so the output is
        always full-width ``[W, out_capacity]``."""
        mesh_size = self.mesh_size
        ppd = num_parts // mesh_size
        ax = self.axis_name
        w_eff = len(keep_words) if keep_words is not None else record_words
        kw_idx = (jnp.asarray(keep_words, jnp.int32)
                  if keep_words is not None else None)

        def rewiden(out):
            # re-widen a projected output to full record width with
            # zero-filled dropped payload words — a static W-way stack,
            # never a scatter (kernels/aggregate.py module docstring)
            if keep_words is None:
                return out
            pos = {wi: i for i, wi in enumerate(keep_words)}
            zero = jnp.zeros(out.shape[1:], out.dtype)
            return jnp.stack([out[pos[wi]] if wi in pos else zero
                              for wi in range(record_words)])

        ring_ex = None
        if self._ring_fused_active():
            ring_ex = self._make_ring_exchange(num_rounds, collective_id)
        data_a2a = self._data_a2a(collective_id)

        def local_step(records, *maybe_buf):
            # records: columnar [W, n_local]
            if num_parts == 1 and num_rounds == 1 and mesh_size == 1:
                # degenerate exchange (single partition, single chip):
                # the slot/window/compact machinery is the identity here
                # — every record stays put — so skip its ~6 full-array
                # copies and run the fused tail on the batch directly
                # (the 1-chip bench's hot path; same spirit as
                # bucket_records' num_parts==1 short-circuit). The
                # pushdown/combine passes still run so outputs (and
                # wire accounting via ``incoming``) stay bit-identical
                # with the multi-chip paths.
                from sparkrdma_tpu.kernels.aggregate import (
                    combine_by_key_cols)
                from sparkrdma_tpu.kernels.sort import sort_by_lead_cols

                n_local = records.shape[1]
                keep = (row_filter(records) if row_filter is not None
                        else None)
                out = (records if kw_idx is None
                       else jnp.take(records, kw_idx, axis=0))
                if combine:
                    # map-side == reduce-side here (single source), so
                    # one combine pass subsumes both the filter compact
                    # and the fused tail; dropped rows are just invalid
                    mode = self.sort_mode(out.shape[0])
                    valid = (keep if keep is not None
                             else jnp.ones((n_local,), bool))
                    out, total = combine_by_key_cols(
                        out, valid, self.conf.key_words, aggregator,
                        float_payload, wide=(mode == "wide"),
                        ride_words=self.conf.wide_sort_ride_words,
                        pack=(mode == "pack"))
                    wire = total
                    if out_capacity != n_local:
                        out = jnp.pad(
                            out, ((0, 0), (0, out_capacity - n_local)))
                else:
                    total = jnp.full((), n_local, jnp.int32)
                    if keep is not None:
                        # stable validity-lead compact: surviving rows
                        # to the front in arrival order, zeroed tail
                        mode = self.sort_mode(out.shape[0])
                        out = sort_by_lead_cols(
                            out, (~keep).astype(jnp.uint32), mode)
                        total = jnp.sum(keep).astype(jnp.int32)
                        live = (jnp.arange(n_local) < total)[None, :]
                        out = out * live.astype(out.dtype)
                    wire = total
                    if out_capacity != n_local:
                        out = jnp.pad(
                            out, ((0, 0), (0, out_capacity - n_local)))
                    out, total = self._fuse_tail(out, total, out_capacity,
                                                 sort_key_words,
                                                 aggregator,
                                                 float_payload, tight_out)
                incoming = wire.reshape(1, 1).astype(jnp.int32)
                out = rewiden(out)
                if maybe_buf:
                    out = lax.dynamic_update_slice(maybe_buf[0], out,
                                                   (0, 0))
                return out, total[None], incoming[None]

            # --- map side: bucket into per-partition runs (plus the
            # --- optional pre-exchange reduction: filter / projection /
            # --- map-side combine) ------------------------------------
            sr, counts, offs = self._map_side(
                records, partitioner, num_parts, combine, aggregator,
                float_payload, row_filter, kw_idx)

            # --- size exchange (metadata fetch analogue) --------------
            dev_counts = _device_partition_counts(
                counts, num_parts, mesh_size, ax)          # [mesh, ppd]

            if ring_ex is not None:
                # --- fused data rounds (one kernel, all rounds) -------
                # dest-major fills: [mesh, ppd, W, C] per round, NO
                # reshape/transpose staging pass — the stack below is a
                # leading-axis concat, and the kernel DMAs row d of each
                # round straight to device d with round r+1 posted while
                # round r completes (double-buffered semaphore banks).
                round_slots = [
                    fill_round_slots_dest_major(
                        sr, counts, offs, num_parts, mesh_size,
                        capacity, r)[0]
                    for r in range(num_rounds)
                ]
                slots = jnp.stack(round_slots)  # [R, mesh, ppd, W, C]
                # the size exchange rides a one-column prefix lane of
                # round 0's payload instead of a separate all_to_all
                # serialized ahead of the data: lane[0, d, q] carries
                # dev_counts[d, q], so the counts land with (not before)
                # the first payload DMA.
                lane = jnp.zeros(
                    (num_rounds, mesh_size, ppd, w_eff, 1),
                    slots.dtype)
                lane = lane.at[0, :, :, 0, 0].set(
                    dev_counts.astype(slots.dtype))
                recv_all = ring_ex(
                    jnp.concatenate([lane, slots], axis=4)
                )                           # [R, mesh, ppd, W, C+1]
                # recv_all[0, s, q, 0, 0] = sender s's dev_counts[my, q]
                # — exactly all_to_all(dev_counts)[s, q]
                incoming = recv_all[0, :, :, 0, 0].astype(jnp.int32)
                data = recv_all[:, :, :, :, 1:]  # [R, mesh, ppd, W, C]
                # stream order (w; q, s, r, c): axes (r, s, q, w, c) ->
                # (w, q, s, r, c)
                stream = data.transpose(3, 2, 1, 0, 4).reshape(
                    w_eff,
                    ppd * mesh_size * num_rounds * capacity,
                )
            else:
                incoming = lax.all_to_all(
                    dev_counts, ax, split_axis=0, concat_axis=0,
                    tiled=True)                             # [mesh, ppd]

                # --- data rounds --------------------------------------
                recv_rounds = []
                for r in range(num_rounds):
                    slots, _ = fill_round_slots(
                        sr, counts, offs, num_parts, capacity, r
                    )                                       # [W, P, C]
                    # group per destination device: [mesh, ppd, W, C]
                    # (partition p = q*mesh + d lives on device d,
                    # local q)
                    slots = slots.reshape(w_eff, ppd, mesh_size,
                                          capacity).transpose(2, 1, 0, 3)
                    # dest-major [mesh, ppd, W, C]: the configured
                    # transport moves row d to device d (xla:
                    # lax.all_to_all; pallas_ring: one-sided remote-DMA
                    # descriptors)
                    recv = data_a2a(slots)              # [mesh, ppd, W, C]
                    recv_rounds.append(recv)

                # data[s, q, r, :, c] = round r's c-th record from
                # source s for local partition q.
                data = jnp.stack(recv_rounds,
                                 axis=2)       # [mesh, ppd, rounds, W, C]
                stream = data.transpose(3, 1, 0, 2, 4).reshape(
                    w_eff,
                    ppd * mesh_size * num_rounds * capacity,
                )

            # --- reduce side: compact the round-chunked stream --------
            # Group the output stream by local partition first, then
            # source (a reduce task consumes ITS partition from every
            # map output in map order), then round. Each (q, s, r)
            # chunk is prefix-valid with length
            # clip(incoming[s, q] - r*capacity, 0, capacity).
            # chunk lengths [ppd*mesh*rounds] in stream order (q, s, r)
            inc = incoming.T.reshape(ppd * mesh_size, 1)    # [q*s, 1]
            r_ix = jnp.arange(num_rounds, dtype=jnp.int32)[None, :]
            chunk_len = jnp.clip(inc - r_ix * capacity, 0, capacity)
            out, total = compact_segments(
                stream, chunk_len.reshape(-1), out_capacity
            )
            out, total = self._fuse_tail(out, total, out_capacity,
                                         sort_key_words, aggregator,
                                         float_payload, tight_out)
            out = rewiden(out)
            if maybe_buf:
                # full-extent write-through into the donated pooled
                # buffer: same shape in and out, so XLA aliases the pages
                # (registered-buffer reuse)
                out = lax.dynamic_update_slice(maybe_buf[0], out, (0, 0))
            return out, total[None], incoming[None]

        in_specs = [P(None, ax)]
        if donate_out:
            in_specs.append(P(None, ax))
        return jax.jit(
            shard_map(
                local_step,
                mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(None, ax), P(ax), P(ax)),
                # VMA inference cannot type pallas kernels (ring
                # transport's device-id arithmetic, merge-sort's grid
                # indices); pure-XLA programs keep the check
                check_vma=(self.transport() == "xla"
                           and not self._uses_fast_sort(
                               out_capacity, sort_key_words, aggregator)),
            ),
            donate_argnums=((1,) if donate_out else ()),
        )

    # ------------------------------------------------------------------
    # phase 2, regime B: streaming round chunks (bounded in-flight)
    # ------------------------------------------------------------------
    def _build_prep(self, num_parts: int, record_words: int,
                    partitioner: Callable,
                    combine: bool = False,
                    aggregator: str = "",
                    float_payload: bool = False,
                    row_filter: Optional[Callable] = None,
                    keep_words: Optional[Tuple[int, ...]] = None
                    ) -> Callable:
        """records -> (bucketed, counts, offsets, incoming, totals).

        The streaming regime's pre-exchange reduction lives HERE: the
        prep's counts (and the size exchange they feed) are
        post-filter/post-combine, so every later chunk program just
        moves the compacted, possibly narrower (projected) stream —
        chunk/fold/tail need no combine awareness beyond their width."""
        mesh_size = self.mesh_size
        ax = self.axis_name
        kw_idx = (jnp.asarray(keep_words, jnp.int32)
                  if keep_words is not None else None)

        def local_prep(records):
            sr, counts, offs = self._map_side(
                records, partitioner, num_parts, combine, aggregator,
                float_payload, row_filter, kw_idx)
            dev_counts = _device_partition_counts(
                counts, num_parts, mesh_size, ax)
            incoming = lax.all_to_all(
                dev_counts, ax, split_axis=0, concat_axis=0, tiled=True)
            total = jnp.sum(incoming).astype(jnp.int32)
            return sr, counts, offs, incoming[None], total[None]

        return jax.jit(shard_map(
            local_prep, mesh=self.mesh,
            in_specs=(P(None, ax),),
            out_specs=(P(None, ax), P(ax), P(ax), P(ax), P(ax)),
            check_vma=(self.transport() == "xla"),
        ))

    def _build_chunk(self, num_parts: int, capacity: int, rounds_per: int,
                     record_words: int,
                     collective_id: int = 7) -> Callable:
        """(bucketed, counts, offsets, r0, recv_buf) -> filled recv_buf.

        Runs ``rounds_per`` rounds starting at traced round index ``r0``;
        one compiled program serves every chunk of the stream (r0 is a
        device scalar, and rounds past the true end just move zeros).
        ``recv_buf`` is pool-served and donated; the full-extent
        write-through aliases it to the output. Per-device output layout:
        ``[rounds_per, mesh, ppd, W, C]``.
        """
        mesh_size = self.mesh_size
        ppd = num_parts // mesh_size
        ax = self.axis_name
        ring_ex = None
        if self._ring_fused_active():
            ring_ex = self._make_ring_exchange(rounds_per, collective_id)
        data_a2a = self._data_a2a(collective_id)

        def local_chunk(sr, counts, offs, r0, recv_buf):
            if ring_ex is not None:
                # fused: dest-major fills stacked on a leading round
                # axis (no reshape/transpose staging), all rounds of the
                # chunk moved by one double-buffered kernel. No counts
                # lane here — the streaming regime's prep already did
                # the size exchange.
                chunk = ring_ex(jnp.stack([
                    fill_round_slots_dest_major(
                        sr, counts, offs, num_parts, mesh_size,
                        capacity, r0[0] + j)[0]
                    for j in range(rounds_per)
                ]))                       # [rounds_per, mesh, ppd, W, C]
            else:
                recvs = []
                for j in range(rounds_per):
                    slots, _ = fill_round_slots(
                        sr, counts, offs, num_parts, capacity, r0[0] + j)
                    slots = slots.reshape(record_words, ppd, mesh_size,
                                          capacity).transpose(2, 1, 0, 3)
                    recvs.append(data_a2a(slots))   # [mesh, ppd, W, C]
                chunk = jnp.stack(recvs,
                                  axis=0)  # [rounds_per, mesh, ppd, W, C]
            return lax.dynamic_update_slice(
                recv_buf, chunk, (0, 0, 0, 0, 0))

        return jax.jit(shard_map(
            local_chunk, mesh=self.mesh,
            in_specs=(P(None, ax), P(ax), P(ax), P(), P(None, ax)),
            out_specs=P(None, ax),
            check_vma=False,   # r0 is replicated data; VMA can't type it
        ), donate_argnums=(4,))

    def _build_fold(self, num_parts: int, capacity: int, rounds_per: int,
                    total_rounds: int, out_capacity: int,
                    record_words: int, first: bool) -> Callable:
        """(acc, recv_chunk, incoming, chunk_idx) -> acc with the chunk's
        segments written at their exact stream offsets.

        ``acc`` is donated (in-place accumulate). Segment (q, s, r) of the
        output stream starts at the prefix sum of all earlier segments'
        valid lengths — computed on device from ``incoming``. Writes are
        read-blend-write over each [W, C] window so a segment's zero tail
        never clobbers neighbours written by other chunks (unlike the
        fused path's ascending-repair trick, chunk arrival order is not
        stream order).
        """
        mesh_size = self.mesh_size
        ppd = num_parts // mesh_size
        w = record_words
        cap = capacity

        def local_fold(acc, recv, incoming, cidx):
            # acc: [W, out_capacity + cap] — the +cap head-room guarantees
            # no dynamic_update_slice ever clamps (a clamped window would
            # shift backward over valid data); the tail program slices it
            # recv: [rounds_per, mesh, ppd, W, C]
            # incoming: [1, mesh, ppd] (this device's row)
            inc = incoming[0]                          # [mesh, ppd]
            # stream-order segment lengths for ALL rounds: index (q, s, r)
            r_ix = jnp.arange(total_rounds, dtype=jnp.int32)
            seg_len = jnp.clip(
                inc.T[:, :, None] - r_ix[None, None, :] * cap, 0, cap
            )                                          # [ppd, mesh, R]
            flat_len = seg_len.reshape(-1)
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(flat_len)[:-1].astype(jnp.int32)]
            ).reshape(ppd, mesh_size, total_rounds)
            col = jnp.arange(cap, dtype=jnp.int32)[None, :]
            if first:
                # data-dependent zeroing (not zeros_like) keeps acc's
                # varying-manual-axes type intact for the fori_loop carry
                # and lets XLA alias the donated pages
                acc = acc & jnp.uint32(0)

            # One blend-write per (q, s, j) segment. Small geometries
            # unroll statically (constant-folded indices, the hot default
            # path); large ones use a device loop so program size is O(1)
            # in mesh size (round 1+2 advisors both flagged the unrolled
            # form: ppd*mesh*rounds_per serialized bodies per chunk
            # program). The writes are serially dependent either way —
            # neighbouring segments share window columns.
            zero = jnp.zeros((), jnp.int32)
            n_segs = ppd * mesh_size * rounds_per

            def blend_one(t, acc):
                q = t // (mesh_size * rounds_per)
                rem = t % (mesh_size * rounds_per)
                s = rem // rounds_per
                j = rem % rounds_per
                r = cidx[0] * rounds_per + j
                seg = lax.dynamic_slice(
                    recv, (j, s, q, zero, zero), (1, 1, 1, w, cap)
                ).reshape(w, cap)
                inc_sq = lax.dynamic_slice(inc, (s, q), (1, 1))[0, 0]
                ln = jnp.clip(inc_sq - r * cap, 0, cap)
                rc = jnp.minimum(r, total_rounds - 1)
                start_qsr = lax.dynamic_slice(
                    starts, (q, s, rc), (1, 1, 1))[0, 0, 0]
                dst = jnp.where(r < total_rounds, start_qsr,
                                acc.shape[1] - cap)  # parked write, len 0
                window = lax.dynamic_slice(acc, (0, dst), (w, cap))
                blended = jnp.where(col < ln, seg, window)
                return lax.dynamic_update_slice(acc, blended, (0, dst))

            if n_segs <= _UNROLL_LIMIT:
                for t in range(n_segs):
                    acc = blend_one(jnp.int32(t), acc)
            else:
                acc = lax.fori_loop(0, n_segs, blend_one, acc)
            # tiny completion token: an undonated output the host can
            # block on for in-flight pacing (acc itself is donated into
            # the NEXT fold, so its handle dies before the host would
            # wait on it)
            token = acc[:1, :1] + jnp.uint32(0)
            return acc, token

        ax = self.axis_name
        return jax.jit(shard_map(
            local_fold, mesh=self.mesh,
            in_specs=(P(None, ax), P(None, ax), P(ax), P()),
            out_specs=(P(None, ax), P(None, ax)),
            check_vma=False,
        ), donate_argnums=(0,))

    def _build_tail(self, out_capacity: int, record_words: int,
                    sort_key_words: int, aggregator: str,
                    float_payload: bool,
                    full_words: Optional[int] = None,
                    keep_words: Optional[Tuple[int, ...]] = None
                    ) -> Callable:
        """(acc, totals) -> (out, totals): strip the accumulator's
        head-room column band, then apply optional sort/aggregation.
        Under a projection pushdown (``keep_words``) the accumulator is
        the narrow wire width; the tail re-widens to ``full_words``
        with zero-filled dropped payload words (static stack, no
        scatter)."""
        ax = self.axis_name
        fw = full_words if full_words is not None else record_words
        pos = ({wi: i for i, wi in enumerate(keep_words)}
               if keep_words is not None else None)

        def local_tail(acc, total):
            out = acc[:, :out_capacity]
            out, t = self._fuse_tail(out, total[0], out_capacity,
                                     sort_key_words, aggregator,
                                     float_payload)
            if pos is not None:
                zero = jnp.zeros(out.shape[1:], out.dtype)
                out = jnp.stack([out[pos[wi]] if wi in pos else zero
                                 for wi in range(fw)])
            return out, t[None]

        return jax.jit(shard_map(
            local_tail, mesh=self.mesh,
            in_specs=(P(None, ax), P(ax)),
            out_specs=(P(None, ax), P(ax)),
            check_vma=not self._uses_fast_sort(out_capacity,
                                               sort_key_words, aggregator),
        ))

    def _exchange_streaming(self, records, partitioner, plan, num_parts,
                            sort_key_words, aggregator, float_payload,
                            shuffle_id=-1, combine=False, row_filter=None,
                            keep_words=None):
        """Regime B driver: prep, paced round chunks, folds, tail."""
        conf = self.conf
        w = records.shape[0]
        # projection pushdown: everything downstream of prep moves (and
        # folds) the narrow wire width; the tail re-widens
        w_eff = len(keep_words) if keep_words is not None else w
        mesh_size = self.mesh_size
        ppd = num_parts // mesh_size
        cap = plan.capacity
        F = conf.max_rounds_in_flight
        n_chunks = math.ceil(plan.num_rounds / F)
        total_rounds = n_chunks * F
        pkey = getattr(partitioner, "cache_key", id(partitioner))
        fkey = (getattr(row_filter, "cache_key", id(row_filter))
                if row_filter is not None else None)

        def cached(key, builder):
            fn = self._exec_cache.get(key)
            if fn is None:
                fn = builder()
                self._exec_cache[key] = fn
            return fn

        from sparkrdma_tpu.exchange.ring import derive_collective_id

        prep = cached(("prep", num_parts, w, pkey, fkey, keep_words,
                       combine, aggregator, float_payload),
                      lambda: self._build_prep(
                          num_parts, w, partitioner, combine=combine,
                          aggregator=aggregator,
                          float_payload=float_payload,
                          row_filter=row_filter, keep_words=keep_words))
        # tenant folded in: two tenants' identically-shaped streaming
        # exchanges must derive distinct collective ids (and programs)
        chunk_key = ("chunk", self.tenant, num_parts, cap, F, w_eff)
        chunk_fn = cached(chunk_key,
                          lambda: self._build_chunk(
                              num_parts, cap, F, w_eff,
                              collective_id=derive_collective_id(chunk_key)))

        self.timeline.begin("stream:prep", chunks=n_chunks,
                            rounds=plan.num_rounds)
        sr, counts, offs, incoming, totals = prep(records)
        dispatches = 1
        self.timeline.end("stream:prep")

        # +cap head-room per device so fold windows never clamp
        acc_shape = (w_eff, mesh_size * (plan.out_capacity + cap))
        out_sharding = NamedSharding(self.mesh, P(None, self.axis_name))
        recv_shape = (F, mesh_size * mesh_size, ppd, w_eff, cap)
        # recv chunks are sharded over their *destination* axis; the
        # global layout is [F, dest_mesh * src_mesh, ppd, W, C]
        recv_sharding = out_sharding

        def get_buf(shape, sharding):
            if self.pool is not None:
                return self._get_buf(shape, sharding)
            # pool-less fallback: cache the compiled zero-alloc per
            # geometry (a fresh jit per call would recompile once per
            # chunk per exchange — round-2 advisor finding)
            zkey = ("zeros", shape, sharding)
            zfn = self._exec_cache.get(zkey)
            if zfn is None:
                zfn = jax.jit(lambda: jnp.zeros(shape, jnp.uint32),
                              out_shardings=sharding)
                self._exec_cache[zkey] = zfn
            return zfn()

        from sparkrdma_tpu import faults as _faults
        from sparkrdma_tpu.exchange.errors import FetchFailedError

        acc = get_buf(acc_shape, out_sharding)
        tl = self.timeline
        in_flight = []   # completion tokens of dispatched chunks
        for j in range(n_chunks):
            if _faults.fire("exchange.stream_round") == "fail":
                # a mid-stream failure abandons the whole exchange (the
                # accumulator holds partial rounds); the reader's retry
                # loop restarts from the still-published map outputs
                self.metrics.counter("exchange.faults").inc()
                raise FetchFailedError(
                    shuffle_id,
                    f"injected fault (fault_spec: exchange.stream_round, "
                    f"chunk {j})")
            if len(in_flight) >= conf.queue_depth:
                # the recvQueueDepth throttle: block on the oldest
                # outstanding chunk before admitting a new one. This is
                # THE blocking wait of the streaming regime, so it is
                # watchdog-armed: a wedged collective fires a journaled
                # stall record instead of hanging silently.
                self.metrics.counter("exchange.queue_blocks").inc()
                tl.begin("queue:block", chunk=j)
                with self.watchdog.armed(
                        "queue:block", shuffle=shuffle_id, chunk=j,
                        queue=len(in_flight),
                        pool_high_water=(self.pool.outstanding_high_water
                                         if self.pool is not None else 0)):
                    if self.block_hook is not None:
                        self.block_hook(j)
                    jax.block_until_ready(in_flight.pop(0))
                tl.end("queue:block", chunk=j)
            self.metrics.counter("exchange.stream_chunks").inc()
            tl.begin("chunk", chunk=j)
            recv_buf = get_buf(recv_shape, recv_sharding)
            r0 = jnp.full((1,), j * F, jnp.int32)
            recv = chunk_fn(sr, counts, offs, r0, recv_buf)
            tl.event("chunk:dispatch", chunk=j, rounds=F)
            if self._ring_fused_active():
                # structural annotations (see exchange()): the chunk's F
                # rounds run inside one fused kernel
                for jr in range(F):
                    tl.begin("ring:round", round=j * F + jr)
                    tl.end("ring:round", round=j * F + jr)
            fold = cached(
                ("fold", num_parts, cap, F, total_rounds,
                 plan.out_capacity, w_eff, j == 0),
                lambda: self._build_fold(num_parts, cap, F, total_rounds,
                                         plan.out_capacity, w_eff,
                                         j == 0))
            cidx = jnp.full((1,), j, jnp.int32)
            acc, token = fold(acc, recv, incoming, cidx)
            dispatches += 2
            in_flight.append(token)
            tl.event("chunk:fold", chunk=j)
            tl.end("chunk", chunk=j)
            tl.counter("chunks.outstanding", len(in_flight))
            if self.pool is not None:
                # recv is consumed by the fold already enqueued; returning
                # it now lets chunk j+1 donate the same pages (the runtime
                # sequences the rewrite after the fold's read)
                self._put_buf(recv, recv_sharding)
        tail = cached(("tail", plan.out_capacity, w_eff, sort_key_words,
                       aggregator, float_payload, w, keep_words),
                      lambda: self._build_tail(
                          plan.out_capacity, w_eff, sort_key_words,
                          aggregator, float_payload,
                          full_words=w, keep_words=keep_words))
        out, totals = tail(acc, totals)
        dispatches += 1
        tl.event("stream:tail")
        if self.pool is not None:
            # the accumulator is free once the (dispatched) tail read it
            self._put_buf(acc, out_sharding)
        self.last_dispatches = dispatches
        self.metrics.counter("exchange.dispatches").inc(dispatches)
        return out, totals, incoming

    # ------------------------------------------------------------------
    # entry
    # ------------------------------------------------------------------
    def exchange(
        self,
        records: jax.Array,
        partitioner: Callable,
        plan: ShufflePlan,
        num_parts: Optional[int] = None,
        shuffle_id: int = -1,
        sort_key_words: int = 0,
        aggregator: str = "",
        float_payload: bool = False,
        row_filter: Optional[Callable] = None,
        keep_words: Optional[Tuple[int, ...]] = None,
        combine_hint: Optional[Tuple[bool, float]] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Run the planned exchange.

        Args:
          records: columnar global ``uint32[W, mesh*N_local]`` sharded
            over the record axis (``MeshRuntime.shard_records``), column
            groups ordered by source device.
          partitioner: jit-safe ``records -> int32[n]`` destination
            partition ids; must match the one used in :meth:`plan`.
          plan: output of :meth:`plan`.
          row_filter: predicate pushdown — jit-safe
            ``records -> bool[n]`` over FULL-width records; rows it
            drops never occupy a slot (they are invisible to the
            output, as if deleted before the shuffle). Give it a stable
            ``cache_key`` attribute or every call recompiles.
          keep_words: projection pushdown — strictly-increasing word
            indices to keep on the wire; must include every key word.
            Dropped payload words come back zero-filled in ``out``
            (the :class:`~sparkrdma_tpu.api.serde.RowSchema` of the
            caller tracks which columns are live).

        Returns ``(out, totals, incoming)``:
          - ``out``: columnar ``uint32[W, mesh*out_capacity]`` — device
            d's columns are
            its compacted received records (zero-padded tail);
          - ``totals``: ``int32[mesh]`` — valid record count per device;
          - ``incoming``: ``int32[mesh, mesh*ppd... ]`` flattened per-source
            counts table (observability; the received metadata).

        When the exchange owns a pool, ``out`` is recycled into the next
        same-geometry exchange (see module docstring: consume it first).

        When ``aggregator`` is set, the plan-time combine gate
        (:meth:`_combine_gate`, driven by ``conf.map_side_combine``)
        may additionally run the map-side combine before bucketing;
        outputs are bit-identical either way (the reduce-side combine
        still merges across sources), only wire bytes change —
        :meth:`wire_stats` reports the measured reduction. A map-side
        combine program that fails to build degrades through the same
        ladder as the transports (sticky combine-off retry, counted and
        journaled) when ``conf.combine_fallback`` is on.
        """
        # The plan's counts matrix is the source of truth for geometry —
        # a mismatched explicit num_parts would silently drop records in
        # bucket_records' fixed-length histogram.
        plan_parts = int(plan.counts.shape[1])
        if (num_parts is not None
                and num_parts * plan.split_factor != plan_parts):
            raise ValueError(
                f"num_parts {num_parts} != plan's {plan_parts} "
                f"(split_factor {plan.split_factor})"
            )
        num_parts = plan_parts
        if plan.split_factor > 1:
            # identical wrapping to the plan's count pass (same iota
            # cycling, same cache_key) — bucketing must agree with counts
            partitioner = split_partitioner(
                partitioner, plan_parts // plan.split_factor,
                plan.split_factor)
        if aggregator and aggregator not in ("sum", "min", "max"):
            raise ValueError(f"unsupported aggregator {aggregator!r}")
        w = records.shape[0]
        if keep_words is not None:
            keep_words = tuple(int(i) for i in keep_words)
            kw = self.conf.key_words
            if (len(keep_words) < kw
                    or keep_words[:kw] != tuple(range(kw))):
                raise ValueError(
                    f"keep_words must start with all {kw} key words")
            if any(b <= a for a, b in zip(keep_words, keep_words[1:])):
                raise ValueError("keep_words must be strictly increasing")
            if keep_words[-1] >= w:
                raise ValueError(
                    f"keep_words {keep_words} out of range for W={w}")
            if len(keep_words) == w:
                keep_words = None    # full width: not a projection
        self._last_wire = None
        self._last_wire_stats = {}
        self._maybe_inject_fault(shuffle_id)
        m = self.metrics
        m.counter("exchange.exchanges").inc()
        m.counter("exchange.rounds").inc(plan.num_rounds)
        m.counter("exchange.records").inc(plan.total_records)
        if row_filter is not None:
            m.counter("pushdown.filters").inc()
        if keep_words is not None:
            m.counter("pushdown.projections").inc()
        from sparkrdma_tpu.exchange.errors import FetchFailedError

        # attempt 0 runs whatever the combine gate decides; if the
        # map-side-combine program itself fails to build/trace, the
        # combine rung of the degradation ladder retries ONCE with
        # combine off (sticky). Injected fetch faults are real exchange
        # failures, not construction failures — they stay on the
        # reader's retry path, never this rung.
        for attempt in (0, 1):
            if combine_hint is not None and aggregator:
                # plan-time hoisted decision (plan_combine): no sampling
                # on the critical path; the sticky combine override and
                # the fallback rung still win over a stale hint
                use_combine, dup_ratio = combine_hint
                use_combine = bool(use_combine) \
                    and not self._combine_override
                self.metrics.counter(
                    "combine.gate_on" if use_combine
                    else "combine.gate_off").inc()
            else:
                # the gate's duplicate-key sampling is host work on the
                # exchange's critical path — timed so the attribution can
                # charge it to the combine phase
                self.timeline.begin("combine:gate")
                use_combine, dup_ratio = self._combine_gate(records,
                                                            aggregator)
                self.timeline.end("combine:gate")
            try:
                out, totals, incoming = self._dispatch(
                    records, partitioner, plan, num_parts, shuffle_id,
                    sort_key_words, aggregator, float_payload,
                    use_combine, row_filter, keep_words)
            except FetchFailedError:
                raise
            except Exception as exc:
                if (attempt == 0 and use_combine
                        and self.conf.combine_fallback):
                    self._degrade_combine(exc)
                    continue
                raise
            self._note_wire(records, incoming, use_combine,
                            row_filter is not None, keep_words, dup_ratio)
            return out, totals, incoming

    def _dispatch(self, records, partitioner, plan, num_parts, shuffle_id,
                  sort_key_words, aggregator, float_payload,
                  use_combine, row_filter, keep_words):
        """One dispatch attempt of the planned exchange (either regime);
        :meth:`exchange` wraps it in the combine-fallback rung."""
        if plan.num_rounds > self.conf.max_rounds_in_flight:
            return self._exchange_streaming(
                records, partitioner, plan, num_parts,
                sort_key_words, aggregator, float_payload,
                shuffle_id=shuffle_id, combine=use_combine,
                row_filter=row_filter, keep_words=keep_words)
        w = records.shape[0]
        # every device's output exactly full -> the fused sort can drop
        # its validity lead operand (static fact from the plan's counts;
        # any pre-exchange reduction shrinks totals below the plan, so
        # it forces the validity operand back on)
        owned = plan.counts.sum(axis=0)
        per_dev = np.array([owned[d::self.mesh_size].sum()
                            for d in range(self.mesh_size)])
        pushed = (use_combine or row_filter is not None
                  or keep_words is not None)
        tight = (not pushed
                 and bool((per_dev == plan.out_capacity).all()))
        fkey = (getattr(row_filter, "cache_key", id(row_filter))
                if row_filter is not None else None)
        # tenant folded in so two tenants' same-geometry fused programs
        # (and their derived collective ids) never alias
        key = (self.tenant, num_parts, plan.capacity, plan.num_rounds,
               plan.out_capacity,
               w, sort_key_words, aggregator, float_payload, tight,
               use_combine, fkey, keep_words,
               getattr(partitioner, "cache_key", id(partitioner)))
        donate = self.pool is not None
        fn = self._exec_cache.get(key)
        if fn is None:
            from sparkrdma_tpu.exchange.ring import derive_collective_id

            fn = self._build_exec(num_parts, plan.capacity, plan.num_rounds,
                                  plan.out_capacity, w, partitioner,
                                  sort_key_words, aggregator, float_payload,
                                  donate_out=donate, tight_out=tight,
                                  collective_id=derive_collective_id(key),
                                  combine=use_combine,
                                  row_filter=row_filter,
                                  keep_words=keep_words)
            self._exec_cache[key] = fn
        self.last_dispatches = 1
        self.metrics.counter("exchange.dispatches").inc()
        self.timeline.begin("exchange:fused", rounds=plan.num_rounds)
        if self._ring_fused_active():
            # structural annotations: the rounds run INSIDE one kernel
            # (that is the point), so per-round host spans cannot bracket
            # real device time — they record the round structure the
            # fused dispatch carries for trace tooling.
            for r in range(plan.num_rounds):
                self.timeline.begin("ring:round", round=r)
                self.timeline.end("ring:round", round=r)
        try:
            if donate:
                okey = (shuffle_id, key)
                sharding = NamedSharding(self.mesh, P(None, self.axis_name))
                prev = self._out_prev.pop(okey, None)
                if prev is not None:
                    self._put_buf(prev[0], prev[1])
                buf = self._get_buf(
                    (w, self.mesh_size * plan.out_capacity), sharding)
                out, totals, incoming = fn(records, buf)
                self._out_prev[okey] = (out, sharding)
                return out, totals, incoming
            return fn(records)
        finally:
            # closes even when the dispatch raises, so the span's
            # timeline stays balanced across retry attempts
            self.timeline.end("exchange:fused")

    def release_shuffle(self, shuffle_id: int) -> None:
        """Return a shuffle's recycled output buffers to the pool.

        The unregisterShuffle -> dispose path: after this, the shuffle's
        last outputs may be handed (and donated) to ANY later exchange,
        so callers must be done consuming them.
        """
        if self.pool is None:
            return
        for okey in [k for k in self._out_prev if k[0] == shuffle_id]:
            arr, sharding = self._out_prev.pop(okey)
            self._put_buf(arr, sharding)

    def release_all(self) -> None:
        """Return every recycled output buffer (session teardown — the
        per-tenant exchange dies with its session, so nothing may stay
        charged to the tenant's account)."""
        if self.pool is None:
            self._out_prev.clear()
            return
        while self._out_prev:
            _, (arr, sharding) = self._out_prev.popitem()
            self._put_buf(arr, sharding)

    def shuffle(
        self,
        records: jax.Array,
        partitioner: Callable,
        num_parts: Optional[int] = None,
        capacity: Optional[int] = None,
        shuffle_id: int = -1,
    ) -> Tuple[jax.Array, jax.Array, ShufflePlan]:
        """plan + exchange in one call. Returns ``(out, totals, plan)``.

        When ``conf.collect_shuffle_read_stats`` is on, each call adds an
        :class:`~sparkrdma_tpu.obs.stats.ExchangeRecord` to ``self.stats``
        (timed to completion via a hard barrier) — this is the stats path
        for exchanges driven WITHOUT a ShuffleManager, e.g. the ring /
        hierarchical transport benches. When constructed with a
        ``journal``, each call additionally emits a (sampled) journal
        span and feeds the window ``rollup`` — so those same standalone
        paths show up in ``shuffle_report.py`` / ``shuffle_top.py``.
        """
        plan = self.plan(records, partitioner, num_parts, capacity)
        journal_on = self.journal is not None and self.journal.enabled
        if not (self.stats.enabled or journal_on):
            out, totals, _ = self.exchange(records, partitioner, plan,
                                           num_parts, shuffle_id=shuffle_id)
            return out, totals, plan
        from sparkrdma_tpu.utils.stats import Timer, barrier

        with Timer() as t:
            out, totals, _ = self.exchange(records, partitioner, plan,
                                           num_parts, shuffle_id=shuffle_id)
            barrier(out, totals)
        if self.stats.enabled:
            self.stats.add(ExchangeRecord(
                shuffle_id=shuffle_id,
                plan_s=self.last_plan_s,
                exec_s=t.elapsed,
                total_records=plan.total_records,
                record_bytes=records.shape[0] * 4,
                num_rounds=plan.num_rounds,
                per_source_records=plan.counts.sum(axis=1),
            ))
        if journal_on:
            from sparkrdma_tpu.hbm.tiered_store import store_totals
            from sparkrdma_tpu.obs.journal import (ExchangeSpan,
                                                   next_span_id)
            span_id = next_span_id()
            st_spill, st_fetch, st_hits, st_sync = store_totals()
            span = ExchangeSpan(
                span_id=span_id,
                shuffle_id=shuffle_id,
                transport=self.transport(),
                rounds=plan.num_rounds,
                dispatches=self.last_dispatches,
                records=plan.total_records,
                record_bytes=records.shape[0] * 4,
                plan_s=self.last_plan_s,
                exchange_s=t.elapsed,
                sort_s=0.0,
                per_peer_records=[int(c) for c in plan.counts.sum(axis=1)],
                pool_high_water=(self.pool.outstanding_high_water
                                 if self.pool is not None else 0),
                process_index=self.identity[0],
                host_count=self.identity[1],
                events=self.timeline.drain(),
                store_spill_bytes=st_spill,
                store_fetch_bytes=st_fetch,
                store_prefetch_hits=st_hits,
                store_sync_fetches=st_sync,
                tenant=self.tenant,
                **self.wire_stats(),
            )
            # schema v12: job-trace coordinates of the active job/stage
            from sparkrdma_tpu.obs import trace as _trace
            tctx = _trace.current_trace()
            if tctx is not None:
                span.trace_id = tctx.trace_id
                span.job = tctx.job
                span.stage = tctx.stage
                span.stage_attempt = tctx.stage_attempt
            # schema v10: phase attribution + bottleneck verdict
            from sparkrdma_tpu.obs import critical_path
            critical_path.enrich(span, metrics=self.metrics)
            # feed the attribution back into the job's stage profile
            _trace.observe_active_span(span)
            weight = self.sampler.keep_weight(span_id, t.elapsed)
            if self.rollup is not None:
                self.rollup.observe(span, kept=weight > 0)
            if weight > 0:
                span.sample_weight = weight
                self.journal.emit(span)
            else:
                self.metrics.counter("journal.sampled_out").inc()
        return out, totals, plan


__all__ = ["ShuffleExchange", "ShufflePlan"]
