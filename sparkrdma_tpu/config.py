"""Typed configuration for the shuffle framework.

TPU-native equivalent of SparkRDMA's ``RdmaShuffleConf``
(src/main/scala/org/apache/spark/shuffle/rdma/RdmaShuffleConf.scala), which
exposes typed accessors over ``spark.shuffle.rdma.*`` keys. The knobs that
survive the move to TPU keep their reference meaning:

===============================  ==============================================
reference key                    here
===============================  ==============================================
``maxAggBlock`` (~2MB)           ``slot_records`` — capacity of one exchange
                                 slot per (src, dst) pair per round. The
                                 reference aggregates adjacent blocks into one
                                 RDMA READ up to this size; we size the padded
                                 all_to_all slot the same way.
bytes-in-flight throttle         ``max_rounds_in_flight`` — how many exchange
                                 rounds may be dispatched before blocking.
``preAllocateBuffers``           ``prealloc`` — "records:count,..." spec for
 ("size:count,...")              warm slot-pool classes.
``recvQueueDepth`` /             ``queue_depth`` — reader result-queue bound
``sendQueueDepth``               (completed slots awaiting consumption).
``collectShuffleReadStats``      ``collect_shuffle_read_stats``; the
                                 machine-readable superset is
                                 ``metrics_sink`` — a JSON-lines exchange
                                 journal (sparkrdma_tpu.obs).
``maxConnectionAttempts``        ``max_retry_attempts`` — job-level retries
                                 from persisted map outputs.
``useOdp``                       dropped (no MR registration on TPU); the
                                 moral analogue ``spill_to_host`` gates the
                                 host staging pool.
``cpuList``                      dropped (no CQ polling threads to pin).
===============================  ==============================================
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

#: Number of 32-bit words a record occupies in exchange buffers by default:
#: 2 key words (lexicographic uint64 as hi/lo) + 2 payload words.
DEFAULT_KEY_WORDS = 2
DEFAULT_VAL_WORDS = 2



def _parse_prealloc(spec: str) -> Dict[int, int]:
    """Parse a ``"records:count,records:count"`` prealloc spec.

    Mirrors RdmaShuffleConf's parsing of ``spark.shuffle.rdma
    .preAllocateBuffers`` ("size:count,...") used by RdmaBufferManager's
    startup preallocation loop.
    """
    out: Dict[int, int] = {}
    spec = spec.strip()
    if not spec:
        return out
    for item in spec.split(","):
        size_s, _, count_s = item.partition(":")
        size, count = int(size_s), int(count_s)
        if size <= 0 or count <= 0:
            raise ValueError(f"invalid prealloc entry {item!r}")
        out[size] = out.get(size, 0) + count
    return out


@dataclasses.dataclass(frozen=True)
class ShuffleConf:
    """All knobs for a shuffle job. Frozen so it can be a static jit arg."""

    # --- exchange geometry (maxAggBlock / bytes-in-flight analogues) ---
    slot_records: int = 4096          # records per (src,dst) slot per round
    max_rounds: int = 64              # static upper bound on streaming rounds
    #: rounds fused into ONE compiled exchange program; shuffles needing
    #: more rounds stream them as separate chunk programs of this many
    #: rounds each (the fetcher's bytes-in-flight dispatch granularity)
    max_rounds_in_flight: int = 2
    #: outstanding streaming chunks before the host blocks on the oldest
    #: (recvQueueDepth: bounds live recv-slot memory to queue_depth chunks)
    queue_depth: int = 8

    # --- record geometry ---
    key_words: int = DEFAULT_KEY_WORDS   # uint32 words per key
    val_words: int = DEFAULT_VAL_WORDS   # uint32 words per payload

    # --- slot pool (RdmaBufferManager analogues) ---
    prealloc: str = ""                # "records:count,..." warm classes
    max_slot_records: int = 1 << 22   # refuse larger single allocations

    # --- transport backend ---
    #: "xla" = lax.all_to_all (compiler-scheduled, default);
    #: "pallas_ring" = explicit one-sided remote-DMA kernel
    #: (exchange/ring.py, the RdmaChannel analogue);
    #: "hierarchical" = two-stage intra-host (ICI) + inter-host (DCN)
    #: all_to_all (exchange/hierarchical.py, the multi-slice transport)
    transport: str = "xla"
    #: pallas_ring only: fuse ALL exchange rounds into one multi-round
    #: kernel (exchange/ring.py make_ring_exchange) — double-buffered
    #: semaphore banks overlap round r+1's remote DMAs with round r's
    #: completion, the barrier handshake runs once per exchange, and the
    #: size exchange rides a prefix lane of round 0's payload. Off =
    #: one single-round kernel dispatch per round (the pre-round-8
    #: behaviour; keep as an A/B lever and a fallback if a geometry
    #: trips the fused lowering).
    ring_fused: bool = True
    #: host-group count for the hierarchical transport; 0 = auto from the
    #: mesh's process set (devices per host = mesh size / processes)
    hierarchy_hosts: int = 0
    #: geometry size-class policy: "pow2" (default — few distinct
    #: compiled geometries, up to 2x slot padding) or "fine" (top-4-bit
    #: classes, <=6.25% padding, ~16x more potential geometries).
    #: Use "fine" for stable-geometry workloads (a bench or production
    #: job repeating one shuffle shape) where padding costs real passes;
    #: keep "pow2" when shuffle sizes vary call to call, or every
    #: slightly-different size recompiles its own program.
    geometry_classes: str = "pow2"

    # --- map-side combine + pushdown (pre-exchange reduction) ---
    #: map-side combine policy for aggregator shuffles: "auto" (default
    #: — a cheap sampled duplicate-ratio estimate gates it per shuffle),
    #: "on" (always pre-combine), "off" (reader-side combine only, the
    #: pre-PR-15 behaviour). When active, each device sorts its batch by
    #: (dest partition, key) and segment-reduces duplicates BEFORE
    #: bucketing, so each (partition, key) pair crosses the fabric once;
    #: the ragged size-exchange lane already carries the shrunken
    #: per-destination counts, so no wire-protocol change. Outputs are
    #: bit-identical with the pass on or off (integer/min/max ops;
    #: float32 sums reassociate — same caveat as any map-side combiner).
    map_side_combine: str = "auto"
    #: rows sampled (host-side, from the first addressable shard) for
    #: the "auto" gate's duplicate-ratio estimate. 0 = skip sampling and
    #: treat "auto" as "on" (the estimate is also journaled per span so
    #: ``--doctor`` can flag high-duplication shuffles running without
    #: combine).
    combine_sample_rows: int = 1024
    #: minimum sampled duplicate ratio (1 - unique/sample, in [0, 1])
    #: at which the "auto" gate turns combine on — below it the sort +
    #: segmented scan would cost more than the bytes it saves.
    combine_min_dup_ratio: float = 0.25
    #: graceful degradation: when True, a map-side-combine program that
    #: fails to build falls back to combine-off for the rest of the
    #: process (sticky, counted as ``degrade.combine``) instead of
    #: failing the job — the PR-5 ladder's combine rung.
    combine_fallback: bool = True

    # --- query planner (plan/ package) rewrite gates ---
    #: sink plan-level ``filter``/``select`` nodes through
    #: layout-preserving nodes into the earliest downstream exchange's
    #: ``row_filter``/``keep_words`` (and hoist the combine gate's
    #: duplicate-ratio sampling to plan time). Off = the naive executor
    #: materializes each filter/select eagerly, so dropped rows still
    #: ride the wire as null-key filler. Results are bit-identical
    #: either way; only wire bytes and pass count change.
    plan_pushdown: bool = True
    #: deduplicate identical exchanges across a plan (and across plans
    #: sharing one executor): the second node with the same canonical
    #: exchange fingerprint adopts the first's output instead of
    #: re-exchanging; with a segment store configured the output is
    #: also persisted via ``checkpoint_segments`` so a restarted
    #: executor resumes it via ``resume_segments``. Fingerprints embed
    #: each source's content digest (or a process-unique object token
    #: when no digest exists — see plan/nodes.py), so the caches can
    #: only ever adopt bit-identical data; the one exception is a NAMED
    #: digest-less source, whose name is a stability contract
    #: (``PlanExecutor.invalidate_reuse()`` is the escape hatch).
    plan_reuse: bool = True
    #: replace a dimension-lookup shuffle join with a broadcast join
    #: when the build side's plan-time row count fits
    #: ``plan_broadcast_records``: the small side replicates to every
    #: device and NEITHER side exchanges. Construction failure (e.g. a
    #: non-unique build key) degrades back to the shuffle join through
    #: the standard ladder (sticky, counted as
    #: ``degrade.broadcast_join``).
    plan_broadcast_join: bool = True
    #: stage-overlap scheduling: the plan executor starts stage k+1's
    #: host encode (the api/pipeline.py chunked-overlap path) on a
    #: background worker while stage k's exchange tail drains.
    plan_overlap: bool = True
    #: broadcast-join eligibility threshold: maximum build-side row
    #: count that may replicate to every device. 0 disables broadcast
    #: selection even when ``plan_broadcast_join`` is on.
    plan_broadcast_records: int = 4096

    # --- reduce-side sort ---
    #: keep arrival order within equal keys on key-ordered reads, and
    #: within a partition on unordered reads. Spark's sortByKey and
    #: repartition contracts do NOT promise this (so the default rides
    #: the cheaper unstable networks — the reduce-side key sort, and
    #: the map-side bucket sort of unordered, unaggregated reads); turn
    #: on for callers that layered meaning onto arrival order. Wide
    #: records (the key+index path) are stable either way.
    stable_key_sort: bool = False

    #: payload width (in uint32 words) at or above which key-ordering
    #: sorts use the WIDE-RECORD path: ride ``wide_sort_ride_words``
    #: payload words through the sort, place the rest with one gather
    #: pass. Measured v5e crossover (16M records): monolithic variadic
    #: sort costs ~15.3ms/word up to ~13 operands then turns superlinear
    #: (13 ops: 202ms, 25 ops: 630ms); a gather pass costs 143ms fixed
    #: + 15.3ms/word. Riding everything therefore WINS until the
    #: superlinear zone eats the gather's fixed cost — at ~22 total
    #: operands — so the default switches at 20 payload words. The wide
    #: path also caps compile time: its sort carries 13 operands, not
    #: 25 (the compile times once quoted here were measured through a
    #: deployment that no longer exists; not measured on the current
    #: chip). 0 disables.
    wide_sort_min_payload: int = 20
    #: payload words that RIDE the wide sort as value operands (the rest
    #: are placed by one gather pass): 10 + 2 keys + index = 13
    #: operands, the measured knee of the sort-cost curve.
    wide_sort_ride_words: int = 10
    #: payload width (words) at or above which full-record sorts use u64
    #: OPERAND PACKING (kernels/sort.py §packed_lexsort_cols): pairs of
    #: u32 words ride as one u64 operand, halving operand count at equal
    #: bytes — the whole record rides, no gather pass at all.
    #:
    #: Round-5 v5e measurements (three layers, each overturning the
    #: last — scripts/profile_sweep.py ab + bench.py A/B hooks):
    #: - standalone same-process, 16M records: packed wins at both
    #:   bench widths (W=25: 620ms vs 625 mono vs 805 ride+gather;
    #:   W=13: 387 vs 439);
    #: - FUSED full pipeline: the standalone wins do NOT survive fusion
    #:   — plain monolithic beats packed at W=13 (3.74 vs 3.57 GB/s)
    #:   AND at W=25 (3.88 vs 3.63, back-to-back), both beating
    #:   round-4's ride/gather default (2.69) by far;
    #: - compile time favored packing at W=25 (measured through a
    #:   deployment that no longer exists; not measured on the current
    #:   chip).
    #:
    #: DEFAULT POLICY: 20 — wide records pack by default, because the
    #: default serves arbitrary user verbs at arbitrary widths, where
    #: the bounded operand count caps both compile time (the round-3
    #: 40-minute 25-operand walls) and the deep superlinear zone, at a
    #: measured ~6% runtime cost at W=25. A stable, benched geometry
    #: should opt into the monolithic tail (pack_sort_min_payload
    #: above the payload width) exactly as bench.py does — same
    #: opt-in philosophy as geometry_classes="fine". Takes precedence
    #: over the wide ride/gather path when both trigger; 0 disables.
    pack_sort_min_payload: int = 20

    # --- observability ---
    collect_shuffle_read_stats: bool = False
    #: exchange-journal sink: a filesystem path receiving one JSON line
    #: per executed shuffle read (schema: sparkrdma_tpu.obs.journal).
    #: Empty = journal off. Enabling the journal also enables the
    #: metrics registry, independent of collect_shuffle_read_stats.
    #: Multi-host: a literal ``{process}`` in the path expands to the
    #: JAX process index at manager construction, so every host writes
    #: its own journal ("/logs/journal-{process}.jsonl"); feed all of
    #: them to the report/trace CLIs for a cross-host merge. Aggregate
    #: offline with ``python scripts/shuffle_report.py <sink>...``;
    #: export a Perfetto-viewable Chrome trace with
    #: ``python scripts/shuffle_trace.py <sink>...``.
    metrics_sink: str = ""
    #: stall watchdog (sparkrdma_tpu.obs.watchdog): a streaming-exchange
    #: blocking wait exceeding this many seconds logs + journals a
    #: ``stall`` record with the full in-flight state (shuffle id, chunk
    #: index, queue occupancy, pool high-water) instead of hanging
    #: silently. 0 (default) disables. SIGUSR1 dumps currently-armed
    #: waits on demand. Size it well above a healthy chunk's wall-clock
    #: — the watchdog observes the wait, it never interrupts it.
    watchdog_timeout_s: float = 0.0
    #: span sampling policy (sparkrdma_tpu.obs.journal.SamplingPolicy):
    #: "all" (default — every recorded read writes a full span),
    #: "1/N" (deterministic 1-in-N by span id; kept spans carry
    #: sample_weight=N so reports scale counts back up), "slow:<ms>"
    #: (always keep latency outliers at/above the threshold), or the
    #: union "1/N+slow:<ms>". Sampled-away reads still feed metrics and
    #: the windowed rollups, so aggregate totals stay exact — sampling
    #: thins per-read detail, never the accounting.
    journal_sample: str = "all"
    #: windowed-rollup period (sparkrdma_tpu.obs.rollup): every read is
    #: folded into per-shuffle windows of this many seconds and each
    #: window lands as one {"kind":"rollup"} journal line — exact
    #: counts/bytes/latency-histogram regardless of journal_sample.
    #: 0 disables rollups (spans only, the pre-v3 behavior).
    rollup_window_s: float = 30.0
    #: heartbeat period: every this many seconds the manager appends a
    #: {"kind":"heartbeat"} line (process identity, uptime, in-flight
    #: reads, pool occupancy, rss) so shuffle_top.py can tell a silent
    #: host from an idle one. 0 (default) disables.
    heartbeat_s: float = 0.0
    #: size-based journal rotation: when the live journal segment
    #: exceeds this many bytes it is atomically renamed to ``<sink>.1``
    #: (shifting older segments to .2, .3, …) and a fresh segment
    #: starts. 0 (default) = never rotate. The report/trace/top CLIs
    #: and read_entries(include_rotated=True) walk all segments.
    journal_max_bytes: int = 0
    #: live telemetry store (sparkrdma_tpu.obs.tsdb): every this many
    #: seconds a sampler thread snapshots all scalar metrics into a
    #: bounded ring, giving rate()/delta()/window() queries and the
    #: probe endpoint a windowed view of the recent past. Requires the
    #: metrics registry (collect_shuffle_read_stats or metrics_sink).
    #: 0 (default) disables — wiring collapses to the allocation-free
    #: null store.
    telemetry_window_s: float = 0.0
    #: telemetry ring capacity: samples retained per metric series and
    #: rollup windows retained per shuffle. Memory is O(history ×
    #: metric count); at the 120 default and a 1s window the store
    #: remembers two minutes.
    telemetry_history: int = 120
    #: probe endpoint (sparkrdma_tpu.obs.probe): TCP port on which the
    #: service/manager serves read-only JSON + Prometheus-text
    #: snapshots (telemetry, live rollups, identity, tenant usage) to
    #: ``shuffle_top --connect``. -1 (default) disables; 0 binds an
    #: ephemeral port (tests — read it back from ``probe.port``).
    probe_port: int = -1
    #: alert-evaluator cadence (sparkrdma_tpu.obs.alerts): every this
    #: many seconds a daemon thread evaluates ALERT_RULES against the
    #: telemetry store with hysteresis, journaling {"kind":"alert"}
    #: fire/resolve lines and serving /alerts + /health on the probe.
    #: Requires the telemetry store (telemetry_window_s > 0). 0
    #: (default) disables.
    alert_eval_s: float = 0.0
    #: alert hysteresis, fire side: a rule must breach this many
    #: CONSECUTIVE evaluations before its alert fires (K in K-of-K) —
    #: one noisy window never pages anyone.
    alert_fire_breaches: int = 3
    #: alert hysteresis, resolve side: an active alert must see this
    #: many consecutive clean evaluations before it resolves — a
    #: flapping signal holds one alert open instead of storming.
    alert_resolve_windows: int = 2
    #: persisted-baseline directory (sparkrdma_tpu.obs.baseline): the
    #: alert evaluator's baseline-anomaly rules and bench.py's
    #: regression gate read/update robust per-metric statistics in
    #: ``<baseline_dir>/baselines.json`` across runs. Empty (default)
    #: disables baselines (anomaly rules stay quiet; bench runs
    #: ungated).
    baseline_dir: str = ""

    # --- fault handling ---
    max_retry_attempts: int = 3       # maxConnectionAttempts analogue
    fault_injection_rate: float = 0.0  # probability of injected exchange fault
    #: unified fault plane (sparkrdma_tpu.faults): ``;``-joined
    #: ``site:action[@predicate]`` rules injecting deterministic faults
    #: at named sites across every layer, e.g.
    #: ``"exchange.dispatch:fail@attempt<2;spill.read:corrupt@0.01;
    #: pool.acquire:delay=50ms@0.05"``. Actions: fail / corrupt /
    #: delay=<N>ms; predicates: attempt<N (first N hits) or a
    #: deterministic rate in (0,1]; empty (default) = no injection.
    #: Subsumes ``fault_injection_rate`` (kept as a compat shim on the
    #: ``exchange.dispatch`` site).
    fault_spec: str = ""
    #: exponential-backoff base for the FetchFailedError retry loop:
    #: retry attempt k sleeps ~``retry_backoff_ms * 2^(k-1)`` ms with
    #: deterministic jitter in [0.5x, 1.0x) (sparkrdma_tpu.faults
    #: .backoff_ms — same schedule on every host for the same span).
    #: 0 (default) = no backoff (the pre-chaos-plane hot retry).
    retry_backoff_ms: float = 0.0
    #: wall-clock retry deadline: once this many seconds have elapsed
    #: since the read's first attempt, the next FetchFailedError is
    #: terminal even if max_retry_attempts is not yet exhausted — a
    #: persistent fault costs bounded wall-clock, never retry-forever.
    #: 0 (default) = attempts-bounded only.
    retry_deadline_s: float = 0.0
    #: graceful degradation: when True, a pallas_ring / hierarchical
    #: transport that fails to build falls back to the "xla" transport
    #: for the rest of the process (sticky, counted as
    #: ``degrade.transport``) instead of failing the job.
    transport_fallback: bool = False

    # --- host staging / spill ---
    spill_to_host: bool = False
    spill_dir: str = ""               # checkpoint root (empty = no store)
    use_native_staging: bool = True   # C++ staging pool when available
    #: optional codec for spill runs + checkpoints: "" (off, default),
    #: "zlib" or "lzma" (both stdlib). STORAGE-side only — the
    #: fabric-side decision is a measured NO (ICI/HBM pipeline ~GB/s vs
    #: zlib decompress ~0.1-0.3 GB/s/core; scripts/compress_note.py) —
    #: mirroring where the reference's "decompress" stage actually
    #: lives: Spark's shuffle files, not the NIC (SURVEY.md §3.3).
    compression: str = ""
    compression_level: int = 1        # zlib 1-9 / lzma preset 0-9

    # --- tiered out-of-core store (hbm/tiered_store.py) ---
    #: disk-segment root for the tiered spill store. Empty (default)
    #: falls back to ``spill_dir``; when both are empty the store runs
    #: with its HBM + host tiers only (host-tier evictions that would
    #: need disk raise instead of silently dropping data).
    spill_tier_dir: str = ""
    #: host-tier watermark in bytes: once pinned host-buffer occupancy
    #: crosses this, the store's background writer evicts least-recently
    #: -used unpinned segments to the disk tier until back under. The
    #: eviction runs asynchronously (overlapped with exchange rounds),
    #: so the watermark is a steady-state target, not a hard cap.
    spill_tier_host_bytes: int = 1 << 28
    #: segments the background prefetcher keeps in flight ahead of the
    #: consumer (disk -> host promotions). A ``get`` of a segment the
    #: prefetcher already promoted is a hit; a disk-resident ``get``
    #: with no promotion in flight is a synchronous fetch (the exchange
    #: blocks on disk — the ``--doctor`` smell). 0 disables prefetch.
    spill_tier_prefetch: int = 2
    #: bounded re-reads of a disk segment whose CRC32 trailer mismatches
    #: before the read raises (transient-media hardening; each overcome
    #: failure is counted as a ``spill_reread`` recovery).
    spill_tier_reread_attempts: int = 3

    # --- multi-tenant service (sparkrdma_tpu/service/) ---
    #: default per-tenant HBM quota, in slot-pool buffers concurrently
    #: held (service/tenant.py; enforced inside SlotPool acquisition).
    #: 0 (default) = unlimited. A tenant at its quota BLOCKS in
    #: acquisition until one of its buffers is returned (bounded by
    #: ``admission_wait_s``), it never steals from other tenants.
    tenant_hbm_slots: int = 0
    #: default per-tenant pinned-host-tier quota in bytes (TieredStore
    #: host tier). 0 (default) = unlimited. Over-quota puts block until
    #: the tenant's own segments evict to disk or are dropped.
    tenant_host_bytes: int = 0
    #: default per-tenant disk-tier quota in bytes (TieredStore disk
    #: segments). 0 (default) = unlimited. Eviction refuses to demote a
    #: tenant already at its disk quota (its hot set stays host-side and
    #: the tenant's puts block instead).
    tenant_disk_bytes: int = 0
    #: exchange reads admitted concurrently across ALL tenants by the
    #: service's deficit-round-robin admission controller
    #: (service/admission.py). 0 (default) = unlimited (admission
    #: bookkeeping still journals per-tenant waits).
    admission_slots: int = 0
    #: deficit-round-robin refill quantum, in exchange ROUNDS per sweep:
    #: each pass over the tenant ring adds this many rounds to a waiting
    #: tenant's deficit; a read is admitted once its tenant's deficit
    #: covers the read's planned round count. Larger values favor big
    #: reads (less interleaving), smaller values favor fairness.
    admission_quantum: float = 1.0
    #: upper bound on any single quota/admission wait in seconds; a
    #: tenant still over quota (or unadmitted) after this long fails
    #: its operation with a clear error instead of waiting forever.
    admission_wait_s: float = 300.0
    #: external-service control port (service/rpc.py RpcServer): the
    #: TCP port on which the daemon serves the length-prefixed-JSON
    #: RPC protocol to out-of-process ``RpcClient``s. -1 (default)
    #: disables — the service stays in-process only; 0 binds an
    #: ephemeral port (tests — read it back from ``rpc.port``).
    rpc_port: int = -1
    #: per-client lease duration in seconds: a client whose last
    #: request/heartbeat is older than this is reaped exactly like a
    #: clean ``close_session`` (tickets returned, charges released,
    #: shuffles dropped) with a journaled ``{"kind": "lease"}`` line.
    #: Clients heartbeat at a third of this. 0 = leases never expire.
    lease_s: float = 30.0
    #: RPC client retry backoff base in milliseconds: transport
    #: failures (drops, CRC-mangled frames, timeouts) retry under
    #: exponential backoff with deterministic jitter
    #: (``faults.backoff_ms``). 0 disables the sleep (tight retry).
    rpc_retry_ms: float = 25.0
    #: wall-clock deadline across ALL attempts of one RPC call; a
    #: daemon still unreachable after this long fails the call with
    #: one clean error instead of retrying forever. 0 = no deadline.
    rpc_deadline_s: float = 30.0

    # --- byte-payload serde (api/serde.py, api/pipeline.py) ---
    #: dispatch encode/decode to the multi-threaded C++ codec in
    #: native/staging.cpp when it is available (built on demand, GIL
    #: released for the whole batch; little-endian hosts only). False
    #: forces the numpy fallback — bit-identical rows either way, the
    #: knob only trades speed.
    serde_native: bool = True
    #: std::thread pool size for one native codec call. 0 (default) =
    #: auto (min(8, cpu count)).
    serde_threads: int = 0
    #: pipelined byte-payload chunk size, in records: from_host_payloads
    #: / to_host_payloads split batches into chunks of this many records
    #: so host encode of chunk k+1 overlaps device transfer of chunk k
    #: (double-buffered through the host staging pool). 0 disables
    #: chunking (one-shot encode, no overlap).
    serde_chunk_records: int = 1 << 20
    #: dispatch schema-declared datasets to the columnar (v2) codec:
    #: wide per-column memcpys on encode, numpy column VIEWS on decode
    #: (no per-row materialization). False pins schema-carrying byte
    #: payloads to the v1 padded-slot codec — bit-identical rows, the
    #: knob only trades speed (from_host_columns/to_host_columns always
    #: use the columnar layout; it is their only representation).
    serde_schema_columnar: bool = True
    #: block-compress spilled segments on the DISK tier with this codec
    #: ("" = store raw, "zlib", "lzma") — reuses the exchange
    #: compression framing (host_staging.compress_array /
    #: decompress_blob), so reads auto-detect and the exchange path is
    #: untouched. Cold columnar frames are highly compressible (zeroed
    #: slot padding), which is what this knob is for.
    serde_schema_spill_codec: str = ""
    #: compression level for serde_schema_spill_codec (zlib 0-9; the
    #: lzma preset). Level 1 keeps eviction cheap — the spill writer
    #: runs concurrently with the exchange.
    serde_schema_spill_level: int = 1

    def __post_init__(self) -> None:
        if self.slot_records <= 0:
            raise ValueError("slot_records must be positive")
        if self.key_words <= 0 or self.val_words < 0:
            raise ValueError("key_words must be >=1, val_words >=0")
        if self.max_rounds <= 0 or self.max_rounds_in_flight <= 0:
            raise ValueError("round counts must be positive")
        if self.queue_depth <= 0:
            raise ValueError("queue_depth must be positive (it bounds "
                             "live recv-slot memory)")
        if self.max_slot_records <= 0:
            raise ValueError("max_slot_records must be positive")
        if self.max_retry_attempts <= 0:
            raise ValueError("max_retry_attempts must be positive (1 = "
                             "no retries)")
        if self.transport not in ("xla", "pallas_ring", "hierarchical"):
            raise ValueError(f"unknown transport {self.transport!r}")
        if self.hierarchy_hosts < 0:
            raise ValueError("hierarchy_hosts must be >= 0")
        if self.map_side_combine not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown map_side_combine {self.map_side_combine!r} "
                "(supported: 'auto', 'on', 'off')")
        if self.combine_sample_rows < 0:
            raise ValueError("combine_sample_rows must be >= 0 (0 = "
                             "no sampling, 'auto' behaves as 'on')")
        if not 0.0 <= self.combine_min_dup_ratio <= 1.0:
            raise ValueError("combine_min_dup_ratio must be in [0, 1]")
        if self.plan_broadcast_records < 0:
            raise ValueError("plan_broadcast_records must be >= 0 (0 = "
                             "never broadcast)")
        if self.wide_sort_min_payload < 0:
            raise ValueError("wide_sort_min_payload must be >= 0")
        if self.wide_sort_ride_words < 0:
            raise ValueError("wide_sort_ride_words must be >= 0")
        if self.pack_sort_min_payload < 0:
            raise ValueError("pack_sort_min_payload must be >= 0")
        if self.geometry_classes not in ("pow2", "fine"):
            raise ValueError(
                f"unknown geometry_classes {self.geometry_classes!r}")
        if self.compression not in ("", "zlib", "lzma"):
            raise ValueError(
                f"unknown compression {self.compression!r} "
                "(supported: '', 'zlib', 'lzma')")
        if not 0 <= self.compression_level <= 9:
            raise ValueError("compression_level must be in [0, 9]")
        if self.watchdog_timeout_s < 0:
            raise ValueError("watchdog_timeout_s must be >= 0 (0 disables)")
        if self.rollup_window_s < 0:
            raise ValueError("rollup_window_s must be >= 0 (0 disables)")
        if self.heartbeat_s < 0:
            raise ValueError("heartbeat_s must be >= 0 (0 disables)")
        if self.journal_max_bytes < 0:
            raise ValueError("journal_max_bytes must be >= 0 (0 = no "
                             "rotation)")
        if self.telemetry_window_s < 0:
            raise ValueError("telemetry_window_s must be >= 0 "
                             "(0 disables)")
        if self.telemetry_history < 2:
            raise ValueError("telemetry_history must be >= 2 "
                             "(rate/delta need two samples)")
        if not -1 <= self.probe_port <= 65535:
            raise ValueError("probe_port must be in [-1, 65535] "
                             "(-1 disables, 0 = ephemeral)")
        if self.alert_eval_s < 0:
            raise ValueError("alert_eval_s must be >= 0 (0 disables)")
        if self.alert_fire_breaches < 1:
            raise ValueError("alert_fire_breaches must be >= 1 "
                             "(1 = fire on first breach)")
        if self.alert_resolve_windows < 1:
            raise ValueError("alert_resolve_windows must be >= 1 "
                             "(1 = resolve on first clean window)")
        if not -1 <= self.rpc_port <= 65535:
            raise ValueError("rpc_port must be in [-1, 65535] "
                             "(-1 disables, 0 = ephemeral)")
        if self.lease_s < 0:
            raise ValueError("lease_s must be >= 0 (0 = leases never "
                             "expire)")
        if self.rpc_retry_ms < 0:
            raise ValueError("rpc_retry_ms must be >= 0 (0 = tight "
                             "retry, no backoff sleep)")
        if self.rpc_deadline_s < 0:
            raise ValueError("rpc_deadline_s must be >= 0 "
                             "(0 = no deadline)")
        if self.spill_tier_host_bytes < 0:
            raise ValueError("spill_tier_host_bytes must be >= 0 (0 = "
                             "evict every unpinned host segment)")
        if self.spill_tier_prefetch < 0:
            raise ValueError("spill_tier_prefetch must be >= 0 (0 "
                             "disables prefetch)")
        if self.spill_tier_reread_attempts <= 0:
            raise ValueError("spill_tier_reread_attempts must be >= 1 "
                             "(1 = no re-reads)")
        if self.tenant_hbm_slots < 0:
            raise ValueError("tenant_hbm_slots must be >= 0 (0 = "
                             "unlimited)")
        if self.tenant_host_bytes < 0:
            raise ValueError("tenant_host_bytes must be >= 0 (0 = "
                             "unlimited)")
        if self.tenant_disk_bytes < 0:
            raise ValueError("tenant_disk_bytes must be >= 0 (0 = "
                             "unlimited)")
        if self.admission_slots < 0:
            raise ValueError("admission_slots must be >= 0 (0 = "
                             "unlimited)")
        if self.admission_quantum <= 0:
            raise ValueError("admission_quantum must be > 0 (rounds "
                             "refilled per DRR sweep)")
        if self.admission_wait_s < 0:
            raise ValueError("admission_wait_s must be >= 0 (0 = fail "
                             "immediately when over quota)")
        if self.serde_threads < 0:
            raise ValueError("serde_threads must be >= 0 (0 = auto)")
        if self.serde_chunk_records < 0:
            raise ValueError("serde_chunk_records must be >= 0 (0 = no "
                             "chunking)")
        if self.serde_schema_spill_codec not in ("", "zlib", "lzma"):
            raise ValueError(
                f"unknown serde_schema_spill_codec "
                f"{self.serde_schema_spill_codec!r} "
                "(supported: '', 'zlib', 'lzma')")
        if not 0 <= self.serde_schema_spill_level <= 9:
            raise ValueError("serde_schema_spill_level must be in [0, 9]")
        if not 0.0 <= self.fault_injection_rate <= 1.0:
            raise ValueError("fault_injection_rate must be in [0, 1]")
        if self.retry_backoff_ms < 0:
            raise ValueError("retry_backoff_ms must be >= 0 (0 disables)")
        if self.retry_deadline_s < 0:
            raise ValueError("retry_deadline_s must be >= 0 (0 disables)")
        self.sampling_policy()  # validate journal_sample eagerly
        self.fault_rules()      # validate fault_spec eagerly
        _parse_prealloc(self.prealloc)  # validate eagerly

    @property
    def record_words(self) -> int:
        """Total uint32 words per record in exchange buffers."""
        return self.key_words + self.val_words

    @property
    def slot_bytes(self) -> int:
        """Bytes of one (src,dst) slot — comparable to maxAggBlock."""
        return self.slot_records * self.record_words * 4

    def sort_strategy(self, record_words: int) -> "SortStrategy":
        """THE precedence rule for full-record sorts of ``record_words``
        words: pack > wide > plain, each at its payload threshold (0
        turns a rung off). Every sort of a record batch — the fused
        tail, the map-side bucket, the combine, group, join, densify and
        filter compactions — runs the strategy chosen here."""
        # local import: config stays importable without jax
        from sparkrdma_tpu.kernels.sort import SortStrategy

        payload = record_words - self.key_words
        if self.pack_sort_min_payload and \
                payload >= self.pack_sort_min_payload:
            return SortStrategy("pack")
        if self.wide_sort_min_payload and \
                payload >= self.wide_sort_min_payload:
            return SortStrategy("wide", self.wide_sort_ride_words)
        return SortStrategy()

    def prealloc_classes(self) -> Dict[int, int]:
        return _parse_prealloc(self.prealloc)

    def sampling_policy(self):
        """Parsed ``journal_sample`` (obs.journal.SamplingPolicy)."""
        # local import: config must stay importable before the package
        # root finishes initializing (obs.journal is stdlib-only)
        from sparkrdma_tpu.obs.journal import SamplingPolicy
        return SamplingPolicy.parse(self.journal_sample)

    def fault_rules(self):
        """Parsed ``fault_spec`` (sparkrdma_tpu.faults.FaultRule list)."""
        # local import for the same reason as sampling_policy
        from sparkrdma_tpu.faults import parse_fault_spec
        return parse_fault_spec(self.fault_spec)

    def replace(self, **kw) -> "ShuffleConf":
        return dataclasses.replace(self, **kw)


def size_class(n_records: int) -> int:
    """Round a record count up to its power-of-two size class.

    Same bucketing rule as RdmaBufferManager (src/main/java/org/apache/spark/
    shuffle/rdma/RdmaBufferManager.java §get): requests are served from
    power-of-two-classed free stacks so buffers are reusable across requests
    of similar size (and, here, so XLA sees few distinct shapes to compile).
    """
    if n_records <= 0:
        raise ValueError("n_records must be positive")
    return 1 << (n_records - 1).bit_length()


def size_class_fine(n_records: int, bits: int = 4) -> int:
    """Round up keeping the top ``bits`` bits — eighth/sixteenth-octave
    size classes for EXCHANGE GEOMETRY (slot capacity, out capacity).

    Power-of-two classes waste up to 2x: a worst (src,dst) pair landing
    just above a boundary doubles every slot, and every downstream pass
    pays the inflation (measured ~30% of the multi-partition map-side
    cost). Keeping 4 top bits caps padding at ~6.7% while the class
    count stays bounded (~16 per octave), so the compiled-program cache
    still converges. Padding is < 1/2^bits = 6.25%; counts up to
    ``2^(bits+1) - 1`` (31) stay exact; large classes are automatically
    multiples of 128 (lane alignment) once ``n >= 2^(bits+8)``. Buffer
    POOL bucketing keeps the coarse pow2 classes (reuse across nearby
    sizes matters more there).
    """
    if n_records <= 0:
        raise ValueError("n_records must be positive")
    shift = max(0, n_records.bit_length() - 1 - bits)
    return ((n_records + (1 << shift) - 1) >> shift) << shift


__all__ = ["ShuffleConf", "size_class", "size_class_fine",
           "DEFAULT_KEY_WORDS", "DEFAULT_VAL_WORDS"]
