"""Canonical metric-name registry — the single list of every counter,
gauge, histogram, and timeline counter-track the package emits.

The names themselves are the contract: ``shuffle_report --doctor`` and
``shuffle_trace`` read them back out of journals and registry snapshots
by string, so an emission renamed in one file silently zeroes a doctor
rule unless something cross-checks. ``srlint``'s ``counter-name-sync``
rule does exactly that — it scans the package AST for
``.counter("...")`` / ``.gauge("...")`` / ``.histogram("...")`` calls
and fails when an emitted name is missing here, when a name declared
here has no emission site left, or when a CLI reads a name nothing
emits.

Dynamic families (``f"faults.{site}"``-style emissions) are declared as
wildcard patterns in :data:`WILDCARDS`; the lint matches the f-string's
literal skeleton against the pattern, so even the dynamic names cannot
drift shape without failing the build.

This module is import-free on purpose (stdlib ``frozenset`` only): the
lint parses it with ``ast`` rather than importing it, and the CLIs under
``scripts/`` stay stdlib-only.
"""

from __future__ import annotations

#: Monotonic counters (``registry.counter(name)``).
COUNTERS = frozenset({
    "staging.spills",
    "staging.spill_bytes",
    "pool.hits",
    "pool.misses",
    "meta.registrations",
    "meta.map_outputs_published",
    "meta.map_records_published",
    "journal.write_errors",
    "journal.rotations",
    "journal.sampled_out",
    "shuffle.exchanges",
    "shuffle.records",
    "shuffle.bytes",
    "shuffle.rounds",
    "transport.ring.kernels",
    "transport.ring.fused_kernels",
    "transport.ring.fused_rounds",
    "transport.ring.overlap_rounds",
    "transport.hier.flat_fallbacks",
    "transport.hier.staged_exchanges",
    "watchdog.stalls",
    "exchange.transport_fallbacks",
    "exchange.faults",
    "exchange.plans",
    "exchange.queue_blocks",
    "exchange.stream_chunks",
    "exchange.dispatches",
    "exchange.exchanges",
    "exchange.rounds",
    "exchange.records",
    "exchange.bucket_sort.stable",
    "exchange.bucket_sort.unstable",
    "exchange.bucket_sort.hash_keyed",
    "combine.gate_on",
    "combine.gate_off",
    "combine.fallbacks",
    "pushdown.filters",
    "pushdown.projections",
    "plan.pushdown_sunk",
    "plan.reuse_hits",
    "plan.broadcast_joins",
    "plan.overlapped_stages",
    "store.puts",
    "store.put_bytes",
    "store.spill_writes",
    "store.spill_bytes",
    "store.fetches",
    "store.fetch_bytes",
    "store.prefetch_hits",
    "store.sync_fetches",
    "store.crc_rereads",
    "store.compressed_segments",
    "service.admits",
    "service.admission_waits",
    "service.sessions_opened",
    "service.sessions_closed",
    "service.rpc.requests",
    "service.rpc.errors",
    "service.rpc.replays",
    "service.rpc.calls",
    "service.rpc.retries",
    "service.leases_granted",
    "service.leases_renewed",
    "service.leases_expired",
    "tsdb.samples",
    "tsdb.evictions",
    "probe.requests",
    "probe.errors",
    "critical_path.attributions",
    "alerts.fired",
    "alerts.resolved",
})

#: Point-in-time gauges (``registry.gauge(name)``).
GAUGES = frozenset({
    "pool.outstanding",
    "meta.registered_shuffles",
    "reads.in_flight",
    "store.host_bytes",
    "store.disk_bytes",
    "service.tenants",
    "alerts.active",
})

#: Distributions (``registry.histogram(name)``).
HISTOGRAMS = frozenset({
    "shuffle.exec_s",
    "exchange.plan_s",
})

#: In-span timeline counter tracks (``timeline.counter(name, value)``) —
#: Chrome-trace ``C`` events, a separate namespace from the registry but
#: read back by name in ``shuffle_trace``. ``pool.outstanding`` is
#: deliberately in both: the gauge is the registry's latest value, the
#: track is its in-span history.
TIMELINE_TRACKS = frozenset({
    "pool.outstanding",
    "chunks.outstanding",
})

#: Dynamic name families emitted through f-strings; ``*`` stands for one
#: interpolated hole. Every f-string emission in the package must match
#: one of these patterns exactly (hole-for-hole), and every pattern must
#: still have a matching emission site.
WILDCARDS = frozenset({
    "faults.*",
    "exchange.programs_built.*",
    "degrade.*",
    "recover.*",
    "serde.*_bytes",
    "serde.*_ns",
    "serde.*_calls",
    "serde.*_native",
    "serde.*_fallback",
    "serde.columnar.*_bytes",
    "serde.columnar.*_ns",
    "serde.columnar.*_calls",
    "serde.columnar.*_native",
    "serde.columnar.*_fallback",
    "tenant.*.hbm_slots",
    "tenant.*.host_bytes",
    "tenant.*.disk_bytes",
    "tenant.*.quota_waits",
})

__all__ = ["COUNTERS", "GAUGES", "HISTOGRAMS", "TIMELINE_TRACKS",
           "WILDCARDS"]
