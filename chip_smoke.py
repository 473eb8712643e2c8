#!/usr/bin/env python3
"""Chip smoke: the TeraSort shuffle end to end on a TPU, through the
entry points a user calls (``MeshRuntime`` -> ``ShuffleManager`` ->
``workloads.terasort.run_terasort`` and the README Quick-start API).

Default — one chip:

- **Phase A, faithful TeraSort.** HiBench records of 100 bytes (2-word
  key + 23-word payload): sample -> range partition -> exchange -> fused
  sort, checked on device (``device_verify``) and by the host's global
  order + permutation check. One warm-up, then ``REPEATS`` repeats.
- **Phase B, hash repartition through the Quick-start API.**
  ``hash_partitioner``, ``write().stop()``, ``read()``, ``host_rows``;
  each chip's rows, and each partition's rows read back by id
  (``read_partition``), compared as a multiset with a numpy reference
  of the same hash, made from ``--seed``.

``--chips 4`` runs only Phase A's TeraSort on a 4-chip mesh, once with
``transport="xla"`` and once with ``transport="pallas_ring"`` (the fused
remote-DMA kernel). Both verify on device; the xla output also passes
the host's global-order check, and the ring's output must equal it bit
for bit (so it passes that check too, without a second 6.4 GB host
sort). Both hold their output on four distinct devices.

Earlier lines carry bring-up notes — compile seconds, steady seconds per
repeat, peak HBM, the sort mode, which Pallas kernels ran compiled.
They are observations, not metrics. The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU
the script prints no result and exits 1; any failed check exits 1.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

#: 100-byte HiBench records: 2 key words + 23 payload words (W = 25)
HIBENCH_VAL_WORDS = 23
#: Phase A records per chip: bench.py's 16M (1.6 GB of input per chip)
RECORDS = 16 << 20
#: Phase B rows per chip
ROWS = 1 << 20
#: Phase A exchange+sort repeats timed after the warm-up
REPEATS = 3
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def note(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Seconds XLA spent compiling, summed from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += secs

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def fallbacks(manager) -> dict:
    c = manager.metrics.counter
    return {name: c(name).value
            for name in ("exchange.transport_fallbacks",
                         "combine.fallbacks")}


def pallas_kernels(manager) -> str:
    """Which Pallas kernels this manager's exchanges ran, and how."""
    conf, c = manager.conf, manager.metrics.counter
    ran = [f"{name} x{c(counter).value}" for name, counter in (
        ("fused ring", "transport.ring.fused_kernels"),
        ("per-round ring", "transport.ring.kernels")) if c(counter).value]
    if conf.fast_sort:
        ran.append("merge_sort_cols (when the plan supports it)")
    if not ran:
        return (f"none (transport={conf.transport}, "
                f"fast_sort={conf.fast_sort})")
    mode = "interpreted" if manager._exchange.interpret else "compiled"
    return f"{', '.join(ran)}: {mode}"


def terasort_phase(devices, seed, clock, transport="xla", host_check=True):
    """Phase A on ``devices``; returns ``(notes, out, totals)``."""
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
    from sparkrdma_tpu.workloads.terasort import run_terasort

    conf = ShuffleConf(
        # one round for a balanced range shuffle (bench.py's geometry)
        slot_records=RECORDS,
        max_slot_records=2 * RECORDS,
        val_words=HIBENCH_VAL_WORDS,
        transport=transport,
        geometry_classes="fine",
        # the sort-mode ladder's cheapest cold compile at W=25: the
        # "wide" rung with no riding payload — a key+index sort, then
        # one gather places all 23 payload words (rehearsed for v5e at
        # N=16M: 48 s to compile, vs 245 s with 10 riding words; the
        # pack and plain rungs did not finish in 360-400 s)
        pack_sort_min_payload=0,
        wide_sort_ride_words=0,
        # live metrics registry, so the fallback counters count
        collect_shuffle_read_stats=True)
    manager = ShuffleManager(MeshRuntime(conf, devices=devices), conf)
    try:
        ex = manager._exchange
        c0, t0 = clock.seconds, time.perf_counter()
        res, out, totals = run_terasort(
            manager, records_per_device=RECORDS, seed=seed,
            verify=host_check, device_verify=True, warmup=True,
            repeats=REPEATS, shuffle_id=1)
        shards = {s.device for s in out.addressable_shards}
        notes = {
            "transport": transport,
            "records_per_chip": RECORDS,
            "record_bytes": res.record_bytes,
            "sort_mode": ex.sort_mode(HIBENCH_VAL_WORDS + conf.key_words),
            "verified": bool(res.verified),
            "host_order_check": host_check,
            "phase_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0,
            "steady_s_per_repeat": res.sort_exchange_s,
            "repeats": REPEATS,
            "peak_bytes_in_use": peak_bytes(devices),
            "output_devices": len(shards),
            "pallas_interpret": ex.interpret,
            "pallas_kernels": pallas_kernels(manager),
            "ring_kernels_traced": manager.metrics.counter(
                "transport.ring.fused_kernels").value,
            **fallbacks(manager),
        }
        return notes, out, totals
    finally:
        manager.stop()


def np_hash_partition(rows, num_parts, key_words=2):
    """numpy reference of ``hash_partitioner`` (uint32 wraparound)."""
    h = np.zeros(rows.shape[0], np.uint64)
    for w in range(key_words):
        h = ((h ^ rows[:, w]) * np.uint64(2654435761)) & np.uint64(
            0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    return (h % np.uint64(num_parts)).astype(np.int64)


def repartition_phase(devices, seed, clock):
    """Phase B: the README Quick-start flow, checked against numpy."""
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
    from sparkrdma_tpu.exchange.partitioners import hash_partitioner
    from sparkrdma_tpu.workloads.terasort import canonical_rows

    num_parts = 64
    conf = ShuffleConf(slot_records=1 << 16, collect_shuffle_read_stats=True)
    m = ShuffleManager(MeshRuntime(conf, devices=devices), conf)
    try:
        c0, t0 = clock.seconds, time.perf_counter()
        mesh = m.runtime.num_partitions
        rows = np.random.default_rng(seed).integers(
            0, 2**32, size=(ROWS * mesh, 4), dtype=np.uint32)
        records = m.runtime.shard_records(rows)
        part = hash_partitioner(num_parts=num_parts, key_words=2)
        handle = m.register_shuffle(0, num_parts=num_parts, partitioner=part)
        m.get_writer(handle).write(records).stop()
        reader = m.get_reader(handle, key_ordering=True)
        out, totals = reader.read()
        host = m.runtime.host_rows(out)
        totals = np.asarray(totals)
        cap = host.shape[0] // mesh
        want = np_hash_partition(rows, num_parts)
        ok = True
        # each chip's rows, in key order (partition p lives on p % mesh)
        for d in range(mesh):
            got = host[d * cap:d * cap + int(totals[d])]
            keys = (got[:, 0].astype(np.uint64) << np.uint64(32)) | got[:, 1]
            ok &= bool(np.all(keys[1:] >= keys[:-1]))
            ok &= bool(np.array_equal(canonical_rows(got, 2),
                                      canonical_rows(rows[want % mesh == d],
                                                     2)))
        # each partition read back by id: checks the hash itself, which
        # the per-chip check cannot on one chip (every p % 1 == 0)
        for p in range(num_parts):
            ok &= bool(np.array_equal(
                canonical_rows(reader.read_partition(p), 2),
                canonical_rows(rows[want == p], 2)))
        notes = {"rows_per_chip": ROWS, "num_parts": num_parts,
                 "verified": ok, "phase_s": time.perf_counter() - t0,
                 "compile_s": clock.seconds - c0,
                 "peak_bytes_in_use": peak_bytes(devices),
                 "pallas_interpret": m._exchange.interpret,
                 "pallas_kernels": pallas_kernels(m),
                 **fallbacks(m)}
        m.unregister_shuffle(0)
        return notes
    finally:
        m.stop()


def clean(notes: dict) -> bool:
    return (notes["verified"]
            and notes["exchange.transport_fallbacks"] == 0
            and notes["combine.fallbacks"] == 0)


def one_chip(devices, seed, clock) -> bool:
    a, _, _ = terasort_phase(devices[:1], seed, clock)
    note(f"phase A TeraSort {json.dumps(a)}")
    b = repartition_phase(devices[:1], seed, clock)
    note(f"phase B repartition {json.dumps(b)}")
    note(f"XLA programs compiled for {devices[0].device_kind}")
    return clean(a) and clean(b)


def four_chips(devices, seed, clock) -> bool:
    runs = {}
    for transport in ("xla", "pallas_ring"):
        notes, out, totals = terasort_phase(
            devices[:4], seed, clock, transport=transport,
            host_check=transport == "xla")
        note(f"4-chip TeraSort {json.dumps(notes)}")
        runs[transport] = (notes, np.asarray(out), np.asarray(totals))
    (xn, xo, xt), (rn, ro, rt) = runs["xla"], runs["pallas_ring"]
    identical = bool(np.array_equal(xo, ro) and np.array_equal(xt, rt))
    ring_compiled = (rn["ring_kernels_traced"] > 0
                     and not rn["pallas_interpret"])
    note(f"4-chip xla vs pallas_ring bit-identical={identical}; fused "
         f"ring kernel compiled={ring_compiled} "
         f"({rn['ring_kernels_traced']} instance(s) traced)")
    return (clean(xn) and clean(rn) and identical and ring_compiled
            and xn["output_devices"] == rn["output_devices"] == 4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 4-chip xla vs pallas_ring TeraSort")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    note(f"compile cache at {cache} ({entries} entries at start)")
    clock = CompileClock()
    try:
        ok = (four_chips if args.chips == 4 else one_chip)(
            devices, args.seed, clock)
    finally:
        clock.close()
    if not ok:
        print("chip_smoke: a phase failed (see the notes above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
