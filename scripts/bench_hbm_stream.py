"""Larger-than-HBM streaming bench: a host dataset several times one
chunk's HBM working set, pipelined through repeated exchanges with
double-buffered H2D (hbm/input_stream.py + workloads/streaming.py).

Reports sustained GB/s across the whole stream in fold (no-spill) mode
— the pure fabric+H2D pipeline — and, with BENCH_SPILL_DIR set, the
external-sort mode whose per-chunk sorted runs go to disk through the
native spooler.

Env: BENCH_CHUNK_RECORDS (default 8M), BENCH_CHUNKS (default 8),
BENCH_RECORD_WORDS (default 13), BENCH_SPILL_DIR (default off).
BENCH_TRACE_DIR: when set, a SEPARATE 2-chunk stream runs under
jax.profiler AFTER the measurement (proving the H2D/compute overlap
without the profiler overhead deflating the reported GB/s).

The H2D rate this pipeline needs is not measured on the current chip.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    chunk_records = int(os.environ.get("BENCH_CHUNK_RECORDS",
                                       8 * 1024 * 1024))
    n_chunks = int(os.environ.get("BENCH_CHUNKS", 8))
    words = int(os.environ.get("BENCH_RECORD_WORDS", 13))
    spill_dir = os.environ.get("BENCH_SPILL_DIR", "")
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    import jax

    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
    from sparkrdma_tpu.hbm.input_stream import ArrayChunkSource
    from sparkrdma_tpu.workloads.streaming import run_streaming_terasort

    conf = ShuffleConf(slot_records=max(4096, chunk_records),
                       max_slot_records=max(1 << 22, 2 * chunk_records),
                       val_words=words - 2,
                       geometry_classes="fine")
    manager = ShuffleManager(MeshRuntime(conf), conf)
    try:
        rng = np.random.default_rng(0)
        mesh = manager.runtime.num_partitions
        total = mesh * chunk_records * n_chunks
        cols = rng.integers(0, 2**32, size=(words, total), dtype=np.uint32)
        src = ArrayChunkSource(cols, mesh * chunk_records)
        # warm the compiled programs on the first chunk's geometry so
        # the measured stream is steady-state (one throwaway pass)
        warm = ArrayChunkSource(cols[:, :mesh * chunk_records],
                                mesh * chunk_records)
        run_streaming_terasort(manager, warm, shuffle_id_base=8000)
        res = run_streaming_terasort(
            manager, src, spill_dir=spill_dir or None,
            shuffle_id_base=9000)
        if trace_dir:
            # trace a short separate stream so the measurement above is
            # profiler-free (tracing all chunks deflated stream_s)
            two = ArrayChunkSource(cols[:, :2 * mesh * chunk_records],
                                   mesh * chunk_records)
            jax.profiler.start_trace(trace_dir)
            run_streaming_terasort(manager, two, shuffle_id_base=8500)
            jax.profiler.stop_trace()
        # conservation proof across the whole stream (fold mode)
        if res.fold_sums is not None:
            ref = np.concatenate(
                [[np.uint32(total)], cols.sum(axis=1, dtype=np.uint32)])
            assert np.array_equal(res.fold_sums, ref.astype(np.uint32)), \
                "conservation FAILED across the stream"
        # device-side per-chunk probe: the exchange+sort leg repeated on
        # ONE resident chunk (no H2D in the timed region) — the rate the
        # pipeline sustains once transfers keep up
        from sparkrdma_tpu.workloads.terasort import run_terasort

        probe = manager.runtime.shard_records(
            np.ascontiguousarray(cols[:, :mesh * chunk_records].T))
        dres, _, _ = run_terasort(
            manager, records_per_device=chunk_records,
            input_records=probe, verify=False, warmup=True,
            repeats=4, shuffle_id=9900)
        dataset_gb = total * words * 4 / 1e9
        chunk_gb = mesh * chunk_records * words * 4 / 1e9
        print(json.dumps({
            "metric": "streaming_input_gbps_per_chip",
            "value": round(res.gbps / mesh, 3),
            "unit": "GB/s/chip",
            "value_device_side_per_chunk": round(dres.gbps / mesh, 3),
            "dataset_gb": round(dataset_gb, 2),
            "chunk_gb": round(chunk_gb, 2),
            "chunks": n_chunks,
            "dataset_over_chunk": n_chunks,
            "mode": "spill" if spill_dir else "fold",
        }))
        return 0
    finally:
        manager.stop()


if __name__ == "__main__":
    sys.exit(main())
