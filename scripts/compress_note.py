"""The measured fabric-side compression decision (VERDICT r4 #4).

Spark compresses every shuffle block and SparkRDMA serves those
compressed bytes (SURVEY.md §3.3: "take stream -> decompress ->
deserialize"), so this framework owes a considered answer on each leg:

1. STORAGE (spill runs, checkpoints): codec behind ShuffleConf — ratio
   is data-dependent, cost is off the hot path (spooler). Measured here.
2. FABRIC (the exchange itself): would require de/compressing at the
   sort/exchange boundary every round. Measured here as codec GB/s vs
   the pipeline's GB/s — the decision is NO when the codec is slower
   than the pipeline (it throttles the data plane instead of helping).
3. H2D: device_put moves raw bytes and takes no codec from user code,
   so compression helps the DISK leg feeding the streamer only. Stated,
   not benchmarked (nothing to vary); the H2D rate itself is not
   measured on the current chip.

Run anywhere (CPU fine — zlib speed is a host property):
    python scripts/compress_note.py
"""

import json
import sys
import time
import zlib

import numpy as np


def measure(name, data: bytes, level: int):
    t0 = time.perf_counter()
    blob = zlib.compress(data, level)
    tc = time.perf_counter() - t0
    t0 = time.perf_counter()
    raw = zlib.decompress(blob)
    td = time.perf_counter() - t0
    assert raw == data
    return {
        "case": name,
        "level": level,
        "ratio": round(len(data) / len(blob), 2),
        "compress_gbps": round(len(data) / tc / 1e9, 3),
        "decompress_gbps": round(len(data) / td / 1e9, 3),
    }


def main() -> int:
    rng = np.random.default_rng(0)
    n = 4 * 1024 * 1024
    # terasort-faithful records: uniform random words (incompressible)
    random_rec = rng.integers(0, 2**32, size=(n, 13),
                              dtype=np.uint32).tobytes()
    # structured records: small-int payloads (the compressible shape
    # real keyed datasets usually have)
    structured = np.zeros((n, 13), dtype=np.uint32)
    structured[:, 1] = rng.integers(0, 1 << 12, size=n)
    structured[:, 2] = rng.integers(0, 1000, size=n)
    structured = structured.tobytes()

    results = [
        measure("random_terasort_records", random_rec, 1),
        measure("structured_records", structured, 1),
        measure("structured_records", structured, 6),
    ]
    for r in results:
        print(json.dumps(r))
    best = max(r["decompress_gbps"] for r in results)
    print(json.dumps({
        "decision": "storage-side only",
        "why": f"best zlib decompress {best} GB/s/core vs exchange+sort "
               "pipeline ~2.7-3.7 GB/s/chip (measured through a "
               "deployment that no longer exists; not measured on the "
               "current chip): fabric-side "
               "compression would bottleneck the data plane; storage "
               "and DCN-class links (~0.1 GB/s) are where ratios pay.",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
