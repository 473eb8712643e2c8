"""Serde-encoded byte payloads at bench scale on TPU (VERDICT r4 #3).

The reference shuffles SERIALIZED OBJECTS (SURVEY.md §3.3); this
framework's codec (api/serde.py) maps variable-length byte payloads
onto fixed-width records. Rounds 1-4 only ever exercised the codec at
test scale — this script runs the full pipeline at bench scale on the
real chip:

1. HOST ENCODE: ~8M variable-length payloads (0-92 bytes, mean ~46) are
   bulk-encoded into 104-byte records (2 key words + length word + 23
   payload words) — the vectorized round-5 codec.
2. DEVICE SHUFFLE: full range-partition + exchange + fused key-ordered
   sort over the encoded records, repeated for steady state, verified
   on device (conservation + order invariants).
3. SAMPLE DECODE: a window per device comes back to host and decodes;
   payloads are a deterministic function of the key (key bytes tiled to
   a key-derived length), so decoded bytes are self-checking without a
   giant host-side reference.

Between (1) and (2), COLUMNAR LEGS time the schema-aware v2 codec on
the same wire-byte accounting: a fixed-width uint32/int64/float64
schema (decode = column views over the row frame) and the same byte
payloads under a bytes-only schema (bit-identical rows, offsets+heap
input). Their rates print next to the v1 legs as
``columnar_*_mbps``.

Prints ONE JSON line with the device-side GB/s over ENCODED bytes (the
wire format, what the fabric actually moves — same accounting as the
reference's compressed-block GB/s), then a second BENCH-style row
(``serde_columnar_decode_gbps``) tracking the serde trajectory.

Env: BENCH_RECORDS_PER_DEVICE (default 8M), BENCH_REPEATS (default 8).
``--journal PATH`` routes the run's exchange journal (spans + rollup
windows) to PATH for ``shuffle_report.py`` / ``shuffle_top.py``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from bench import _bench_metrics
from sparkrdma_tpu import MeshRuntime, ShuffleConf
from sparkrdma_tpu.api.serde import (decode_bytes_rows, encode_bytes_rows,
                                     payload_words)
from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.utils.compile_cache import enable_compile_cache
from sparkrdma_tpu.workloads.terasort import run_terasort

MAX_PAYLOAD = 92


def expected_payload(hi: int, lo: int) -> bytes:
    """Deterministic payload of a key: its 8 bytes tiled to a
    key-derived length in [0, MAX_PAYLOAD]."""
    ln = (hi ^ lo) % (MAX_PAYLOAD + 1)
    pat = hi.to_bytes(4, "little") + lo.to_bytes(4, "little")
    return (pat * 12)[:ln]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serde-encoded shuffle bench (one JSON line)")
    ap.add_argument("--journal", default="", metavar="PATH",
                    help="write the exchange journal (spans + rollup "
                         "windows) to PATH")
    args = ap.parse_args(argv)
    enable_compile_cache()
    n = int(os.environ.get("BENCH_RECORDS_PER_DEVICE", 8 * 1024 * 1024))
    repeats = int(os.environ.get("BENCH_REPEATS", 8))
    rng = np.random.default_rng(7)
    import time

    keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    # bulk-build the self-checking payloads: pattern = key bytes, length
    # = key-derived; one big byte matrix sliced per row at C speed
    lens = ((keys[:, 0] ^ keys[:, 1]) % (MAX_PAYLOAD + 1)).astype(np.int64)
    pat = np.zeros((n, 96), dtype=np.uint8)
    le = keys.astype("<u4").view(np.uint8).reshape(n, 8)
    for r in range(12):
        pat[:, r * 8:(r + 1) * 8] = le
    whole = pat.tobytes()
    payloads = [whole[i * 96: i * 96 + ln]
                for i, ln in enumerate(lens.tolist())]
    # time ONLY the codec (input synthesis above is test scaffolding,
    # not serializer work — review finding); native codec + threads when
    # the library is built, numpy fallback otherwise — the JSON line
    # says which ran
    from sparkrdma_tpu.api.serde import native_codec_available

    native = native_codec_available()
    t0 = time.perf_counter()
    rows = encode_bytes_rows(keys, payloads, MAX_PAYLOAD)
    encode_s = time.perf_counter() - t0
    w = rows.shape[1]
    assert w == 2 + payload_words(MAX_PAYLOAD)
    # host decode over the full encoded batch — the symmetric number
    # (wire bytes back into payload bytes), separate from device GB/s
    t0 = time.perf_counter()
    dec_keys, dec_payloads = decode_bytes_rows(rows, 2)
    decode_s = time.perf_counter() - t0
    if not (np.array_equal(dec_keys, keys)
            and dec_payloads[:256] == payloads[:256]):
        print(json.dumps({"error": "host codec round trip FAILED"}))
        return 1
    del dec_keys, dec_payloads

    # ---- columnar (v2) legs: the same wire-byte accounting as the v1
    # legs so the encode_mbps/decode_mbps columns compare directly.
    # Fixed-width leg: a 5-payload-word analytics-ish schema; decode is
    # column VIEWS over the row frame (the whole point of v2).
    from sparkrdma_tpu.api.serde import RowSchema, decode_cols, encode_cols

    fsch = RowSchema([("a", "uint32"), ("b", "int64"), ("c", "float64")])
    fcols = {"a": keys[:, 0].copy(),
             "b": (keys[:, 0].astype(np.int64) << 16)
             - keys[:, 1].astype(np.int64),
             "c": (keys[:, 1].astype(np.float64) + 0.5) / 3.0}
    # encode into a pre-touched out buffer, timing the SECOND pass: the
    # pipeline encodes into REUSED pool-leased staging buffers, so the
    # steady-state rate is the representative number (a cold first call
    # is page-fault-bound on the fresh output pages, not codec-bound)
    frows = np.empty((n, 2 + fsch.payload_words), dtype=np.uint32)
    encode_cols(keys, fcols, fsch, out=frows)
    t0 = time.perf_counter()
    encode_cols(keys, fcols, fsch, out=frows)
    col_fixed_encode_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fk, fdec = decode_cols(frows, 2, fsch)
    # touch every decoded column so lazily-evaluated views cannot make
    # the number a lie (sum forces a full read of each column)
    sink = (int(fdec["a"].sum(dtype=np.uint64))
            ^ int(fdec["b"].sum(dtype=np.int64)))
    col_fixed_decode_s = time.perf_counter() - t0
    ok = (np.array_equal(fk, keys)
          and np.array_equal(fdec["b"][:4096], fcols["b"][:4096])
          and np.array_equal(fdec["c"][:4096], fcols["c"][:4096])
          and sink is not None)
    if not ok:
        print(json.dumps({"error": "columnar fixed round trip FAILED"}))
        return 1
    fixed_nbytes = frows.nbytes
    del frows, fk, fdec, fcols

    # Varlen leg: the SAME payloads as the v1 legs, under a bytes-only
    # schema (bit-identical rows), fed in canonical offsets+heap form —
    # the columnar contract for streaming pipelines.
    from sparkrdma_tpu.api.serde import BytesColumn

    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    heap = pat.reshape(-1)[
        (np.arange(96)[None, :] < lens[:, None]).reshape(-1)]
    vsch = RowSchema.bytes_only(MAX_PAYLOAD)
    vrows = np.empty_like(rows)
    encode_cols(keys, {"payload": BytesColumn(offsets, heap)}, vsch,
                out=vrows)                       # warm the out pages
    t0 = time.perf_counter()
    encode_cols(keys, {"payload": BytesColumn(offsets, heap)}, vsch,
                out=vrows)
    col_var_encode_s = time.perf_counter() - t0
    if not np.array_equal(vrows, rows):
        print(json.dumps({"error": "columnar varlen rows differ from "
                                   "v1 rows"}))
        return 1
    t0 = time.perf_counter()
    vk, vdec = decode_cols(vrows, 2, vsch)
    col_var_decode_s = time.perf_counter() - t0
    bc = vdec["payload"]
    if not (np.array_equal(vk, keys)
            and np.array_equal(bc.heap[:4096], heap[:4096])
            and bc[n // 2] == payloads[n // 2]):
        print(json.dumps({"error": "columnar varlen round trip FAILED"}))
        return 1
    del vrows, vk, vdec, bc, pat

    conf = ShuffleConf(slot_records=max(4096, n), max_rounds=64,
                       max_slot_records=max(1 << 22, 2 * n),
                       val_words=w - 2, geometry_classes="fine",
                       # stats ride only the final recorded read; the
                       # timed loop stays record_stats=False (see bench.py)
                       collect_shuffle_read_stats=True,
                       metrics_sink=args.journal)
    manager = ShuffleManager(MeshRuntime(conf), conf)
    try:
        records = manager.runtime.shard_records(rows)
        res, out, totals = run_terasort(
            manager, records_per_device=n, input_records=records,
            verify=False, device_verify=True, warmup=True,
            repeats=repeats, shuffle_id=0)
        if not res.verified:
            print(json.dumps({"error": "device verification FAILED"}))
            return 1
        # sample decode: 4096 columns per device, content self-check
        mesh = manager.runtime.num_partitions
        cap = out.shape[1] // mesh
        tot = np.asarray(totals)
        checked = 0
        for d in range(mesh):
            k = min(int(tot[d]), 4096)
            win = np.asarray(out[:, d * cap: d * cap + k]).T
            got_keys, got_payloads = decode_bytes_rows(win, 2)
            for i in range(k):
                exp = expected_payload(int(got_keys[i, 0]),
                                       int(got_keys[i, 1]))
                if got_payloads[i] != exp:
                    print(json.dumps({"error": f"payload mismatch at "
                                               f"device {d} row {i}"}))
                    return 1
            checked += k
        gbps = res.gbps / mesh
        print(json.dumps({
            "metric": "serde_shuffle_gbps_per_chip",
            "value": round(gbps, 3),
            "unit": "GB/s/chip",
            "record_bytes": w * 4,
            "payload": "variable 0-92B, mean ~46B",
            "encode_mbps": round(n * w * 4 / encode_s / 1e6, 1),
            "decode_mbps": round(n * w * 4 / decode_s / 1e6, 1),
            "columnar_fixed_encode_mbps": round(
                fixed_nbytes / col_fixed_encode_s / 1e6, 1),
            "columnar_fixed_decode_mbps": round(
                fixed_nbytes / col_fixed_decode_s / 1e6, 1),
            "columnar_varlen_encode_mbps": round(
                n * w * 4 / col_var_encode_s / 1e6, 1),
            "columnar_varlen_decode_mbps": round(
                n * w * 4 / col_var_decode_s / 1e6, 1),
            "serde_native": native,
            "decoded_rows_verified": checked,
            "metrics": _bench_metrics(manager),
        }))
        # BENCH-style trajectory row for the serde series: the headline
        # is the fixed-width columnar DECODE rate (the number ROADMAP
        # item 2 tracks against the fabric GB/s), with the other legs
        # riding as context columns.
        print(json.dumps({
            "metric": "serde_columnar_decode_gbps",
            "value": round(fixed_nbytes / col_fixed_decode_s / 1e9, 3),
            "unit": "GB/s",
            "columnar_fixed_encode_gbps": round(
                fixed_nbytes / col_fixed_encode_s / 1e9, 3),
            "pickle_encode_gbps": round(n * w * 4 / encode_s / 1e9, 3),
            "pickle_decode_gbps": round(n * w * 4 / decode_s / 1e9, 3),
            "serde_native": native,
        }))
        return 0
    finally:
        manager.stop()


if __name__ == "__main__":
    sys.exit(main())
