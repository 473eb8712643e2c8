"""TeraSort — the north-star workload (BASELINE.md config 2).

HiBench TeraSort on Spark is ``sortByKey`` over 100-byte records with
10-byte keys: sample -> RangePartitioner -> full shuffle -> per-partition
sort; the global output is the concatenation of sorted partitions in
partition order. The reference accelerates only the shuffle leg; correctness
is judged on the final sort (SURVEY.md §4 north star: output globally
sorted and a permutation of the input).

TPU-native pipeline (one partition per chip, partition p on device p):

1. compiled strided sample + all_gather          (meta/sampling.py)
2. identical quantile splitters on every host    (compute_splitters)
3. range-partitioned slotted exchange            (exchange/protocol.py)
4. per-chip lexicographic sort of the received prefix (kernels/sort.py)

Validation checks the three invariants that make a sort a sort:
conservation (count + key checksum), intra-device order, and inter-device
boundary order.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import range_partitioner
from sparkrdma_tpu.meta.sampling import compute_splitters, make_sampler
from sparkrdma_tpu.utils.stats import barrier


@dataclasses.dataclass
class TeraSortResult:
    records: int
    record_bytes: int
    sample_s: float
    plan_s: float
    sort_exchange_s: float
    verified: bool

    @property
    def total_bytes(self) -> int:
        return self.records * self.record_bytes

    @property
    def gbps(self) -> float:
        return self.total_bytes / max(self.sort_exchange_s, 1e-9) / 1e9


def validate_global_sort(
    out: np.ndarray, totals: np.ndarray, x_input: np.ndarray,
    key_words: int, out_capacity: int,
) -> bool:
    """Sorted + permutation-of-input check (host-side, test-sized data).

    ``out`` is the columnar read result ``[W, mesh*out_capacity]``;
    ``x_input`` is host rows ``[N, W]``.
    """
    mesh = totals.shape[0]
    prev_max = None
    collected = []
    for d in range(mesh):
        k = int(totals[d])
        dev = out[:, d * out_capacity:d * out_capacity + k].T  # rows [k, W]
        collected.append(dev)
        if k == 0:
            continue
        keys = dev[:, :key_words].astype(np.uint64)
        flat = keys[:, 0]
        for w in range(1, key_words):
            flat = (flat << np.uint64(32)) | keys[:, w]
        if np.any(flat[1:] < flat[:-1]):
            return False
        if prev_max is not None and flat[0] < prev_max:
            return False
        prev_max = flat[-1]
    got = np.concatenate(collected) if collected else np.zeros_like(x_input)
    if got.shape[0] != x_input.shape[0]:
        return False
    # permutation check: row-wise multiset equality via canonical sort
    return bool(np.array_equal(canonical_rows(got, key_words),
                               canonical_rows(x_input, key_words)))


def canonical_rows(rows: np.ndarray, key_words: int) -> np.ndarray:
    """``rows [N, W]`` in full-record lexicographic order.

    One sort on the key words, then a full-record sort of only the rows
    whose key repeats — the same order as a lexsort over all ``W``
    columns, at a fraction of its cost when keys are nearly unique
    (random 10-byte TeraSort keys): two sort keys instead of 25 at 16M
    rows."""
    def lex(a, words):
        return np.lexsort(tuple(a[:, c] for c in range(words - 1, -1, -1)))

    out = rows[lex(rows, key_words)]
    keys = out[:, :key_words]
    same = np.all(keys[1:] == keys[:-1], axis=1)
    if same.any():
        in_run = np.zeros(out.shape[0], bool)
        in_run[1:] |= same
        in_run[:-1] |= same
        idx = np.flatnonzero(in_run)
        # key words lead the full-record order, so sorting the runs'
        # rows together keeps every run at its own positions
        sub = out[idx]
        out[idx] = sub[lex(sub, sub.shape[1])]
    return out


def device_verify_sort(
    manager: ShuffleManager,
    records: jax.Array,
    out: jax.Array,
    totals: jax.Array,
    key_words: int,
    out_capacity: int,
) -> bool:
    """Cheap large-scale invariant check, entirely on device.

    Validates the three properties that make a sort a sort without the
    O(n log n) host-side permutation check (bench scale: the host check
    would dwarf the measured exchange):

    - conservation: record count, per-word uint32 sums, AND a summed
      per-record multiplicative hash of the output's valid prefix match
      the input's. Plain per-word sums are blind to multi-record
      cancellations (e.g. dup {2,2} replacing {1,3} in one word); the
      mixed hash makes such dup/drop pairs collide only if the full
      word-mixing hash sums collide mod 2^32 — no longer constructible
      by linear arithmetic on single words;
    - intra-device order: every device's valid prefix is lexicographically
      non-decreasing on the key words;
    - inter-device order: device boundaries ascend (first/last keys).

    One compiled elementwise+reduction pass per side (~2 HBM reads);
    catches dropped/duplicated/corrupted/misordered records. Not a full
    permutation proof — pair with the host check at test scale.
    """
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rt = manager.runtime
    ax = rt.axis_name
    w = records.shape[0]

    def rec_hash(cols):
        """Per-record word-mixing hash (murmur-style, order-invariant
        only across records, not across words within a record)."""
        h = jnp.full(cols.shape[1], 0x9E3779B9, jnp.uint32)
        for i in range(w):
            h = h ^ (cols[i] * jnp.uint32(0x85EBCA6B))
            h = (h << 13) | (h >> 19)
            h = h * jnp.uint32(0xC2B2AE35)
        return h

    def in_sums(cols):
        s = jnp.stack([jnp.sum(cols[i], dtype=jnp.uint32) for i in range(w)]
                      + [jnp.sum(rec_hash(cols), dtype=jnp.uint32)])
        n = jnp.full((1,), cols.shape[1], jnp.int32)
        return s[None], n

    def out_checks(cols, total):
        valid = jnp.arange(out_capacity) < total[0]
        vu = valid.astype(jnp.uint32)
        s = jnp.stack([jnp.sum(cols[i] * vu, dtype=jnp.uint32)
                       for i in range(w)]
                      + [jnp.sum(rec_hash(cols) * vu, dtype=jnp.uint32)])
        count = total[0]
        # lexicographic non-decreasing over key words on the valid prefix
        gt = jnp.zeros((out_capacity - 1,), bool)   # prev > next so far
        eq = jnp.ones((out_capacity - 1,), bool)
        for k in range(key_words):
            a, b = cols[k][:-1], cols[k][1:]
            gt = gt | (eq & (a > b))
            eq = eq & (a == b)
        ordered = jnp.all(~gt | ~valid[1:])
        # boundary keys (first/last valid) for the host's cross-device check
        first = jnp.stack([cols[k][0] for k in range(key_words)])
        last_ix = jnp.maximum(total[0] - 1, 0)
        last = jnp.stack([cols[k][last_ix] for k in range(key_words)])
        return (s[None], count[None], ordered[None],
                first[None], last[None])

    in_fn = jax.jit(shard_map(in_sums, mesh=rt.mesh,
                              in_specs=(P(None, ax),),
                              out_specs=(P(ax), P(ax))))
    out_fn = jax.jit(shard_map(out_checks, mesh=rt.mesh,
                               in_specs=(P(None, ax), P(ax)),
                               out_specs=(P(ax),) * 5))
    s_in, n_in = map(np.asarray, in_fn(records))
    s_out, n_out, ordered, first, last = map(np.asarray, out_fn(out, totals))
    if int(n_in.sum()) != int(n_out.sum()):
        return False
    if not np.array_equal(s_in.sum(axis=0, dtype=np.uint32),
                          s_out.sum(axis=0, dtype=np.uint32)):
        return False
    if not bool(ordered.all()):
        return False
    # device boundaries ascend (devices with 0 records are skipped)
    tot = np.asarray(totals)
    prev = None
    for d in range(tot.shape[0]):
        if tot[d] == 0:
            continue
        fk = int.from_bytes(first[d].astype(">u4").tobytes(), "big")
        lk = int.from_bytes(last[d].astype(">u4").tobytes(), "big")
        if prev is not None and fk < prev:
            return False
        prev = lk
    return True


def run_terasort(
    manager: ShuffleManager,
    records_per_device: int,
    seed: int = 0,
    shuffle_id: int = 1,
    samples_per_device: int = 256,
    verify: bool = True,
    warmup: bool = True,
    input_records: Optional[jax.Array] = None,
    repeats: int = 1,
    device_verify: bool = False,
) -> Tuple[TeraSortResult, jax.Array, jax.Array]:
    """Returns ``(result, sorted_records, totals)``.

    ``repeats > 1`` measures steady-state shuffle throughput: the timed
    region re-runs the full exchange+sort ``repeats`` times back-to-back
    (dispatches pipeline; output buffers ping-pong through the slot pool)
    and ``sort_exchange_s`` is the per-iteration mean — amortizing
    per-dispatch latency exactly as line-rate NIC numbers do.
    ``device_verify`` adds the cheap on-device invariant check
    (:func:`device_verify_sort`), usable at bench scale.

    The returned ``sorted_records`` is detached from the shuffle's pooled
    buffer (copied before ``unregister_shuffle`` releases that buffer to
    the pool), so callers may hold it across later exchanges safely."""
    rt = manager.runtime
    mesh = rt.num_partitions
    kw = manager.conf.key_words
    if input_records is None:
        rng = np.random.default_rng(seed)
        x = rng.integers(0, 2**32,
                         size=(mesh * records_per_device,
                               manager.conf.record_words), dtype=np.uint32)
        records = rt.shard_records(x)
        n_records, rec_words = x.shape
    else:
        records = input_records          # columnar [W, N]
        rec_words, n_records = records.shape
        # full D2H transpose only when the permutation check needs it
        x = rt.host_rows(records) if verify else None

    # 1-2: sample on-fabric, splitters everywhere
    t0 = time.perf_counter()
    sampler = make_sampler(rt.mesh, rt.axis_name, kw, samples_per_device)
    samples = np.asarray(jax.device_get(sampler(records)))
    splitters = compute_splitters(samples, mesh)
    sample_s = time.perf_counter() - t0

    part = range_partitioner(splitters, kw)
    handle = manager.register_shuffle(shuffle_id, mesh, part)
    try:
        writer = manager.get_writer(handle).write(records)
        t0 = time.perf_counter()
        plan = writer.stop(True)
        plan_s = time.perf_counter() - t0

        reader = manager.get_reader(handle, key_ordering=True)
        if warmup:
            # finish the warmup (compile + first run) before the clock
            # starts, so it cannot leak into the timed region
            barrier(reader.read(record_stats=False)[0])
        t0 = time.perf_counter()
        for _ in range(repeats - 1):
            # steady state: each read is a complete exchange+sort; the
            # donation chain through the pool serializes them correctly
            reader.read(record_stats=False)
        out, totals = reader.read()
        barrier(out)
        sort_exchange_s = (time.perf_counter() - t0) / max(repeats, 1)

        verified = True
        if verify:
            verified = validate_global_sort(
                np.asarray(out), np.asarray(totals), x, kw, plan.out_capacity
            )
        if device_verify:
            verified = verified and device_verify_sort(
                manager, records, out, totals, kw, plan.out_capacity)
        res = TeraSortResult(
            records=n_records,
            record_bytes=rec_words * 4,
            sample_s=sample_s,
            plan_s=plan_s,
            sort_exchange_s=sort_exchange_s,
            verified=verified,
        )
        # detach from the pool-recycled exchange buffer: the finally
        # block's unregister releases that buffer for reuse, and a later
        # same-shape exchange would donate (delete) it out from under the
        # caller (round-2 advisor finding)
        out = jnp.array(out)
        return res, out, totals
    finally:
        manager.unregister_shuffle(shuffle_id)


__all__ = ["run_terasort", "TeraSortResult", "validate_global_sort",
           "canonical_rows", "device_verify_sort"]
