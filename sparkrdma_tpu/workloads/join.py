"""Hash-join workload — the TPC-DS-style shuffle join (BASELINE.md config 3).

TPC-DS q64/q95 are shuffle-bound because every join first co-partitions
both tables by key across the cluster (Spark's ShuffledHashJoin /
SortMergeJoin exchange). The shuffle legs here are two slotted exchanges
with the same hash partitioner; the local leg is a sort-merge join.

The joined row stream itself is variable-length (XLA-hostile), and the
benchmark queries all end in aggregates anyway — so the local join
produces the two standard reductions directly: match count and
sum-of-payload-products (the inner-join aggregate), combined across the
mesh with a ``psum``. Keys for this workload are single-word (hi word 0).
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import hash_partitioner
from sparkrdma_tpu.kernels.sort import SortStrategy, sort_by_lead_cols
from sparkrdma_tpu.utils.stats import barrier


@dataclasses.dataclass
class JoinResult:
    rows_a: int
    rows_b: int
    matches: int
    sum_products: float
    shuffle_s: float
    join_s: float
    verified: Optional[bool] = None


def _local_join(cols_a, total_a, cols_b, total_b, cap_a, cap_b,
                key_ix: int = 1, pay_ix: int = 2):
    """Per-device sort-merge join -> (count, sum of payload products).

    Inputs are columnar ``[W, cap]`` batches. Sorts both sides by the lo
    key word (one fused variadic sort per side, payload riding along),
    then for each A record looks up B's per-key aggregate via two
    searchsorteds — no pair materialization. ``key_ix``/``pay_ix`` locate
    the join-key and payload words (``conf.key_words - 1`` and
    ``conf.key_words`` for callers with non-default key widths);
    payloads are accumulated as float32 sums.
    """
    ka = cols_a[key_ix]
    kb = cols_b[key_ix]
    va = jnp.arange(cap_a) < total_a[0]
    vb = jnp.arange(cap_b) < total_b[0]

    # substitute a sentinel for padding keys BEFORE sorting: padding
    # sorts to the tail as a block. A VALID record may itself carry the
    # sentinel key value, so validity (not position vs total) decides
    # what counts: both the match count and the payload sum aggregate
    # the validity-masked values over the searchsorted range, which
    # makes interleaved padding contribute exactly zero.
    ka = jnp.where(va, ka, jnp.uint32(0xFFFFFFFF))
    kb = jnp.where(vb, kb, jnp.uint32(0xFFFFFFFF))
    sa, pa, va_s = jax.lax.sort((ka, cols_a[pay_ix], va), num_keys=1,
                                is_stable=True)
    sb, pb, vb_s = jax.lax.sort((kb, cols_b[pay_ix], vb), num_keys=1,
                                is_stable=True)

    # B per-key prefix sums for O(log n) range aggregation
    pb_f = pb.astype(jnp.float32) * vb_s
    csum = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(pb_f)])
    ccnt = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(vb_s.astype(jnp.int32))])
    lo = jnp.searchsorted(sb, sa, side="left")
    hi = jnp.searchsorted(sb, sa, side="right")
    cnt_per_a = (jnp.take(ccnt, hi) - jnp.take(ccnt, lo)) * va_s
    sum_per_a = (jnp.take(csum, hi) - jnp.take(csum, lo)) * va_s
    count = jnp.sum(cnt_per_a).astype(jnp.int32)
    prods = jnp.sum(pa.astype(jnp.float32) * sum_per_a)
    return count, prods


def _local_join_rows(cols_a, total_a, cols_b, total_b, out_capacity,
                     key_ix, kw, val_a, val_b,
                     strategy: SortStrategy = SortStrategy()):
    """Per-device sort-merge join MATERIALIZING the joined rows.

    Spark joins produce row streams; the TPU-native form is a
    fixed-capacity output with an overflow contract (the same contract
    :func:`~sparkrdma_tpu.kernels.sort.compact` uses): returns
    ``(joined [kw + val_a + val_b, out_capacity], count)`` where
    ``count`` is the TRUE match count — ``count > out_capacity`` means
    the caller's capacity was too small and rows beyond it are absent.

    Joined row layout: A's key words, then A's payload words, then B's
    payload words (the standard ``(k, (va, vb))`` pair of ``rdd.join``).

    Mechanics (all fixed-shape, scatter-free): sort both sides by the
    join key (full records move as ``strategy`` says — wide records
    pack or gather, so no record width meets the compile wall); per A row
    ``i`` a searchsorted range ``[lo_i, hi_i)`` of B matches; exclusive
    cumsum of match counts gives each A row's output offset; every
    output slot ``j`` then locates its (A row, B row) pair by one
    searchsorted back into the offsets — a gather, not a scatter.
    """
    cap_a = cols_a.shape[1]
    cap_b = cols_b.shape[1]
    va = jnp.arange(cap_a) < total_a[0]
    vb = jnp.arange(cap_b) < total_b[0]
    ka = jnp.where(va, cols_a[key_ix], jnp.uint32(0xFFFFFFFF))
    kb = jnp.where(vb, cols_b[key_ix], jnp.uint32(0xFFFFFFFF))

    def key_sort(k, v, cols):
        both = jnp.concatenate([v.astype(jnp.uint32)[None], cols])
        k_s, rows = sort_by_lead_cols(both, k, strategy)
        return k_s, rows[0].astype(bool), rows[1:]

    ka_s, va_s, a_rows = key_sort(ka, va, cols_a)  # [Wa, cap_a] sorted
    kb_s, vb_s, b_rows = key_sort(kb, vb, cols_b)  # [Wb, cap_b] sorted

    # per-A-row match range in B, counted by validity (a valid record
    # may carry the sentinel key value — same rule as _local_join)
    ccnt = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(vb_s.astype(jnp.int32))])
    lo = jnp.searchsorted(kb_s, ka_s, side="left")
    hi = jnp.searchsorted(kb_s, ka_s, side="right")
    cnt = (jnp.take(ccnt, hi) - jnp.take(ccnt, lo)) * va_s
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(cnt).astype(jnp.int32)])
    count = starts[-1]
    # int32 cumsum past 2^31 matches wraps negative, which would slip
    # under the caller's count > out_capacity overflow check and return
    # an empty result silently. A wrap of a nonnegative running sum
    # always shows as a decrease somewhere (each step adds < 2^31), so
    # pin count to INT32_MAX on any decrease — the caller's loud
    # overflow contract then fires. (x64 is off, so no int64 cumsum.)
    wrapped = jnp.any(starts[1:] < starts[:-1])
    count = jnp.where(wrapped, jnp.int32(2**31 - 1), count)

    # output slot j -> (A row, B row). B's valid matches for an A row
    # are contiguous in the validity-cumsum domain, so the B row is
    # found by inverting ccnt at (ccnt[lo] + offset-within-range).
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    a_ix = jnp.clip(jnp.searchsorted(starts, j, side="right") - 1,
                    0, cap_a - 1)
    off = j - jnp.take(starts, a_ix)
    b_rank = jnp.take(ccnt, jnp.take(lo, a_ix)) + off   # validity rank
    # first B position with ccnt[pos+1] == b_rank+1 (i.e. the b_rank-th
    # valid row): searchsorted over the inclusive cumsum
    b_ix = jnp.clip(jnp.searchsorted(ccnt[1:], b_rank + 1, side="left"),
                    0, cap_b - 1)
    live = j < jnp.minimum(count, out_capacity)

    a_sel = jnp.take(a_rows, a_ix, axis=1)         # [Wa, out_cap]
    b_sel = jnp.take(b_rows, b_ix, axis=1)         # [Wb, out_cap]
    joined = jnp.concatenate(
        [a_sel[:kw], a_sel[kw:kw + val_a], b_sel[kw:kw + val_b]], axis=0)
    joined = joined * live[None].astype(joined.dtype)
    return joined, count


#: Compiled local-join cache, scoped per manager (weak, so dropping the
#: manager frees its compiled programs) and keyed by capacities —
#: re-jitting per call would make join_s measure trace+compile.
_join_cache: "weakref.WeakKeyDictionary[ShuffleManager, Dict[Tuple, Callable]]" \
    = weakref.WeakKeyDictionary()


def run_hash_join(
    manager: ShuffleManager,
    rows_per_device_a: int,
    rows_per_device_b: int,
    key_range: int = 1 << 12,
    seed: int = 0,
    shuffle_ids: Tuple[int, int] = (30, 31),
    verify: bool = True,
    key_offset_b: int = 0,
) -> JoinResult:
    """``key_offset_b`` shifts B's key range (e.g. by ``key_range`` to make
    the sides provably disjoint — the zero-match path)."""
    rt = manager.runtime
    mesh = rt.num_partitions
    conf = manager.conf
    w = conf.record_words
    if conf.val_words < 1:
        raise ValueError("hash join needs at least one payload word")
    # the join key is the LOW key word; payload is the word after the
    # keys — derived from conf, not a hardcoded key_words==2 layout
    key_ix = conf.key_words - 1
    pay_ix = conf.key_words
    rng = np.random.default_rng(seed)

    def gen(n, key_offset):
        x = np.zeros((mesh * n, w), dtype=np.uint32)
        x[:, key_ix] = rng.integers(0, key_range, size=mesh * n) + key_offset
        x[:, pay_ix] = rng.integers(1, 1000, size=mesh * n)   # payload
        return x

    xa = gen(rows_per_device_a, 0)
    xb = gen(rows_per_device_b, key_offset_b)
    part = hash_partitioner(mesh, manager.conf.key_words)

    t0 = time.perf_counter()
    outs = []
    # Both shuffles stay registered until the join consumed their outputs:
    # unregister disposes the read buffers back to the pool (the reference
    # frees registered buffers on unregisterShuffle), so tearing a shuffle
    # down mid-join would let the other side's exchange recycle its pages.
    for sid, x in zip(shuffle_ids, (xa, xb)):
        handle = manager.register_shuffle(sid, mesh, part)
        writer = manager.get_writer(handle).write(rt.shard_records(x))
        writer.stop(True)
        out, totals = manager.get_reader(handle).read()
        outs.append((out, totals, writer.plan.out_capacity))
    barrier(outs[-1][0])
    shuffle_s = time.perf_counter() - t0

    (oa, ta, ca), (ob, tb, cb) = outs
    ax = rt.axis_name

    cache = _join_cache.setdefault(manager, {})
    cache_key = (ca, cb, key_ix, pay_ix)
    joined = cache.get(cache_key)
    if joined is None:
        def local(rows_a, total_a, rows_b, total_b):
            c, s = _local_join(rows_a, total_a, rows_b, total_b, ca, cb,
                               key_ix=key_ix, pay_ix=pay_ix)
            return (jax.lax.psum(c, ax)[None], jax.lax.psum(s, ax)[None])

        joined = jax.jit(shard_map(
            local, mesh=rt.mesh,
            in_specs=(P(None, ax), P(ax), P(None, ax), P(ax)),
            out_specs=(P(ax), P(ax)),
        ))
        cache[cache_key] = joined
    t0 = time.perf_counter()
    count, prods = joined(oa, ta, ob, tb)
    count = int(np.asarray(count)[0])
    prods = float(np.asarray(prods)[0])
    join_s = time.perf_counter() - t0

    for sid in shuffle_ids:
        manager.unregister_shuffle(sid)

    verified = None
    if verify:
        ref_count, ref_sum = _numpy_reference_join(xa, xb, key_ix, pay_ix)
        verified = (count == ref_count
                    and abs(prods - ref_sum) <= 1e-6 * max(1.0, abs(ref_sum)))
    return JoinResult(
        rows_a=xa.shape[0], rows_b=xb.shape[0], matches=count,
        sum_products=prods, shuffle_s=shuffle_s, join_s=join_s,
        verified=verified,
    )


def _numpy_reference_join(xa: np.ndarray, xb: np.ndarray,
                          key_ix: int = 1,
                          pay_ix: int = 2) -> Tuple[int, float]:
    ka, pa = xa[:, key_ix], xa[:, pay_ix].astype(np.float64)
    kb, pb = xb[:, key_ix], xb[:, pay_ix].astype(np.float64)
    sum_b: Dict[int, float] = {}
    cnt_b: Dict[int, int] = {}
    for k, p in zip(kb, pb):
        sum_b[k] = sum_b.get(k, 0.0) + p
        cnt_b[k] = cnt_b.get(k, 0) + 1
    count = sum(cnt_b.get(k, 0) for k in ka)
    total = sum(pa[i] * sum_b.get(ka[i], 0.0) for i in range(len(ka)))
    return count, total


__all__ = ["run_hash_join", "JoinResult"]
