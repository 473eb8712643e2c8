"""Map-side partition bucketing and slot packing (columnar).

This is the map half of the data path. In the reference, map output is
produced by stock Spark (``SortShuffleWriter`` -> ``ExternalSorter``: sort
records by reduce-partition id into one data file + an index file of
per-partition offsets), and ``RdmaMappedFile`` then exposes each partition
as an ``(addr, len)`` range for one-sided READ (src/main/java/org/apache/
spark/shuffle/rdma/RdmaMappedFile.java §getRdmaBlockLocation).

Here the same two steps happen in HBM, on COLUMNAR record batches
``uint32[W, N]`` (one contiguous vector per record word — see
``MeshRuntime.shard_records`` for the layout rationale):

- :func:`bucket_records` = the ExternalSorter: one variadic ``lax.sort``
  keyed on destination partition, every word column riding along as a
  value — the "data file" (bucketed columns) and "index file"
  (counts/offsets) in one fused pass.
  :func:`bucket_records_hash_keyed` is its form for a hash partitioner
  on a read that keeps no order within a partition: the record's own
  hash order key takes the place of its last key word, so no partition
  id rides the sort.
- :func:`fill_round_slots` = RdmaMappedFile + the fetcher's block
  aggregation: carve the bucketed columns into fixed-capacity
  per-destination windows for exchange round ``r``. Each window is a
  contiguous ``dynamic_slice`` — literally an RDMA READ of byte range
  ``(addr=offsets[p] + r*cap, len=cap)``. Fixed capacity is what turns
  SparkRDMA's exact-byte-range READs into XLA-legal static shapes;
  partitions larger than one slot stream across rounds (the
  ``maxAggBlock`` / chunked-READ analogue, SURVEY.md §5 long-context row).
- :func:`compact_segments` is the reduce-side inverse: concatenate the
  valid prefixes of received fixed-stride segments by chained contiguous
  copies (ascending order repairs each zero tail).

All functions are jit-safe per-device functions (no collectives).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkrdma_tpu.exchange.partitioners import HashOrder
from sparkrdma_tpu.kernels.sort import SortStrategy, sort_by_lead_cols
from sparkrdma_tpu.utils.profiling import device_phase

#: Above this many serially-dependent copies, emit a device loop instead of
#: unrolling — keeps program size O(1) in partition/segment count.
_UNROLL_LIMIT = 16

#: Low bits of a partition id in :func:`histogram_pids`' outer product:
#: 16 low bins by ``ceil(P / 16)`` high bins.
_LO_BITS = 4


def histogram_pids(part_ids: jax.Array, num_parts: int,
                   sorted_ids: jax.Array | None = None) -> jax.Array:
    """Per-partition record counts WITHOUT ``jnp.bincount``.

    bincount lowers to scatter-add, which on TPU is an operand-bound
    serial disaster — measured ~147ms for 16M records into 8 bins (it
    was the single largest op in the multi-partition exchange program).
    Three scatter-free forms, picked by what the caller has:

    - ``sorted_ids`` given: binary-search the boundaries of the
      ALREADY-SORTED pid vector (the bucketing sort has it for free) —
      P+1 tiny probes.
    - ``num_parts <= 32``: one comparison+reduction pass per partition.
    - otherwise: one outer product of two one-hots on the MXU
      (:func:`_outer_histogram`) — the plan's count pass over 134M
      hash-partitioned ids into 256 bins takes 8.6ms of device time on
      one v5e, where sorting the ids to search them took 413.7ms.

    Out-of-range ids are DROPPED by every form (bincount would clip
    negatives into bin 0): a partitioner that strays loses records from
    the counts, which ``ShuffleExchange.plan`` turns into an error —
    every partitioner in :mod:`sparkrdma_tpu.exchange.partitioners`
    produces in-range ids by construction (mod/clip).
    """
    part_ids = part_ids.astype(jnp.int32)
    if sorted_ids is not None:
        edges = jnp.searchsorted(
            sorted_ids, jnp.arange(num_parts + 1, dtype=jnp.int32))
        return (edges[1:] - edges[:-1]).astype(jnp.int32)
    if num_parts <= 32:
        return jnp.stack([
            jnp.sum((part_ids == p).astype(jnp.int32))
            for p in range(num_parts)])
    return _outer_histogram(part_ids, num_parts)


def _outer_histogram(part_ids: jax.Array, num_parts: int) -> jax.Array:
    """Counts in one pass: split each id as ``hi * 16 + lo``; then
    ``counts[hi, lo] = sum_n [hi_n == hi] * [lo_n == lo]`` is a product
    of a ``[ceil(P/16), N]`` and a ``[16, N]`` one-hot contracted over
    the record axis.

    The one-hots are int8 and accumulate in int32, so every count is
    exact up to 2**31 - 1 with no chunking. On TPU, XLA fuses the
    comparisons into the product, so no ``[·, N]`` one-hot reaches HBM:
    the temporaries are the ``hi`` and ``lo`` vectors, as large as the
    sort's. Out-of-range ids take ``hi = -1``, which matches no bin, so
    they are dropped.
    """
    lo_bins = 1 << _LO_BITS
    hi_bins = -(-num_parts // lo_bins)
    in_range = (part_ids >= 0) & (part_ids < num_parts)
    hi = jnp.where(in_range, part_ids >> _LO_BITS, -1)
    lo = part_ids & (lo_bins - 1)

    def one_hot(v, bins):
        return (v[None, :] == lax.iota(jnp.int32, bins)[:, None]
                ).astype(jnp.int8)

    grid = lax.dot_general(one_hot(hi, hi_bins), one_hot(lo, lo_bins),
                           (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)
    return grid.reshape(-1)[:num_parts]


def bucket_records(
    records: jax.Array, part_ids: jax.Array, num_parts: int,
    strategy: SortStrategy = SortStrategy(), stable: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort a columnar batch ``[W, N]`` by destination partition.

    Returns ``(bucketed [W, N], counts [P], offsets [P])`` where
    ``counts[p]`` is the number of local records bound for partition ``p``
    and ``offsets[p]`` the start of its run — the exact content of Spark's
    shuffle index file. One lead-keyed sort
    (:func:`~sparkrdma_tpu.kernels.sort.sort_by_lead_cols`, moving the
    record words as ``strategy`` says): pid is the key; counts come from
    the sorted pid vector (see :func:`histogram_pids`), not a scatter.

    ``stable`` (the default) keeps arrival order within a partition. XLA
    makes a sort stable with a hidden s32 iota as its last key, so
    ``stable=False`` drops an operand and a compare key: on the plain
    strategy at ``W = 2`` the sort carries 3 operands instead of 4.
    Counts, offsets, partition contiguity and which records land in
    which partition are the same either way; only the order of records
    within one partition may differ. The wide strategy is stable
    regardless (its index operand is the permutation it places rows by).
    """
    n = records.shape[1]
    if num_parts == 1:
        # single destination: the batch IS the one run — no reorder, no
        # histogram (the degenerate case a 1-chip mesh hits on its hot
        # path; the monolithic 5-operand sort this skips is ~100ms at
        # 16M records on TPU, measured scripts/profile_sweep.py sortform)
        return (records,
                jnp.full((1,), n, jnp.int32),
                jnp.zeros((1,), jnp.int32))
    part_ids = part_ids.astype(jnp.int32)
    sorted_ids, bucketed = sort_by_lead_cols(records, part_ids, strategy,
                                             stable)
    return (bucketed,) + bucket_sorted_counts(sorted_ids, num_parts)


def bucket_records_hash_keyed(
    records: jax.Array, order: HashOrder, num_parts: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`bucket_records` for a hash partitioner, unstable, on the
    plain strategy, with the partition id carried INSIDE the record.

    The hash's order key (:class:`~sparkrdma_tpu.exchange.partitioners.
    HashOrder`) sorts by partition first and is a bijection of the last
    key word given the others, so it replaces that word in the sort:
    ``W`` operands, where the pid-keyed sort carries ``W + 1``. After
    the sort the pid and the last key word are rebuilt elementwise and
    the word goes back to its row. Same contract and result as
    ``bucket_records(records, pids, num_parts, stable=False)`` but for
    the order of records within one partition.
    """
    k, w = order.key_words, records.shape[0]
    rest = tuple(records[i] for i in range(w) if i != k - 1)
    out = lax.sort((order.order_key(records),) + rest, num_keys=1,
                   is_stable=False)
    lead = out[1:k]
    pids, last = order.invert(out[0], lead)
    bucketed = jnp.stack(lead + (last,) + out[k:])
    return (bucketed,) + bucket_sorted_counts(pids, num_parts)


def bucket_sorted_counts(
    sorted_pids: jax.Array, num_parts: int
) -> Tuple[jax.Array, jax.Array]:
    """Counts/offsets for a batch ALREADY sorted ascending by partition.

    The map-side-combine and predicate-pushdown paths produce their
    bucketed layout directly (``map_side_combine_cols`` sorts by
    (partition, key); dropped rows carry the sentinel pid ``num_parts``
    on the tail), so :func:`bucket_records`' own sort would be a wasted
    full pass — this computes just its index-file half. Sentinel rows
    fall outside ``[0, num_parts)`` and are therefore excluded from
    every count: they never occupy a slot in
    :func:`fill_round_slots` / :func:`fill_round_slots_dest_major`
    (whose per-window masks derive from these counts).
    """
    counts = histogram_pids(sorted_pids, num_parts,
                            sorted_ids=sorted_pids.astype(jnp.int32))
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    return counts, offsets


@device_phase("sr_slots")
def fill_round_slots(
    bucketed: jax.Array,
    counts: jax.Array,
    offsets: jax.Array,
    num_parts: int,
    capacity: int,
    round_idx,
) -> Tuple[jax.Array, jax.Array]:
    """Pack round ``round_idx``'s window of each bucket into send slots.

    Slot ``p`` receives records ``[r*capacity, (r+1)*capacity)`` of bucket
    ``p``. Returns ``(slots: uint32[W, num_parts, capacity], send_counts:
    int32[num_parts])``; tails beyond ``send_counts[p]`` are zero padding.

    ``num_parts`` contiguous window reads per column at HBM bandwidth —
    a per-row gather of narrow records would use W of the VPU's 128 lanes.
    Small partition counts unroll statically; large ones use a
    ``lax.scan`` so program size stays O(1) in ``num_parts`` (the copies
    are serially dependent either way — a repartition(256) geometry must
    not produce a 256-body program).
    """
    w, n = bucketed.shape
    round_idx = jnp.asarray(round_idx, jnp.int32)
    c = jnp.arange(capacity, dtype=jnp.int32)
    send_counts = jnp.clip(counts - round_idx * capacity, 0, capacity)
    valid = (c[None, :] < send_counts[:, None])           # [P, C]
    pad = jnp.zeros((w, capacity), bucketed.dtype)
    # pad so every window is in-bounds (dynamic_slice clamps otherwise,
    # which would silently shift a window into the previous bucket)
    padded = jnp.concatenate([bucketed, pad], axis=1)     # [W, N+C]
    if num_parts <= _UNROLL_LIMIT:
        windows = []
        for p in range(num_parts):  # static unroll: P contiguous copies
            start = offsets[p] + round_idx * capacity
            windows.append(
                lax.dynamic_slice(padded, (0, start), (w, capacity)))
        slots = jnp.stack(windows, axis=1)                # [W, P, C]
    else:
        def window(_, p):
            start = offsets[p] + round_idx * capacity
            return None, lax.dynamic_slice(padded, (0, start),
                                           (w, capacity))
        _, wins = lax.scan(window, None,
                           jnp.arange(num_parts, dtype=jnp.int32))
        slots = wins.transpose(1, 0, 2)                   # [W, P, C]
    slots = slots * valid[None].astype(slots.dtype)
    return slots, send_counts.astype(jnp.int32)


@device_phase("sr_slots")
def fill_round_slots_dest_major(
    bucketed: jax.Array,
    counts: jax.Array,
    offsets: jax.Array,
    num_parts: int,
    mesh_size: int,
    capacity: int,
    round_idx,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`fill_round_slots` emitting the transport layout directly.

    Returns ``(slots: uint32[mesh_size, ppd, W, capacity], send_counts:
    int32[num_parts])`` where ``slots[d, q]`` is the round's window of
    partition ``p = q * mesh_size + d`` (partition ``p`` lives on device
    ``p % mesh_size`` — the exchange's round-robin ownership rule).

    Bit-identical to ``fill_round_slots(...)[0].reshape(W, ppd, mesh,
    C).transpose(2, 1, 0, 3)`` (pinned by tests), but WITHOUT the
    reshape/transpose pass: the per-partition window reads are issued in
    destination-major order, so the stacked result already has the
    ``[mesh, ppd, W, C]`` shape the ring transport DMAs. On the fused
    pallas-ring path this removes one full HBM round-trip of the slot
    tensor per exchange round (the staging layout between bucketing and
    dispatch that ISSUE 8 / ROADMAP item 2 target).
    """
    w, n = bucketed.shape
    ppd = num_parts // mesh_size
    round_idx = jnp.asarray(round_idx, jnp.int32)
    c = jnp.arange(capacity, dtype=jnp.int32)
    send_counts = jnp.clip(counts - round_idx * capacity, 0, capacity)
    pad = jnp.zeros((w, capacity), bucketed.dtype)
    # pad so every window is in-bounds (dynamic_slice clamps otherwise,
    # which would silently shift a window into the previous bucket)
    padded = jnp.concatenate([bucketed, pad], axis=1)      # [W, N+C]
    # dest-major flat order t = d*ppd + q reads partition p = q*mesh + d
    t_ix = jnp.arange(num_parts, dtype=jnp.int32)
    pids = (t_ix % ppd) * mesh_size + t_ix // ppd

    def window(p):
        start = offsets[p] + round_idx * capacity
        win = lax.dynamic_slice(padded, (0, start), (w, capacity))
        # same per-(p, c) 0/1 mask as fill_round_slots, applied per
        # window so the masked stack needs no second full-tensor pass
        return win * (c[None, :] < send_counts[p]).astype(win.dtype)

    if num_parts <= _UNROLL_LIMIT:
        wins = jnp.stack([window(jnp.int32((t % ppd) * mesh_size + t // ppd))
                          for t in range(num_parts)], axis=0)
    else:
        _, wins = lax.scan(lambda _, p: (None, window(p)), None, pids)
    # leading-axis reshape only — no transpose, the data is already laid
    # out dest-major
    slots = wins.reshape(mesh_size, ppd, w, capacity)
    return slots, send_counts.astype(jnp.int32)


@device_phase("sr_compact")
def compact_segments(
    stream: jax.Array, seg_counts: jax.Array, out_capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Concatenate the valid prefixes of fixed-stride segments.

    ``stream: [W, S*C]`` where segment ``s`` occupies columns ``[s*C, s*C
    + seg_counts[s])`` (prefix-valid, zero tail) — the layout the exchange
    produces per (local partition, source, round). Validity is
    per-segment-prefix, so the compaction is S chained contiguous
    ``dynamic_update_slice`` copies written in ascending segment order:
    each segment's zero tail is overwritten by the next segment's data,
    and the final tail is masked. No sort, no gather.

    Returns ``(packed: [W, out_capacity], total)``; ``total`` may exceed
    ``out_capacity`` (overflow is the caller's contract, as in
    :func:`~sparkrdma_tpu.kernels.sort.compact`).
    """
    w, sc = stream.shape
    s = seg_counts.shape[0]
    c = sc // s
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(seg_counts).astype(jnp.int32)])
    total = cum[-1]
    # +C headroom so the last write never clamps (clamping would shift the
    # window backward over valid data). The zero is derived from the data
    # so the loop carry's varying-manual-axes type matches the body output
    # under shard_map (a constant init would be unvarying -> fori_loop
    # carry type error).
    vzero = stream[0, 0] & stream.dtype.type(0)
    out = jnp.zeros((w, out_capacity + c), stream.dtype) + vzero

    def copy_seg(i, out):  # ascending: later segments repair earlier tails
        seg = lax.dynamic_slice(stream, (0, i * c), (w, c))
        dst = jnp.minimum(cum[i], out_capacity)
        return lax.dynamic_update_slice(out, seg, (0, dst))

    if s <= _UNROLL_LIMIT:
        for i in range(s):
            out = copy_seg(i, out)
    else:
        out = lax.fori_loop(0, s, copy_seg, out)
    packed = out[:, :out_capacity]
    valid = jnp.arange(out_capacity, dtype=jnp.int32) < total
    packed = packed * valid[None, :].astype(packed.dtype)
    return packed, total


__all__ = ["bucket_records", "bucket_records_hash_keyed",
           "bucket_sorted_counts", "fill_round_slots",
           "fill_round_slots_dest_major", "compact_segments"]
