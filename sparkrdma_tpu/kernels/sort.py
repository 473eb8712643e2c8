"""Reduce-side kernels: compaction, wide-key sort, run merge.

In the reference the reduce side hands fetched blocks to stock Spark:
decompress -> deserialize -> optional ``Aggregator`` combine -> optional
``ExternalSorter`` key-ordering spill-sort (RdmaShuffleReader §read). Here
the same post-fetch stages run in HBM on fixed-shape arrays:

- :func:`compact` squeezes the valid prefix out of padded exchange slots
  (the analogue of consuming completed fetch buffers off the result queue);
- :func:`lexsort_records` is the ExternalSorter analogue: sort records by a
  multi-word (e.g. 64-bit as hi/lo uint32) key;
- :func:`merge_sorted_runs` exploits that each source's run arrives already
  key-sorted (when the writer pre-sorts), like Spark's tiered merge.

Keys sort lexicographically over their uint32 words, most-significant word
first — matching TeraSort's byte-lexicographic ordering.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import enable_x64, lax


def _sort_rows(records: jax.Array, num_keys: int,
               lead_keys: Tuple[jax.Array, ...] = ()) -> jax.Array:
    """Sort rows of ``records: [N, W]`` by ``lead_keys`` then the leading
    ``num_keys`` columns, lexicographically, via ONE fused ``lax.sort``.

    A single variadic sort (XLA's native lexicographic comparator over
    ``num_keys`` operands) replaces the chained per-word stable
    argsort+gather passes — one sort network instead of K+1, and the
    payload columns ride along as values instead of being gathered
    afterwards. Stable, so equal keys keep their arrival order.
    """
    n, w = records.shape
    cols = tuple(records[:, i] for i in range(w))
    operands = lead_keys + cols
    out = lax.sort(operands, num_keys=len(lead_keys) + num_keys,
                   is_stable=True)
    return jnp.stack(out[len(lead_keys):], axis=1)


def compact(
    records: jax.Array, valid: jax.Array, out_capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Pack valid records to the front; return ``(packed, count)``.

    ``records: [N, W]``, ``valid: bool[N]``. Output has static shape
    ``[out_capacity, W]`` (zero-padded). A stable sort on the inverted
    mask is XLA's native way to partition without dynamic shapes.

    ``count`` is the TRUE number of valid records, which may exceed
    ``out_capacity``; callers must treat ``count > out_capacity`` as
    overflow (records beyond capacity are not in ``packed``) and size
    capacity accordingly — the analogue of a fetch buffer too small for the
    block, which the reference also surfaces to the caller rather than
    resizing silently.
    """
    n = records.shape[0]
    packed = _sort_rows(records, 0,
                        lead_keys=((~valid).astype(jnp.uint8),))
    if out_capacity <= n:
        packed = packed[:out_capacity]
    else:
        packed = jnp.pad(packed, ((0, out_capacity - n), (0, 0)))
    count = jnp.sum(valid).astype(jnp.int32)
    live = jnp.minimum(count, out_capacity)
    packed = packed * (jnp.arange(out_capacity) < live)[:, None].astype(
        packed.dtype
    )
    return packed, count


def lexsort_records(
    records: jax.Array, key_words: int, valid: jax.Array | None = None
) -> jax.Array:
    """Sort ``records: uint32[N, W]`` by their leading ``key_words`` words.

    Padding rows (``valid == False``) are moved to the tail regardless of
    key value. Stable within equal keys. Row-major convenience wrapper
    (host-scale data, tests); the device data path uses
    :func:`lexsort_cols`.
    """
    lead = () if valid is None else ((~valid).astype(jnp.uint8),)
    return _sort_rows(records, key_words, lead_keys=lead)


def lexsort_cols(
    cols: jax.Array, key_words: int, valid: jax.Array | None = None,
    stable: bool = True
) -> jax.Array:
    """Sort a columnar batch ``uint32[W, N]`` by its leading ``key_words``
    word rows — one fused variadic ``lax.sort`` over contiguous columns.

    Padding (``valid == False``) sorts to the tail. Stable by default;
    pass ``stable=False`` where equal-key arrival order is not part of
    the caller's contract (Spark's ``sortByKey`` promises none) — the
    unstable network measures ~6% faster at 16M x 13 operands on v5e.
    """
    w, n = cols.shape
    lead = () if valid is None else ((~valid).astype(jnp.uint8),)
    out = lax.sort(lead + tuple(cols[i] for i in range(w)),
                   num_keys=len(lead) + key_words, is_stable=stable)
    return jnp.stack(out[len(lead):])


def _pack_u64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """One u64 row from two u32 rows, ``hi`` in the high bits — u64
    ascending order == (hi, lo) lexicographic ascending. Bitcast only
    (little-endian minor-dim pack), no shift arithmetic."""
    return lax.bitcast_convert_type(jnp.stack([lo, hi], axis=-1),
                                    jnp.uint64)


def _unpack_u64(p: jax.Array) -> Tuple[jax.Array, jax.Array]:
    two = lax.bitcast_convert_type(p, jnp.uint32)       # [N, 2]
    return two[:, 1], two[:, 0]


def packed_lexsort_cols(
    cols: jax.Array, key_words: int, valid: jax.Array | None = None,
    stable: bool = False
) -> jax.Array:
    """:func:`lexsort_cols` with u64 OPERAND PACKING — same contract,
    roughly half the operand count at equal bytes.

    Round-5 measurement (scripts/profile_sweep.py pack + ab, v5e 16M
    records): variadic sort cost turns superlinear in OPERAND COUNT
    past ~13, so carrying 25 words as 13 packed operands (1 u64 key +
    11 u64 + 1 u32 payload) runs ~25% faster than the 25-operand
    monolithic AND beats the ride/gather wide path (the gather pays
    143ms fixed + 15.3ms/word; packing makes riding everything cheaper
    than placing anything). Key word pairs pack hi||lo so u64 ascending
    == lexicographic ascending; an odd trailing key word stays a u32
    key operand of its own. The u64 dtype exists only INSIDE this
    kernel (``jax.enable_x64`` trace context) — inputs and outputs are
    u32, and the process-wide x64 flag is untouched.
    """
    w, n = cols.shape
    with enable_x64(True):
        keys = []
        for i in range(0, key_words - 1, 2):
            keys.append(_pack_u64(cols[i], cols[i + 1]))
        if key_words % 2:
            keys.append(cols[key_words - 1])
        vals = []
        odd = None
        for i in range(key_words, w - 1, 2):
            vals.append(_pack_u64(cols[i], cols[i + 1]))
        if (w - key_words) % 2:
            odd = cols[w - 1]
        lead = () if valid is None else ((~valid).astype(jnp.uint8),)
        operands = lead + tuple(keys) + tuple(vals) \
            + ((odd,) if odd is not None else ())
        out = lax.sort(operands, num_keys=len(lead) + len(keys),
                       is_stable=stable)
        out = out[len(lead):]
        rows = []
        for i, o in enumerate(out[:len(keys)]):
            if key_words % 2 and i == len(keys) - 1:
                rows.append(o)
            else:
                hi, lo = _unpack_u64(o)
                rows += [hi, lo]
        for o in out[len(keys):len(keys) + len(vals)]:
            hi, lo = _unpack_u64(o)
            rows += [hi, lo]
        if odd is not None:
            rows.append(out[-1])
    return jnp.stack(rows)


def packed_partition_cols(
    cols: jax.Array, lead: jax.Array, stable: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Sort full records by a single u32 ``lead`` row (partition id,
    validity rank, compaction flag...), the whole record riding as
    packed u64 operands. Returns ``(sorted_lead, sorted_cols)``.

    The shared primitive behind the map-side bucket, the wide
    re-densification and the rank-keyed filters once packing is on: any
    "order rows by one computed key" pass becomes lead + ceil(W/2)
    operands instead of lead + W.
    """
    cols2 = jnp.concatenate([lead[None].astype(jnp.uint32), cols])
    out = packed_lexsort_cols(cols2, 1, stable=stable)
    return out[0], out[1:]


def sort_by_lead_cols(cols: jax.Array, lead: jax.Array, mode: str,
                      stable: bool = True) -> jax.Array:
    """Order full records ``[W, N]`` by a single u32 ``lead`` row
    (validity flag, partition rank, compaction key...), with the record
    movement strategy chosen by ``mode`` (the
    ``ShuffleExchange.sort_mode`` value): ``"pack"`` rides u64-packed,
    ``"wide"`` sorts ``(lead, index)`` and places by one gather,
    ``"plain"`` rides every word. THE one implementation of lead-keyed
    compaction — the join filler strips, re-densification and the
    skew-split range filter all call here, so a strategy fix applies
    everywhere at once.
    """
    lead = lead.astype(jnp.uint32)
    if mode == "pack":
        return packed_partition_cols(cols, lead, stable=stable)[1]
    if mode == "wide":
        from sparkrdma_tpu.kernels.wide_sort import apply_perm

        idx = lax.iota(jnp.int32, cols.shape[1])
        srt = lax.sort((lead, idx), num_keys=1, is_stable=stable)
        return apply_perm(cols.T, srt[-1]).T
    out = lax.sort((lead,) + tuple(cols[i] for i in range(cols.shape[0])),
                   num_keys=1, is_stable=stable)
    return jnp.stack(out[1:])


def merge_sorted_runs(
    runs: jax.Array, run_counts: jax.Array, key_words: int
) -> Tuple[jax.Array, jax.Array]:
    """Merge ``S`` key-sorted runs into one sorted stream.

    ``runs: uint32[S, C, W]`` (each run sorted on its valid prefix),
    ``run_counts: int32[S]``. Returns ``(merged: [S*C, W], total: int32)``
    with padding at the tail. XLA has no efficient k-way merge primitive, so
    this flattens and re-sorts — O(n log n) but fully parallel on the VPU.
    The Pallas true-merge exists (``kernels/merge_sort.py``) but measured
    slower than ``lax.sort`` on v5e — see its MEASURED STATUS note.
    """
    s, c, w = runs.shape
    flat = runs.reshape(s * c, w)
    valid = (jnp.arange(c)[None, :] < run_counts[:, None]).reshape(s * c)
    merged = lexsort_records(flat, key_words, valid)
    total = jnp.sum(run_counts).astype(jnp.int32)
    merged = merged * (jnp.arange(s * c) < total)[:, None].astype(merged.dtype)
    return merged, total


__all__ = ["compact", "lexsort_records", "lexsort_cols",
           "packed_lexsort_cols", "packed_partition_cols",
           "sort_by_lead_cols", "merge_sorted_runs"]
