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
- :func:`sort_by_key_cols` and :func:`sort_by_lead_cols` are the only
  places that tell the record-movement strategies (plain, wide, pack:
  :class:`SortStrategy`) apart; every full-record sort goes through
  one of them.

Keys sort lexicographically over their uint32 words, most-significant word
first — matching TeraSort's byte-lexicographic ordering.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import enable_x64, lax

from sparkrdma_tpu.kernels.wide_sort import apply_perm, sort_wide_cols
from sparkrdma_tpu.utils.profiling import device_phase


class SortStrategy(NamedTuple):
    """How a full-record sort moves the record words through the
    comparator network (:func:`sort_by_key_cols` and
    :func:`sort_by_lead_cols` carry it out;
    :meth:`~sparkrdma_tpu.config.ShuffleConf.sort_strategy` chooses it):

    - ``"plain"``: every word rides one variadic ``lax.sort``;
    - ``"wide"``: the sort keys, ``ride_words`` more words and a row
      index are sorted, then one gather places the rest;
    - ``"pack"``: pairs of u32 words ride as u64 operands.
    """

    kind: str = "plain"
    ride_words: int = 0

    def sorts_stably(self, stable: bool) -> bool:
        """Is a sort asked for ``stable`` stable under this strategy?
        Every wide form is: its index operand breaks ties."""
        return stable or self.kind == "wide"


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


@device_phase("sr_sort_keys")
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


@device_phase("sr_sort_keys")
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


def sort_by_key_cols(cols: jax.Array, key_words: int,
                     valid: jax.Array | None, strategy: SortStrategy,
                     stable: bool) -> jax.Array:
    """Sort full records ``cols: uint32[W, N]`` by their leading
    ``key_words`` rows, padding (``valid == False``) to the tail — the
    key-ordered sort of the fused tail, the combine and the group, with
    the record movement ``strategy`` chooses
    (:meth:`~sparkrdma_tpu.config.ShuffleConf.sort_strategy`).

    ``strategy.ride_words`` counts PAYLOAD words, past the keys, that
    ride the wide sort as values; the rest are placed by one gather.
    Plain and pack honour ``stable``; the wide form is stable always,
    so no output depends on the ride. Adds no phase: the key sort is
    ``sr_sort_keys`` and a wide placement ``sr_sort_gather``.
    """
    if strategy.kind == "pack":
        return packed_lexsort_cols(cols, key_words, valid, stable=stable)
    if strategy.kind == "wide":
        return sort_wide_cols(cols, key_words, valid,
                              ride_words=strategy.ride_words)
    return lexsort_cols(cols, key_words, valid, stable=stable)


def sort_by_lead_cols(cols: jax.Array, lead: jax.Array,
                      strategy: SortStrategy, stable: bool = True
                      ) -> Tuple[jax.Array, jax.Array]:
    """Order full records ``cols: [W, N]`` by one ``lead`` row
    (partition id, validity flag, compaction rank...); return
    ``(sorted_lead, sorted_cols)``. THE one lead-keyed sort: the
    map-side bucket, the combine compaction, the join's key sort and
    every filter and densify compaction call here.

    The lead sorts in its own dtype (partition ids stay s32); packing
    sorts it as u32, the width its operands share, and returns it in
    its own dtype. ``strategy.ride_words`` counts RECORD words, from
    word 0, that ride the wide sort beside the lead and a row index;
    the rest are placed by one gather. Plain and pack honour
    ``stable``; the wide form is stable always
    (:meth:`~sparkrdma_tpu.config.SortStrategy.sorts_stably`). Adds no
    phase: the sort is the caller's (bucketing, a compaction).
    """
    w, n = cols.shape
    is_stable = strategy.sorts_stably(stable)
    if strategy.kind == "pack":
        both = jnp.concatenate([lead[None].astype(jnp.uint32), cols])
        # unwrapped: outside ``sr_sort_keys``, in the caller's phase
        out = packed_lexsort_cols.__wrapped__(both, 1, stable=is_stable)
        return out[0].astype(lead.dtype), out[1:]
    if strategy.kind == "wide":
        ride = min(strategy.ride_words, w)
        out = lax.sort((lead,) + tuple(cols[i] for i in range(ride))
                       + (lax.iota(jnp.int32, n),),
                       num_keys=1, is_stable=is_stable)
        ridden = jnp.stack(out[1:-1]) if ride else cols[:0]
        placed = apply_perm(cols[ride:].T, out[-1]).T
        return out[0], jnp.concatenate([ridden, placed], axis=0)
    out = lax.sort((lead,) + tuple(cols[i] for i in range(w)),
                   num_keys=1, is_stable=is_stable)
    return out[0], jnp.stack(out[1:])


def merge_sorted_runs(
    runs: jax.Array, run_counts: jax.Array, key_words: int
) -> Tuple[jax.Array, jax.Array]:
    """Merge ``S`` key-sorted runs into one sorted stream.

    ``runs: uint32[S, C, W]`` (each run sorted on its valid prefix),
    ``run_counts: int32[S]``. Returns ``(merged: [S*C, W], total: int32)``
    with padding at the tail. XLA has no efficient k-way merge primitive, so
    this flattens and re-sorts — O(n log n) but fully parallel on the VPU.
    """
    s, c, w = runs.shape
    flat = runs.reshape(s * c, w)
    valid = (jnp.arange(c)[None, :] < run_counts[:, None]).reshape(s * c)
    merged = lexsort_records(flat, key_words, valid)
    total = jnp.sum(run_counts).astype(jnp.int32)
    merged = merged * (jnp.arange(s * c) < total)[:, None].astype(merged.dtype)
    return merged, total


__all__ = ["SortStrategy", "compact", "lexsort_records", "lexsort_cols",
           "packed_lexsort_cols", "sort_by_key_cols", "sort_by_lead_cols",
           "merge_sorted_runs"]
