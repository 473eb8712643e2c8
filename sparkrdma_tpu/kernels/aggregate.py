"""Combine-by-key kernels — Spark's Aggregator stage, in HBM.

The reference's reduce path hands fetched blocks to Spark's optional
``Aggregator`` (map-side combine / reduce-side merge in
RdmaShuffleReader §read). TPU-native equivalent: after the exchange, sort
the received records by key and segment-reduce runs of equal keys — fixed
shapes, VPU-friendly, no hash tables, and (critically) NO SCATTER OPS.

Scatter-free design: on TPU, ``jax.ops.segment_sum`` and ``.at[].set``
lower to scatter, an operand-bound serial disaster this repo has measured
repeatedly (16M-element scatter ≈ 1.4s; the 147ms bincount scatter-add
was round 3's headline kill, kernels/bucketing.py §histogram_pids). The
replacement pipeline is three parallel-friendly primitives:

1. one stable variadic ``lax.sort`` groups equal keys into runs;
2. a SEGMENTED ASSOCIATIVE SCAN (``lax.associative_scan`` over
   ``(value, boundary_flag)`` pairs — the classic segmented-scan
   operator) leaves each run's full reduction in its LAST row:
   log2(N) elementwise passes, no data movement across lanes beyond
   XLA's own scan slicing;
3. one more stable sort keyed on "is last of run" compacts the unique
   keys (already in ascending key order) to the front.

Core is columnar (``uint32[W, N]`` batches, matching the exchange data
path); thin row-major wrappers remain for host-scale callers and tests.
Payload words can be interpreted as uint32 or float32 (bitcast);
reductions supported: sum (uint32 wraparound or float32), min, max.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkrdma_tpu.kernels.sort import (SortStrategy, sort_by_key_cols,
                                        sort_by_lead_cols)
from sparkrdma_tpu.utils.profiling import device_phase


def _segmented_scan(vals: jax.Array, first: jax.Array, op) -> jax.Array:
    """Inclusive left-to-right scan of ``op`` over ``vals: [P, N]`` with
    segment resets where ``first: bool[N]`` is True.

    The classic segmented-scan pair operator: combining summaries
    ``(va, fa) ⊕ (vb, fb) = (fb ? vb : op(va, vb), fa | fb)`` — if the
    right block contains a segment head, the left block's accumulation
    must not leak into it. Associative, so ``lax.associative_scan``
    parallelizes it in log2(N) elementwise passes.
    """
    flags = first[None, :]

    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, op(va, vb)), fa | fb

    out, _ = lax.associative_scan(combine, (vals, flags), axis=1)
    return out


@device_phase("sr_combine")
def combine_by_key_cols(
    cols: jax.Array,
    valid: jax.Array,
    key_words: int,
    op: str = "sum",
    float_payload: bool = False,
    strategy: SortStrategy = SortStrategy(),
) -> Tuple[jax.Array, jax.Array]:
    """Reduce payloads of equal keys; return ``(combined, num_unique)``.

    ``cols: uint32[W, N]`` with leading ``key_words`` key rows. Output
    keeps shape ``[W, N]``: the first ``num_unique`` columns are unique
    keys (sorted ascending) with reduced payloads; tail is zero padding.
    Both sorts move the record words as ``strategy`` says
    (kernels/sort.py), so wide payloads never meet the >13-operand
    comparator wall; same output contract under every strategy.
    """
    n = cols.shape[1]
    srt = sort_by_key_cols(cols, key_words, valid, strategy, stable=True)
    nvalid = jnp.sum(valid).astype(jnp.int32)
    in_valid = jnp.arange(n) < nvalid
    keys = srt[:key_words]                       # [kw, N]
    payload = srt[key_words:]                    # [W-kw, N]
    if float_payload:
        payload = jax.lax.bitcast_convert_type(payload, jnp.float32)

    eq = jnp.all(keys[:, 1:] == keys[:, :-1], axis=0)
    same = jnp.concatenate([jnp.zeros((1,), bool), eq]) & in_valid
    first_of_run = (~same) & in_valid
    num_unique = jnp.sum(first_of_run).astype(jnp.int32)

    if op == "sum":
        red = _segmented_scan(payload, first_of_run, jnp.add)
    elif op == "min":
        red = _segmented_scan(payload, first_of_run, jnp.minimum)
    elif op == "max":
        red = _segmented_scan(payload, first_of_run, jnp.maximum)
    else:
        raise ValueError(f"unsupported op {op!r}")
    if float_payload:
        red = jax.lax.bitcast_convert_type(red, jnp.uint32)

    # the LAST row of each run now holds the run's full reduction (and
    # its key words — all rows of a run share the key); compact those
    # rows to the front with one stable validity-lead sort, preserving
    # ascending key order
    next_same = jnp.concatenate([same[1:], jnp.zeros((1,), bool)])
    last_of_run = in_valid & ~next_same
    lead = (~last_of_run).astype(jnp.uint8)
    _, out = sort_by_lead_cols(jnp.concatenate([keys, red], axis=0), lead,
                               strategy)
    live = (jnp.arange(n) < num_unique)[None, :]
    out = out * live.astype(out.dtype)
    return out, num_unique


@device_phase("sr_combine")
def map_side_combine_cols(
    records: jax.Array,
    part_ids: jax.Array,
    num_parts: int,
    key_words: int,
    op: str = "sum",
    float_payload: bool = False,
    strategy: SortStrategy = SortStrategy(),
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-exchange reduction: collapse duplicate (partition, key) pairs.

    The map half of Spark's Aggregator (map-side combine), phrased for
    the exchange's bucketing contract: the destination partition id is
    prepended as an extra leading key word, so ONE
    :func:`combine_by_key_cols` pass both sorts the batch by
    ``(dest partition, key)`` AND segment-reduces equal keys — each
    (partition, key) pair then occupies one slot in the round layout.

    ``part_ids`` outside ``[0, num_parts)`` mark rows already dropped by
    a predicate pushdown; they are treated as invalid and never reach
    the output (filter and combine compose in the same pass).

    Returns ``(combined [W, N], new_pids int32[N], num_unique)``:
    ``combined``'s first ``num_unique`` columns are the surviving rows
    sorted ascending by (partition, key) with reduced payloads (zero
    tail); ``new_pids`` carries their partition ids with the sentinel
    ``num_parts`` on the tail, ascending — exactly the
    ``sorted_ids`` form :func:`~sparkrdma_tpu.kernels.bucketing
    .histogram_pids` consumes, so the caller needs no second bucketing
    sort.
    """
    w, n = records.shape
    part_ids = part_ids.astype(jnp.int32)
    cols = jnp.concatenate(
        [part_ids.astype(jnp.uint32)[None], records], axis=0)
    valid = (part_ids >= 0) & (part_ids < num_parts)
    combined, num_unique = combine_by_key_cols(
        cols, valid, 1 + key_words, op, float_payload, strategy)
    live = jnp.arange(n) < num_unique
    new_pids = jnp.where(live, combined[0].astype(jnp.int32),
                         jnp.int32(num_parts))
    return combined[1:], new_pids, num_unique


def combine_by_key(
    records: jax.Array,
    valid: jax.Array,
    key_words: int,
    op: str = "sum",
    float_payload: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Row-major wrapper: ``records uint32[N, W]`` -> ``([N, W], n)``."""
    out, n = combine_by_key_cols(records.T, valid, key_words, op,
                                 float_payload)
    return out.T, n


def count_by_key(records: jax.Array, valid: jax.Array,
                 key_words: int) -> Tuple[jax.Array, jax.Array]:
    """Per-unique-key record counts: ``(rows [N, key_words+1], n_unique)``."""
    n, w = records.shape
    ones = jnp.ones((n, 1), jnp.uint32)
    with_ones = jnp.concatenate([records[:, :key_words], ones], axis=1)
    return combine_by_key(with_ones, valid, key_words, op="sum")


__all__ = ["combine_by_key", "combine_by_key_cols",
           "map_side_combine_cols", "count_by_key"]
