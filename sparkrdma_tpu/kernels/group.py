"""Grouped-values kernels — Spark's groupByKey/cogroup, in HBM.

``rdd.groupByKey`` materializes, per key, the full list of values —
variable-length per key, which is XLA-hostile as a ragged structure but
natural as the classic CSR-style pair:

- a VALUES buffer: the records key-sorted, so each key's values are one
  contiguous run (the buffer already exists — it is the sorted exchange
  output, no second materialization);
- a GROUPS table: one row per unique key holding ``(key words, count,
  offset)`` with ``offset`` pointing at the run's start in the values
  buffer.

In the reference this shape never appears explicitly — stock Spark's
``ExternalSorter`` groups runs the same way before handing an iterator
per key to user code (SURVEY.md §1 L5 "user jobs"); the CSR pair is that
iterator's fixed-shape equivalent.

Everything is scatter-free (the repo-wide discipline — see
kernels/aggregate.py's module docstring for the measured scatter
numbers): run boundaries come from adjacent-equality, run START
positions are compacted by a single-operand sort (ascending positions
with an N sentinel for non-starts), counts are adjacent differences of
the compacted starts, and keys are gathered at start positions instead
of riding a second full-record sort. The one full-record sort moves
wide records as the conf's sort strategy says (kernels/sort.py
§sort_by_key_cols), so groupByKey never meets the 25-operand compile
wall.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkrdma_tpu.kernels.sort import SortStrategy, sort_by_key_cols


def group_runs_cols(
    cols: jax.Array,
    valid: jax.Array,
    key_words: int,
    strategy: SortStrategy = SortStrategy(),
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Key-sort ``cols: uint32[W, N]`` and emit its CSR group table.

    Returns ``(values, groups, n_groups, total)``:

    - ``values: [W, N]`` — records sorted by key, invalid rows zeroed at
      the tail (each key's values contiguous: THE values buffer);
    - ``groups: [key_words + 2, N]`` — per unique key ``(key words...,
      count, offset)``, compacted to the front in ascending key order,
      zero tail. ``offset`` indexes into ``values``;
    - ``n_groups``: unique-key count; ``total``: valid record count.

    Capacity contract: ``groups`` shares N, and unique keys <= valid
    records always, so there is NO overflow mode here — unlike the join,
    every group fits by construction.
    """
    n = cols.shape[1]
    values = sort_by_key_cols(cols, key_words, valid, strategy, stable=True)
    total = jnp.sum(valid).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    in_valid = pos < total
    keys = values[:key_words]
    eq = jnp.all(keys[:, 1:] == keys[:, :-1], axis=0)
    same = jnp.concatenate([jnp.zeros((1,), bool), eq]) & in_valid
    first_of_run = (~same) & in_valid
    n_groups = jnp.sum(first_of_run).astype(jnp.int32)

    # compact run-start positions with ONE single-operand sort: starts
    # ascend already, so sorting (start-or-N-sentinel) packs them to the
    # front in order; counts are then adjacent differences
    starts = jnp.sort(jnp.where(first_of_run, pos, jnp.int32(n)))
    ends = jnp.minimum(jnp.concatenate([starts[1:],
                                        jnp.full((1,), n, jnp.int32)]),
                       total)
    counts = jnp.maximum(ends - starts, 0)
    live = pos < n_groups
    safe = jnp.minimum(starts, n - 1)
    gkeys = jnp.take(keys, safe, axis=1)           # [kw, N]
    offsets = jnp.where(live, starts, 0)
    groups = jnp.concatenate(
        [gkeys, counts.astype(jnp.uint32)[None],
         offsets.astype(jnp.uint32)[None]], axis=0)
    groups = groups * live[None].astype(groups.dtype)
    # values buffer: zero the invalid tail so both outputs share the
    # padding convention
    values = values * in_valid[None].astype(values.dtype)
    return values, groups, n_groups, total


def cogroup_tables(
    groups_a: jax.Array, n_a: jax.Array,
    groups_b: jax.Array, n_b: jax.Array,
    key_words: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two per-device group tables over the UNION of their keys.

    Inputs are :func:`group_runs_cols` tables ``[key_words + 2, Na/Nb]``
    (unique keys ascending). Returns ``(cotable, n_union)`` where
    ``cotable: [key_words + 4, Na + Nb]`` rows are ``(key words...,
    count_a, offset_a, count_b, offset_b)`` for every key present on
    EITHER side (absent side: count 0), ascending, zero tail.

    Scatter-free union: concatenate both tables with a side tag, one
    stable sort by (validity, key words) brings equal keys adjacent
    (the A row first — tags ride arrival order), and since each side's
    keys are unique a run is 1-2 rows whose per-side fields are
    disjoint — the first row of each run absorbs its successor's fields
    by one shifted add, then a final validity sort compacts first-rows
    to the front. Spark's ``cogroup`` (the primitive under join/
    intersection/etc.) returns exactly this pair-of-iterables shape.
    """
    kw = key_words
    na, nb = groups_a.shape[1], groups_b.shape[1]
    n = na + nb
    pos = jnp.arange(n, dtype=jnp.int32)

    def fields(g, cnt_ix, live):
        # (ca, oa, cb, ob) rows for one side's table; the other side's
        # pair stays zero
        z = jnp.zeros((g.shape[1],), jnp.uint32)
        cnt, off = g[kw], g[kw + 1]
        cols = [z, z, z, z]
        cols[cnt_ix], cols[cnt_ix + 1] = cnt, off
        return jnp.stack(cols) * live[None].astype(jnp.uint32)

    live_a = jnp.arange(na) < n_a
    live_b = jnp.arange(nb) < n_b
    keys = jnp.concatenate([groups_a[:kw], groups_b[:kw]], axis=1)
    quad = jnp.concatenate([fields(groups_a, 0, live_a),
                            fields(groups_b, 2, live_b)], axis=1)
    valid = jnp.concatenate([live_a, live_b])

    lead = (~valid).astype(jnp.uint8)
    srt = lax.sort((lead,) + tuple(keys[i] for i in range(kw))
                   + tuple(quad[i] for i in range(4)),
                   num_keys=1 + kw, is_stable=True)
    skeys = jnp.stack(srt[1:1 + kw])
    squad = jnp.stack(srt[1 + kw:])
    total = jnp.sum(valid).astype(jnp.int32)
    in_valid = pos < total
    eq = jnp.all(skeys[:, 1:] == skeys[:, :-1], axis=0)
    same = jnp.concatenate([jnp.zeros((1,), bool), eq]) & in_valid
    first = (~same) & in_valid
    n_union = jnp.sum(first).astype(jnp.int32)
    # absorb the successor row's (disjoint) fields into the run head
    nxt = jnp.concatenate([squad[:, 1:], jnp.zeros((4, 1), jnp.uint32)],
                          axis=1)
    nxt_same = jnp.concatenate([same[1:], jnp.zeros((1,), bool)])
    merged = squad + nxt * nxt_same[None].astype(jnp.uint32)
    # compact run heads to the front (ascending key order preserved)
    lead2 = (~first).astype(jnp.uint8)
    srt2 = lax.sort((lead2,) + tuple(skeys[i] for i in range(kw))
                    + tuple(merged[i] for i in range(4)),
                    num_keys=1, is_stable=True)
    cotable = jnp.stack(srt2[1:])
    live = (pos < n_union)[None].astype(cotable.dtype)
    return cotable * live, n_union


__all__ = ["group_runs_cols", "cogroup_tables"]
