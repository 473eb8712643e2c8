"""Destination-partition functions — Spark's Partitioner analogue.

The reference inherits partitioning entirely from Spark (HashPartitioner
for groupBy/join, RangePartitioner for sortByKey); the shuffle plugin only
moves bytes. Here partitioners are jit-safe functions ``records ->
int32[n]`` carried into the compiled exchange. Each carries a stable
``cache_key`` so :class:`~sparkrdma_tpu.exchange.protocol.ShuffleExchange`
can key its compiled-program cache on partitioner identity.

Record batches are COLUMNAR on device: ``uint32[W, N]`` with the key in
the leading ``key_words`` rows, most-significant word first (see
``MeshRuntime.shard_records`` for why). ``records[w]`` is word ``w`` of
every record — a contiguous full-lane vector.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _tag(fn: Callable, key) -> Callable:
    fn.cache_key = key
    return fn


#: Knuth's multiplicative constant; odd, so multiplying by it is a
#: bijection of u32 and :data:`_HASH_K_INV` undoes it
_HASH_K = 2654435761
_HASH_K_INV = pow(_HASH_K, -1, 1 << 32)


class HashOrder(NamedTuple):
    """A hash partitioner's inverse, for a bucket sort that keys on the
    record itself (:func:`~sparkrdma_tpu.kernels.bucketing.
    bucket_records_hash_keyed`).

    ``order_key(records) -> u32[N]`` is a bijection of the hash ``h``
    that sorts by partition first. Given the leading ``key_words - 1``
    words, ``h`` is a bijection of the last key word, so the order key
    can stand in for that word through a sort.
    ``invert(order, lead_words) -> (pid s32[N], last key word u32[N])``
    rebuilds both from the sorted order key and the sorted leading key
    words (a tuple of ``key_words - 1`` rows).
    """

    key_words: int
    order_key: Callable
    invert: Callable


def _hash_prefix(lead_words) -> jax.Array:
    """The multiplicative hash over all key words but the last."""
    h = jnp.uint32(0)
    for w in lead_words:
        h = (h ^ w) * jnp.uint32(_HASH_K)
    return h


def _pid_order(num_parts: int) -> Tuple[Callable, Callable]:
    """``(to_order, from_order)``: a u32 bijection ``h -> f`` ascending in
    ``r = h mod P``, and its inverse ``f -> (r, h)``.

    Partition ``r`` holds ``q + [r < m]`` hashes (``q, m = divmod(2**32,
    P)``), so ``f = r*q + min(r, m) + h // P`` packs the partitions in
    order with no gap. For ``P = 2**b`` that is a rotate right by ``b``.
    """
    if num_parts & (num_parts - 1) == 0:
        b = num_parts.bit_length() - 1
        lo, hi = jnp.uint32(b), jnp.uint32(32 - b)

        def to_order(h):
            return (h >> lo) | (h << hi)

        def from_order(f):
            return (f >> hi).astype(jnp.int32), (f << lo) | (f >> hi)

        return to_order, from_order
    q, m = divmod(1 << 32, num_parts)
    p_, q_, m_ = jnp.uint32(num_parts), jnp.uint32(q), jnp.uint32(m)
    edge = jnp.uint32(m * (q + 1))     # first order key of partition m

    def to_order(h):
        r = h % p_
        return r * q_ + jnp.minimum(r, m_) + h // p_

    def from_order(f):
        low = f < edge
        g = jnp.where(low, f, f - edge)
        span = jnp.where(low, q_ + jnp.uint32(1), q_)
        r = jnp.where(low, jnp.uint32(0), m_) + g // span
        return r.astype(jnp.int32), (g % span) * p_ + r

    return to_order, from_order


def hash_partitioner(num_parts: int, key_words: int = 2) -> Callable:
    """Multiplicative hash of the key words mod ``num_parts``.

    Spark's HashPartitioner is ``key.hashCode % numPartitions``; a plain
    modulo on the raw key would correlate with range partitioning for
    sequential keys, so mix the words first (Knuth multiplicative constant,
    standard public-domain technique). Every step of the mix inverts
    once the earlier words are known, so the function carries its
    inverse as ``hash_order`` (:class:`HashOrder`).
    """

    def mix(records):
        h = _hash_prefix(records[i] for i in range(key_words - 1))
        h = (h ^ records[key_words - 1]) * jnp.uint32(_HASH_K)
        return h ^ (h >> 16)

    def part(records: jax.Array) -> jax.Array:
        return (mix(records) % jnp.uint32(num_parts)).astype(jnp.int32)

    to_order, from_order = _pid_order(num_parts)

    def invert(order, lead_words):
        pid, h = from_order(order)
        h = h ^ (h >> 16)                  # x ^ (x >> 16) is an involution
        last = (h * jnp.uint32(_HASH_K_INV)) ^ _hash_prefix(lead_words)
        return pid, last

    part.hash_order = HashOrder(key_words, lambda r: to_order(mix(r)),
                                invert)
    return _tag(part, ("hash", num_parts, key_words))


def modulo_partitioner(num_parts: int, key_word: int = 0) -> Callable:
    """``key % num_parts`` on one key word — deterministic and easy to
    reason about in tests (the reference's tests-by-workload equivalent)."""

    def part(records: jax.Array) -> jax.Array:
        return (records[key_word] % jnp.uint32(num_parts)).astype(jnp.int32)

    return _tag(part, ("mod", num_parts, key_word))


def range_partitioner(splitters: np.ndarray, key_words: int = 2) -> Callable:
    """Range partitioner over lexicographic key order — sortByKey's.

    ``splitters: uint32[num_parts-1, key_words]`` are ascending upper
    boundaries (exclusive): partition p gets keys in
    ``[splitters[p-1], splitters[p])``. Built from a sample of the data by
    :func:`sparkrdma_tpu.meta.sampling.compute_splitters`, mirroring
    Spark's RangePartitioner reservoir sampling.

    Comparison is vectorized: a record belongs to partition
    ``sum(key >= splitter_i)`` — one [N, num_parts-1] comparison matrix,
    VPU-friendly, no data-dependent control flow.
    """
    spl = jnp.asarray(np.asarray(splitters, dtype=np.uint32))
    if spl.ndim != 2 or spl.shape[1] < key_words:
        raise ValueError("splitters must be [num_parts-1, >=key_words] uint32")
    num_parts = int(spl.shape[0]) + 1

    def part(records: jax.Array) -> jax.Array:
        n = records.shape[1]
        # lexicographic records[:, i] >= spl[j]: strictly greater at the
        # first differing word, or equal throughout
        gt = jnp.zeros((n, num_parts - 1), dtype=bool)
        eq = jnp.ones((n, num_parts - 1), dtype=bool)
        for w in range(key_words):
            rw = records[w][:, None]
            sw = spl[None, :, w]
            gt = gt | (eq & (rw > sw))
            eq = eq & (rw == sw)
        return jnp.sum(gt | eq, axis=1).astype(jnp.int32)

    key = ("range", num_parts, key_words,
           hash(np.asarray(splitters, dtype=np.uint32).tobytes()))
    return _tag(part, key)


__all__ = ["HashOrder", "hash_partitioner", "modulo_partitioner",
           "range_partitioner"]
