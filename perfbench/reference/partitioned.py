"""Plain reference of a hash ``repartition`` job: every record lands, once,
in the partition its key's hash decides, and partition ``p`` lives on
chip ``p % chips``.

The hash is the configuration's: a multiplicative mix of the key words
(Knuth's constant 2654435761, uint32 wraparound), then ``h ^= h >> 16``,
mod ``num_parts``. Within a partition no order is promised, so the
records are compared as multisets: both sides are put in a canonical
order (partition, then every word) and compared position by position.

Written in plain ``jax.numpy``/``lax`` and imports nothing of
``sparkrdma_tpu``. It runs on one device, ``dev``.

Numbers compared, each with limit 0:

- ``count_gap``: |records in the output - records in the input|;
- ``misrouted_records``: output records on a chip that does not own
  their partition, or out of the partition-grouped order within a
  chip's output;
- ``unmatched_records``: canonical positions at which the output's
  record differs from the input's.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LIMITS = {"count_gap": 0, "misrouted_records": 0, "unmatched_records": 0}


def partition_of(keys, num_parts: int):
    h = jnp.zeros(keys[0].shape, jnp.uint32)
    for k in keys:
        h = (h ^ k) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(num_parts)).astype(jnp.int32)


@partial(jax.jit, static_argnums=(1, 2))
def _chip_and_pos(totals, cap, n):
    ends = jnp.cumsum(totals)
    rank = jnp.arange(n, dtype=jnp.int32)
    chip = jnp.searchsorted(ends, rank, side="right").astype(jnp.int32)
    return chip, rank - (ends - totals)[chip] + chip * cap


@partial(jax.jit, static_argnums=(2, 3, 4))
def _gather(out, pos, w, kw, parts):
    """The output's valid records in rank order, and their partitions."""
    cols = tuple(jnp.take(out[i], pos) for i in range(w))
    return cols, partition_of(cols[:kw], parts)


@partial(jax.jit, static_argnums=(2,))
def _misrouted(pid, chip, chips):
    q = pid // chips
    wrong_chip = (pid % chips) != chip
    backwards = (q[1:] < q[:-1]) & (chip[1:] == chip[:-1])
    return (jnp.sum(wrong_chip, dtype=jnp.int32)
            + jnp.sum(backwards, dtype=jnp.int32))


@partial(jax.jit, static_argnums=(1, 2))
def _canonical(cols, kw, parts):
    pid = partition_of(cols[:kw], parts)
    return lax.sort((pid,) + tuple(cols), num_keys=1 + len(cols))


@jax.jit
def _unmatched(a, b):
    diff = jnp.zeros(a[0].shape, bool)
    for x, y in zip(a, b):
        diff = diff | (x != y)
    return jnp.sum(diff, dtype=jnp.int32)


class Reference:
    def __init__(self, config: dict, dev, chips: int):
        self.kw = int(config["key_words"])
        self.parts = int(config.get("num_parts")
                         or config["parts_per_chip"] * chips)
        self.dev = dev
        self._canon = {}

    def _local(self, x):
        return jax.device_put(x, self.dev)

    def canonical_input(self, x, input_id):
        if input_id not in self._canon:
            xs = self._local(x)
            self._canon[input_id] = _canonical(
                tuple(xs[i] for i in range(xs.shape[0])), self.kw,
                self.parts)
        return self._canon[input_id]

    def numbers(self, x, input_id, out, totals) -> dict:
        n, w = x.shape[1], x.shape[0]
        t = np.asarray(jax.device_get(totals)).astype(np.int64).reshape(-1)
        chips = t.shape[0]
        cap = out.shape[1] // chips
        gap = abs(int(t.sum()) - n)
        if gap or (t > cap).any():
            return {"count_gap": gap, "misrouted_records": n,
                    "unmatched_records": n}
        chip, pos = _chip_and_pos(self._local(np.int32(t)), cap, n)
        cols, pid = _gather(self._local(out), pos, w, self.kw, self.parts)
        misrouted = int(_misrouted(pid, chip, chips))
        del pid, chip, pos
        got = _canonical(cols, self.kw, self.parts)
        del cols
        unmatched = int(_unmatched(got, self.canonical_input(x, input_id)))
        return {"count_gap": 0, "misrouted_records": misrouted,
                "unmatched_records": unmatched}

    def control_output(self, x, input_id, sharding):
        """The control: this reference with one guarantee broken — a
        one-round exchange whose slots hold the mean partition size
        drops each partition's records beyond it. One chip only."""
        if sharding.mesh.size != 1:
            raise NotImplementedError("partitioned control: one chip")
        xs = self._local(x)
        return _truncated(xs, self.kw, self.parts)


@partial(jax.jit, static_argnums=(1, 2))
def _truncated(xs, kw, parts):
    n = xs.shape[1]
    pid = partition_of(tuple(xs[i] for i in range(kw)), parts)
    idx = lax.iota(jnp.int32, n)
    spid, order = lax.sort((pid, idx), num_keys=2)
    first = jnp.searchsorted(spid, spid, side="left")
    keep = (idx - first) < n // parts      # rank within the partition
    drop, order = lax.sort(((~keep).astype(jnp.int32), order), num_keys=1,
                           is_stable=True)
    out = jnp.take(xs, order, axis=1) * (drop == 0)[None, :].astype(
        xs.dtype)
    return out, jnp.sum(keep, dtype=jnp.int32)[None]
