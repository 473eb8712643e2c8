"""Plain reference of a ``sortByKey`` job (TeraSort): the global sort of
the input by key.

What the job guarantees: the chips' valid output prefixes, read in chip
order, are the input's records in ascending key order, every record
once. That covers the partitioning too: a range partition puts each
record on the chip whose key range holds it, and only then does the
concatenation come out in global order. Keys made by the ``unique``
scheme never repeat, so the order is fully determined and the
comparison is exact.

Written in plain ``jax.numpy``/``lax`` and imports nothing of
``sparkrdma_tpu``. It runs on one device, ``dev``, a word column at a
time (a sort of the key words and the index, then one gather per
word), so that it fits beside what the run still holds.

Numbers compared, each with limit 0:

- ``count_gap``: |records in the output - records in the input|;
- ``misplaced_records``: output positions whose record differs in any
  word from the reference's record at the same global rank.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LIMITS = {"count_gap": 0, "misplaced_records": 0}


@jax.jit
def _row(x, w):
    return lax.dynamic_index_in_dim(x, w, 0, keepdims=False)


@partial(jax.jit, static_argnums=1)
def _order(keys, num_keys):
    """Permutation that sorts by the first ``num_keys`` key words,
    equal keys in input order."""
    n = keys[0].shape[0]
    ops = tuple(keys[:num_keys]) + (lax.iota(jnp.int32, n),)
    return lax.sort(ops, num_keys=len(ops))[-1]


@partial(jax.jit, static_argnums=(1, 2))
def _positions(totals, cap, n):
    """Global rank -> column of the program's output that holds it, for
    outputs whose ``totals`` add up to ``n``."""
    ends = jnp.cumsum(totals)
    rank = jnp.arange(n, dtype=jnp.int32)
    chip = jnp.searchsorted(ends, rank, side="right")
    return rank - (ends - totals)[chip] + chip * cap


@jax.jit
def _mismatch(acc, col_in, perm, col_out, pos):
    return acc | (jnp.take(col_in, perm) != jnp.take(col_out, pos))


class Reference:
    def __init__(self, config: dict, dev, chips: int):
        self.kw = int(config["key_words"])
        self.dev = dev
        self._perm = {}

    def _col(self, x, w):
        return jax.device_put(_row(x, w), self.dev)

    def order(self, x, input_id, num_keys=None):
        k = self.kw if num_keys is None else num_keys
        key = (input_id, k)
        if key not in self._perm:
            keys = tuple(self._col(x, w) for w in range(self.kw))
            self._perm[key] = _order(keys, k)
        return self._perm[key]

    def numbers(self, x, input_id, out, totals) -> dict:
        n = x.shape[1]
        t = np.asarray(jax.device_get(totals)).astype(np.int64).reshape(-1)
        chips = t.shape[0]
        cap = out.shape[1] // chips
        gap = abs(int(t.sum()) - n)
        if gap or (t > cap).any():
            return {"count_gap": gap, "misplaced_records": n}
        perm = self.order(x, input_id)
        pos = _positions(jax.device_put(np.int32(t), self.dev), cap, n)
        acc = jax.device_put(jnp.zeros((n,), bool), self.dev)
        for w in range(x.shape[0]):
            acc = _mismatch(acc, self._col(x, w), perm, self._col(out, w),
                            pos)
        return {"count_gap": 0,
                "misplaced_records": int(jnp.sum(acc, dtype=jnp.int32))}

    def control_output(self, x, input_id, sharding):
        """The control: this reference with one guarantee broken — it
        orders by the first key word alone (a 32-bit sort where the key
        is 64 bits). Laid out as the program's output: ``[W, N]`` on
        ``sharding``, ``totals`` the records per chip."""
        perm = self.order(x, input_id, num_keys=1)
        cols = [jax.device_put(jnp.take(self._col(x, w), perm),
                               sharding) for w in range(x.shape[0])]
        out = jnp.stack(cols)
        chips = sharding.mesh.size
        totals = jnp.full((chips,), x.shape[1] // chips, jnp.int32)
        return out, totals
