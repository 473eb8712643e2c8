"""Inputs of a cell, made on the device from ``--seed`` in one jitted call.

One general generator: what it makes is read from the configuration
(record shape, records per chip, key scheme) and the traffic mix (how
many inputs, whether they share their key columns). Inputs are columnar
``uint32[W, chips * records_per_chip]`` sharded over the record axis,
the layout ``ShuffleManager`` takes; chip ``d`` holds global records
``[d * n, (d + 1) * n)``.

Key schemes:

- ``unique``: the first two key words (64 bits) of global record ``i``
  are a 4-round Feistel permutation of ``i`` under round keys drawn from
  the seed. A Feistel network is a bijection, so no two records share
  them, as TeraGen's random 10-byte keys in practice never repeat; any
  further key word is a seeded mix of ``i`` (for TeraGen's layout: the
  key's last 2 bytes in its high half, the value's first 2 in its low).
  The first 64 bits being unique, the order the sort must produce is
  fully determined, on any number of key words.
- ``random``: independent uniform words (duplicates possible).
"""

from __future__ import annotations

from typing import List

import numpy as np


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` uint32 words from any whole-number seed (any size, any sign)."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0)]
                                  ).generate_state(n, dtype=np.uint32)


def _mix32(x, k):
    """A 32-bit round function (xor key, then a murmur-style finaliser)."""
    import jax.numpy as jnp

    x = x ^ k
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def feistel_keys(idx, round_keys):
    """Bijective 64-bit keys ``(hi, lo)`` of uint32 indices ``idx``."""
    import jax.numpy as jnp

    left, right = jnp.zeros_like(idx), idx
    for r in range(round_keys.shape[0]):
        left, right = right, left ^ _mix32(right, round_keys[r])
    return left, right


def make_inputs(mesh, axis: str, config: dict, traffic: dict, seed: int,
                records_per_chip: int) -> List:
    """All of the cell's inputs, placed on ``mesh``; blocks until made."""
    import jax
    import jax.numpy as jnp

    fn = generator(mesh, axis, config, traffic, records_per_chip)
    made = fn(jnp.asarray(seed_words(seed, 8)))
    jax.block_until_ready(made)
    return list(made)


def generator(mesh, axis: str, config: dict, traffic: dict,
              records_per_chip: int):
    """The jitted call that makes every input from 8 seed words."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    chips = int(mesh.shape[axis])
    n = chips * records_per_chip
    if n >= 2 ** 32:
        raise ValueError("record indices must fit 32 bits")
    kw, vw = int(config["key_words"]), int(config["val_words"])
    scheme = config["key_scheme"]
    if scheme == "unique" and kw < 2:
        raise ValueError("the unique key scheme needs 2 key words or more")
    inputs = int(traffic["inputs"])
    share = bool(traffic["share_keys"])
    sharding = NamedSharding(mesh, P(None, axis))

    def gen(words):
        base = jax.random.wrap_key_data(words[4:6], impl="threefry2x32")
        if scheme == "unique":
            idx = jnp.arange(n, dtype=jnp.uint32)
            hi, lo = feistel_keys(idx, words[:4])
            rest = [_mix32(idx, words[6] ^ jnp.uint32(w))
                    for w in range(2, kw)]
            shared = jnp.stack([hi, lo] + rest)
        out = []
        for i in range(inputs):
            key = jax.random.fold_in(base, i)
            if scheme == "unique":
                keys = shared
            else:
                keys = jax.random.bits(
                    jax.random.fold_in(base, 0 if share else 1000 + i),
                    (kw, n), jnp.uint32)
            pay = jax.random.bits(key, (vw, n), jnp.uint32)
            out.append(jnp.concatenate([keys, pay]) if vw else keys)
        return tuple(out)

    return jax.jit(gen, out_shardings=(sharding,) * inputs)
