import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.kernels import compact, lexsort_records, merge_sorted_runs
from sparkrdma_tpu.kernels.sort import (SortStrategy, sort_by_key_cols,
                                        sort_by_lead_cols)


def make_records(rng, n, key_words=2, val_words=2):
    return jnp.asarray(
        rng.integers(0, 2**32, size=(n, key_words + val_words), dtype=np.uint32)
    )


def np_lexsort_rows(arr, key_words):
    # numpy reference: lexicographic over leading key words, msw first
    keys = tuple(arr[:, w] for w in range(key_words - 1, -1, -1))
    return arr[np.lexsort(keys)]


def test_compact_packs_valid_prefix(rng):
    recs = make_records(rng, 16)
    valid = jnp.asarray(rng.random(16) < 0.5)
    packed, count = compact(recs, valid, 16)
    assert int(count) == int(valid.sum())
    np.testing.assert_array_equal(
        np.asarray(packed[: int(count)]), np.asarray(recs)[np.asarray(valid)]
    )
    assert not np.any(np.asarray(packed[int(count):]))


def test_compact_overflow_reports_true_count(rng):
    recs = make_records(rng, 8)
    valid = jnp.ones(8, bool)
    packed, count = compact(recs, valid, 4)
    assert int(count) == 8  # caller must detect count > capacity
    assert packed.shape == (4, 4)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(recs)[:4])


def test_compact_capacity_larger_than_input(rng):
    recs = make_records(rng, 4)
    valid = jnp.asarray([True, False, True, False])
    packed, count = compact(recs, valid, 10)
    assert packed.shape == (10, 4)
    assert int(count) == 2
    assert not np.any(np.asarray(packed[2:]))


def test_lexsort_matches_numpy(rng):
    recs = make_records(rng, 100)
    out = np.asarray(lexsort_records(recs, 2))
    np.testing.assert_array_equal(out, np_lexsort_rows(np.asarray(recs), 2))


def test_lexsort_single_word_keys(rng):
    recs = make_records(rng, 50, key_words=1, val_words=1)
    out = np.asarray(lexsort_records(recs, 1))
    ref = np.asarray(recs)[np.argsort(np.asarray(recs)[:, 0], kind="stable")]
    np.testing.assert_array_equal(out, ref)


def test_lexsort_moves_invalid_to_tail(rng):
    recs = make_records(rng, 20)
    valid = jnp.asarray(rng.random(20) < 0.7)
    out = np.asarray(lexsort_records(recs, 2, valid))
    nvalid = int(valid.sum())
    ref_valid = np_lexsort_rows(np.asarray(recs)[np.asarray(valid)], 2)
    np.testing.assert_array_equal(out[:nvalid], ref_valid)


def test_merge_sorted_runs(rng):
    s, c = 4, 8
    runs, counts = [], []
    all_valid = []
    for _ in range(s):
        n = int(rng.integers(0, c + 1))
        rec = np.asarray(make_records(rng, c)).copy()
        rec[:n] = np_lexsort_rows(rec[:n], 2)
        rec[n:] = 0
        runs.append(rec)
        counts.append(n)
        all_valid.append(rec[:n])
    merged, total = merge_sorted_runs(
        jnp.asarray(np.stack(runs)), jnp.asarray(np.array(counts, np.int32)), 2
    )
    assert int(total) == sum(counts)
    ref = np_lexsort_rows(np.concatenate(all_valid), 2) if sum(counts) else None
    if ref is not None:
        np.testing.assert_array_equal(np.asarray(merged[: int(total)]), ref)
    assert not np.any(np.asarray(merged[int(total):]))


# --- u64 operand packing (round 5) -----------------------------------

def _canon_cols(a):
    import numpy as np
    return a[:, np.lexsort(tuple(a[c] for c in range(a.shape[0] - 1, -1,
                                                     -1)))]


@pytest.mark.parametrize("w,kw", [(25, 2), (13, 2), (26, 1), (9, 3),
                                  (4, 2), (5, 4)])
def test_packed_lexsort_matches_unpacked(rng, w, kw):
    """packed_lexsort_cols == lexsort_cols for every key/payload parity
    (even/odd key words, even/odd payload words). Multiset equality for
    full records; exact key-column order equality."""
    import jax.numpy as jnp
    from sparkrdma_tpu.kernels.sort import lexsort_cols, packed_lexsort_cols

    n = 1 << 11
    cols = rng.integers(0, 2**32, size=(w, n), dtype=np.uint32)
    cols[:kw, : n // 4] = cols[:kw, n // 4: n // 2]   # duplicate keys
    x = jnp.asarray(cols)
    got = np.asarray(packed_lexsort_cols(x, kw))
    ref = np.asarray(lexsort_cols(x, kw, stable=False))
    np.testing.assert_array_equal(got[:kw], ref[:kw])
    np.testing.assert_array_equal(_canon_cols(got), _canon_cols(ref))


def test_packed_lexsort_valid_padding_and_stability(rng):
    import jax.numpy as jnp
    from sparkrdma_tpu.kernels.sort import lexsort_cols, packed_lexsort_cols

    n = 1 << 10
    cols = np.zeros((7, n), dtype=np.uint32)
    cols[0] = rng.integers(0, 4, size=n)
    cols[1] = 0
    cols[2] = np.arange(n)                       # arrival marker
    valid = rng.random(n) < 0.8
    x = jnp.asarray(cols)
    v = jnp.asarray(valid)
    got = np.asarray(packed_lexsort_cols(x, 2, v, stable=True))
    ref = np.asarray(lexsort_cols(x, 2, v, stable=True))
    np.testing.assert_array_equal(got, ref)


def test_packed_lexsort_leaves_x64_flag_off():
    import jax
    import jax.numpy as jnp
    from sparkrdma_tpu.kernels.sort import packed_lexsort_cols

    x = jnp.zeros((4, 128), jnp.uint32)
    jax.jit(lambda c: packed_lexsort_cols(c, 2))(x)
    assert not jax.config.jax_enable_x64


# --- the two entry points: one result whatever the strategy ----------

STRATEGIES = {
    "plain": SortStrategy(),
    "wide_ride0": SortStrategy("wide"),
    "wide_ride3": SortStrategy("wide", 3),
    "pack": SortStrategy("pack"),
}


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_sort_by_key_cols_strategies_agree(rng, name, masked):
    """A stable key sort equals numpy's stable lexsort under every
    strategy: invalid rows to the tail, equal keys in arrival order."""
    n, w, kw = 512, 9, 2
    cols = rng.integers(0, 2**32, size=(w, n), dtype=np.uint32)
    cols[0] = rng.integers(0, 3, size=n)          # many equal keys
    cols[1] = rng.integers(0, 8, size=n)
    valid = rng.random(n) < 0.8 if masked else np.ones(n, bool)
    got = np.asarray(sort_by_key_cols(
        jnp.asarray(cols), kw, jnp.asarray(valid) if masked else None,
        STRATEGIES[name], stable=True))
    order = np.lexsort((cols[1], cols[0], ~valid))
    np.testing.assert_array_equal(got, cols[:, order])


@pytest.mark.parametrize("name", ["plain", "wide_ride3", "pack"])
def test_sort_by_lead_cols_strategies_agree(rng, name):
    """The lead comes back sorted in its own dtype, and the rows follow
    it in a stable order, under every strategy."""
    n, w = 512, 5
    cols = rng.integers(0, 2**32, size=(w, n), dtype=np.uint32)
    lead = rng.integers(0, 6, size=n).astype(np.int32)
    got_lead, got = sort_by_lead_cols(jnp.asarray(cols), jnp.asarray(lead),
                                      STRATEGIES[name])
    order = np.argsort(lead, kind="stable")
    assert got_lead.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_lead), lead[order])
    np.testing.assert_array_equal(np.asarray(got), cols[:, order])


def _np_hash(cols, key_words):
    """numpy reference of ``hash_partitioner``'s mix, in u64 arithmetic."""
    h = np.zeros(cols.shape[1], np.uint64)
    for w in range(key_words):
        h = ((h ^ cols[w].astype(np.uint64)) * 2654435761) & 0xFFFFFFFF
    return h ^ (h >> 16)


@pytest.mark.parametrize("key_words", [1, 2, 3])
@pytest.mark.parametrize("p", [2, 200, 256, 4096])
def test_hash_order_key_inverts_and_sorts_by_partition(rng, p, key_words):
    """The hash partitioner's order key is ``r*q + min(r, m) + h // P``
    with ``r = h mod P`` (a rotate when P is a power of two), so sorting
    by it groups records by ascending partition; the inverse rebuilds
    the partition and the last key word bit for bit, edge words too."""
    from sparkrdma_tpu.exchange.partitioners import hash_partitioner

    n = 4096
    cols = rng.integers(0, 2**32, size=(key_words, n), dtype=np.uint32)
    edge = np.array([0, 2**32 - 1, 1, 2**31], np.uint32)
    grid = np.array(list(itertools.product(edge, repeat=key_words)),
                    np.uint32).T                 # every edge-word record
    cols[:, :grid.shape[1]] = grid
    part = hash_partitioner(p, key_words)
    order = part.hash_order
    assert order.key_words == key_words
    f = np.asarray(order.order_key(jnp.asarray(cols)))
    h = _np_hash(cols, key_words)
    q, m = divmod(1 << 32, p)
    r = h % p
    np.testing.assert_array_equal(
        f, (r * q + np.minimum(r, m) + h // p).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(part(jnp.asarray(cols))),
                                  r.astype(np.int32))
    s = np.argsort(f, kind="stable")
    lead = tuple(jnp.asarray(cols[i, s]) for i in range(key_words - 1))
    pid, last = order.invert(jnp.asarray(f[s]), lead)
    assert pid.dtype == jnp.int32 and last.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(pid), r[s].astype(np.int32))
    assert np.all(np.diff(np.asarray(pid)) >= 0)
    np.testing.assert_array_equal(np.asarray(last), cols[-1, s])
