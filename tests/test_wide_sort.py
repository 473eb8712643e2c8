"""Wide-record sort (key+index sort + payload placement) vs the
monolithic lexsort and numpy references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.kernels.sort import SortStrategy, lexsort_cols
from sparkrdma_tpu.kernels.wide_sort import (apply_perm, sort_perm,
                                             sort_wide_cols)


def np_lexsort_rows(rows, kw):
    order = np.lexsort(tuple(rows[:, k] for k in range(kw - 1, -1, -1)))
    return rows[order]


@pytest.mark.parametrize("w", [4, 25])
def test_matches_monolithic_lexsort(rng, w):
    n = 2048
    cols = jnp.asarray(rng.integers(0, 2**32, size=(w, n), dtype=np.uint32))
    got = np.asarray(sort_wide_cols(cols, 2))
    ref = np.asarray(lexsort_cols(cols, 2))
    np.testing.assert_array_equal(got, ref)


def test_stability_equal_keys(rng):
    """Equal keys must keep arrival order (the index tiebreak)."""
    n = 512
    cols = np.zeros((5, n), dtype=np.uint32)
    cols[0] = rng.integers(0, 4, size=n)          # few distinct hi keys
    cols[1] = 0                                   # all-equal lo keys
    cols[2] = np.arange(n)                        # payload = arrival order
    got = np.asarray(sort_wide_cols(jnp.asarray(cols), 2))
    for k in np.unique(cols[0]):
        sel = got[2][got[0] == k]
        assert np.all(np.diff(sel.astype(np.int64)) > 0), \
            f"arrival order broken within key {k}"


def test_validity_padding_to_tail(rng):
    n = 1024
    cols = jnp.asarray(rng.integers(1, 2**32, size=(6, n), dtype=np.uint32))
    nvalid = 700
    valid = jnp.arange(n) < nvalid
    got = np.asarray(sort_wide_cols(cols, 2, valid))
    ref = np.asarray(lexsort_cols(cols, 2, valid))
    np.testing.assert_array_equal(got, ref)


def test_apply_perm_chunked_matches_flat(rng):
    n = 4096
    rows = rng.integers(0, 2**32, size=(n, 7), dtype=np.uint32)
    perm = rng.permutation(n).astype(np.int32)
    got = np.asarray(apply_perm(jnp.asarray(rows), jnp.asarray(perm),
                                chunk=512))
    np.testing.assert_array_equal(got, rows[perm])


def test_sort_perm_is_permutation(rng):
    n = 1000
    cols = jnp.asarray(rng.integers(0, 50, size=(3, n), dtype=np.uint32))
    keys, perm = sort_perm(cols, 2)
    perm = np.asarray(perm)
    assert sorted(perm.tolist()) == list(range(n))
    np.testing.assert_array_equal(
        np.asarray(keys).T, np_lexsort_rows(np.asarray(cols[:2]).T, 2))


def test_jittable_under_jit(rng):
    cols = jnp.asarray(rng.integers(0, 2**32, size=(25, 512),
                                    dtype=np.uint32))
    f = jax.jit(lambda c: sort_wide_cols(c, 2))
    got = np.asarray(f(cols))
    ref = np.asarray(lexsort_cols(cols, 2))
    np.testing.assert_array_equal(got, ref)


def test_combine_by_key_wide_parity(rng):
    """The wide combine must equal the plain one on identical input."""
    from sparkrdma_tpu.kernels.aggregate import combine_by_key_cols

    n = 1024
    cols = np.zeros((12, n), dtype=np.uint32)
    cols[0] = 0
    cols[1] = rng.integers(0, 30, size=n)
    cols[2:] = rng.integers(0, 1000, size=(10, n))
    valid = rng.random(n) < 0.9
    for op in ("sum", "min", "max"):
        ref, nref = combine_by_key_cols(jnp.asarray(cols),
                                        jnp.asarray(valid), 2, op)
        got, ngot = combine_by_key_cols(jnp.asarray(cols),
                                        jnp.asarray(valid), 2, op,
                                        strategy=SortStrategy("wide"))
        assert int(nref) == int(ngot)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_terasort_wide_records_end_to_end(rng):
    """Full shuffle + fused sort at the HiBench-faithful 25-word (100B)
    record width on the 8-device mesh, verified as a sorted permutation
    of the input (exercises the wide bucket_records and wide fused-tail
    paths end to end)."""
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
    from sparkrdma_tpu.workloads.terasort import run_terasort

    conf = ShuffleConf(slot_records=512, val_words=23)
    m = ShuffleManager(MeshRuntime(conf), conf)
    try:
        res, out, totals = run_terasort(m, records_per_device=256,
                                        shuffle_id=77)
        assert res.verified
        assert res.record_bytes == 100
    finally:
        m.stop()


def test_repartition_wide_records(rng):
    """Multi-partition exchange (wide bucket path) preserves the record
    multiset at 25 words."""
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=512, val_words=23)
    m = ShuffleManager(MeshRuntime(conf), conf)
    try:
        x = rng.integers(1, 2**32, size=(8 * 64, 25), dtype=np.uint32)
        ds = Dataset.from_host_rows(m, x).repartition()
        got = ds.to_host_rows()
        assert got.shape == x.shape

        def canon(a):
            return a[np.lexsort(tuple(a[:, c]
                                      for c in range(a.shape[1] - 1, -1,
                                                     -1)))]
        np.testing.assert_array_equal(canon(got), canon(x))
    finally:
        m.stop()


@pytest.mark.parametrize("ride", [0, 3, 23, 99])
def test_ride_words_parity(rng, ride):
    """Every ride split (none / partial / all / clamped) produces the
    identical sorted result."""
    n = 1024
    cols = jnp.asarray(rng.integers(0, 2**32, size=(25, n),
                                    dtype=np.uint32))
    nvalid = 900
    valid = jnp.arange(n) < nvalid
    ref = np.asarray(lexsort_cols(cols, 2, valid))
    got = np.asarray(sort_wide_cols(cols, 2, valid, ride_words=ride))
    np.testing.assert_array_equal(got, ref)


def test_combine_wide_ride_parity(rng):
    from sparkrdma_tpu.kernels.aggregate import combine_by_key_cols

    n = 1024
    cols = np.zeros((12, n), dtype=np.uint32)
    cols[1] = rng.integers(0, 30, size=n)
    cols[2:] = rng.integers(0, 1000, size=(10, n))
    valid = np.ones(n, bool)
    ref, nref = combine_by_key_cols(jnp.asarray(cols), jnp.asarray(valid),
                                    2, "sum")
    got, ngot = combine_by_key_cols(jnp.asarray(cols), jnp.asarray(valid),
                                    2, "sum", strategy=SortStrategy("wide", 4))
    assert int(nref) == int(ngot)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_wide_verbs_end_to_end(rng):
    """distinct / count_by_key / join / group_by_key at the 25-word
    record width: every verb must route through packed (or wide) sorts
    — none may build the >13-operand comparator the round-4 verdict
    flagged — and match numpy references."""
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=512, val_words=23)
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        assert conf.sort_strategy(conf.record_words).kind == "pack"
        n = 8 * 32
        base = rng.integers(1, 2**31, size=(n // 2, 25), dtype=np.uint32)
        x = np.concatenate([base, base])          # every row twice
        rng.shuffle(x)

        def canon(a):
            return a[np.lexsort(tuple(a[:, c]
                                      for c in range(a.shape[1] - 1, -1,
                                                     -1)))]

        # distinct at W=25
        got = Dataset.from_host_rows(m, x).distinct().to_host_rows()
        np.testing.assert_array_equal(canon(got), canon(np.unique(
            x, axis=0)))

        # count_by_key at W=25 (few distinct keys)
        xk = x.copy()
        xk[:, 0] = 0
        xk[:, 1] = rng.integers(0, 7, size=n)
        ds = Dataset.from_host_rows(m, xk).count_by_key()
        got_counts = {int(r[1]): int(r[2]) for r in ds.to_host_rows()}
        ref_counts = {}
        for k in xk[:, 1]:
            ref_counts[int(k)] = ref_counts.get(int(k), 0) + 1
        assert got_counts == ref_counts

        # materialized join at W=25
        xa = xk[: 8 * 8]
        xb = xk[8 * 8: 8 * 12]
        joined, totals = Dataset.from_host_rows(m, xa).join(
            Dataset.from_host_rows(m, xb))
        rows = Dataset.collect_rows(joined, totals)
        ref = sum(int((xb[:, 1] == xa[i, 1]).sum())
                  for i in range(xa.shape[0]))
        assert rows.shape[0] == ref

        # group_by_key at W=25
        g = Dataset.from_host_rows(m, xk).group_by_key()
        sizes = {k[1]: v.shape[0] for k, v in g.to_host().items()}
        assert sizes == ref_counts
