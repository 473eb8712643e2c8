"""groupByKey / cogroup: CSR grouping kernels + Dataset verbs vs numpy.

Reference contract: Spark's ``rdd.groupByKey`` yields, per key, the full
multiset of values (arrival order NOT promised across partitions);
``cogroup`` pairs both sides' value lists over the union of keys.
Verified against dict-of-lists numpy references, including skewed
multiplicities (one hot key holding most records) and wide (25-word)
records.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.config import ShuffleConf
from sparkrdma_tpu.kernels.group import cogroup_tables, group_runs_cols
from sparkrdma_tpu.kernels.sort import SortStrategy


def np_groups(rows, kw):
    """key tuple -> sorted payload rows (canonical multiset form)."""
    out = {}
    for r in rows:
        out.setdefault(tuple(int(v) for v in r[:kw]), []).append(r[kw:])
    return {k: canon(np.array(v, dtype=np.uint32))
            for k, v in out.items()}


def canon(a):
    if a.size == 0:
        return a
    return a[np.lexsort(tuple(a[:, c]
                              for c in range(a.shape[1] - 1, -1, -1)))]


@pytest.mark.parametrize("w,wide", [(4, False), (25, True)])
def test_group_runs_cols_matches_numpy(rng, w, wide):
    n, kw = 1024, 2
    rows = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    rows[:, 0] = rng.integers(0, 3, size=n)       # few hi words
    rows[:, 1] = rng.integers(0, 20, size=n)      # ~60 distinct keys
    rows[: n // 2, :kw] = [1, 7]                  # hot key: half the rows
    valid = rng.random(n) < 0.9
    values, groups, n_groups, total = group_runs_cols(
        jnp.asarray(rows.T), jnp.asarray(valid), kw,
        SortStrategy("wide", 3) if wide else SortStrategy())
    values, groups = np.asarray(values), np.asarray(groups)
    ng, tot = int(n_groups), int(total)
    ref = np_groups(rows[valid], kw)
    assert tot == valid.sum()
    assert ng == len(ref)
    got = {}
    keys_seen = []
    for i in range(ng):
        key = tuple(int(groups[k, i]) for k in range(kw))
        cnt, off = int(groups[kw, i]), int(groups[kw + 1, i])
        got[key] = canon(values[kw:, off:off + cnt].T)
        keys_seen.append(key)
    assert keys_seen == sorted(keys_seen), "groups not key-ascending"
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=f"key {k}")
    # zero tails
    assert not np.any(groups[:, ng:])
    assert not np.any(values[:, tot:])


def test_cogroup_tables_union(rng):
    kw, w = 2, 4
    na, nb = 256, 384

    def gen(n, key_lo):
        rows = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
        rows[:, 0] = 0
        rows[:, 1] = rng.integers(key_lo, key_lo + 12, size=n)
        return rows

    a = gen(na, 0)        # keys 0..11
    b = gen(nb, 6)        # keys 6..17: overlap 6..11, each side has own
    va, ga, n_a, _ = group_runs_cols(jnp.asarray(a.T),
                                     jnp.ones(na, bool), kw)
    vb, gb, n_b, _ = group_runs_cols(jnp.asarray(b.T),
                                     jnp.ones(nb, bool), kw)
    table, n_u = cogroup_tables(ga, n_a, gb, n_b, kw)
    table = np.asarray(table)
    n_u = int(n_u)
    ref_a, ref_b = np_groups(a, kw), np_groups(b, kw)
    assert n_u == len(set(ref_a) | set(ref_b))
    va, vb = np.asarray(va), np.asarray(vb)
    for i in range(n_u):
        key = tuple(int(table[k, i]) for k in range(kw))
        ca_, oa = int(table[kw, i]), int(table[kw + 1, i])
        cb_, ob = int(table[kw + 2, i]), int(table[kw + 3, i])
        got_a = canon(va[kw:, oa:oa + ca_].T)
        got_b = canon(vb[kw:, ob:ob + cb_].T)
        np.testing.assert_array_equal(
            got_a, ref_a.get(key, np.zeros((0, w - kw), np.uint32)))
        np.testing.assert_array_equal(
            got_b, ref_b.get(key, np.zeros((0, w - kw), np.uint32)))
    assert not np.any(table[:, n_u:])


@pytest.mark.parametrize("w", [4, 25])
def test_dataset_group_by_key(rng, w):
    """End-to-end verb on the 8-device mesh, incl. the wide path."""
    from sparkrdma_tpu import MeshRuntime
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=512, val_words=w - 2)
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        n = 8 * 48
        rows = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
        rows[:, 0] = 0
        rows[:, 1] = rng.integers(0, 25, size=n)
        rows[: n // 3, 1] = 13                    # skewed multiplicity
        g = Dataset.from_host_rows(m, rows).group_by_key()
        got = {k: canon(v) for k, v in g.to_host().items()}
        ref = np_groups(rows, 2)
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k])


def test_dataset_cogroup(rng):
    from sparkrdma_tpu import MeshRuntime
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=512)
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        w = conf.record_words

        def gen(n, lo):
            rows = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
            rows[:, 0] = 0
            rows[:, 1] = rng.integers(lo, lo + 10, size=n)
            return rows

        a, b = gen(8 * 32, 0), gen(8 * 24, 5)
        cg = Dataset.from_host_rows(m, a).cogroup(
            Dataset.from_host_rows(m, b))
        got = cg.to_host()
        ref_a, ref_b = np_groups(a, 2), np_groups(b, 2)
        assert set(got) == set(ref_a) | set(ref_b)
        empty = np.zeros((0, w - 2), np.uint32)
        for k, (va, vb) in got.items():
            np.testing.assert_array_equal(canon(va),
                                          ref_a.get(k, empty))
            np.testing.assert_array_equal(canon(vb),
                                          ref_b.get(k, empty))


def test_dataset_cogroup_rejects_cross_manager(rng):
    from sparkrdma_tpu import MeshRuntime
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=512)
    with ShuffleManager(MeshRuntime(conf), conf) as m1, \
            ShuffleManager(MeshRuntime(conf), conf) as m2:
        rows = rng.integers(1, 2**31, size=(8, conf.record_words),
                            dtype=np.uint32)
        with pytest.raises(ValueError, match="same manager"):
            Dataset.from_host_rows(m1, rows).cogroup(
                Dataset.from_host_rows(m2, rows))
