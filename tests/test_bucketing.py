import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.kernels import (bucket_records, compact_segments,
                                   fill_round_slots,
                                   fill_round_slots_dest_major)
from sparkrdma_tpu.exchange.partitioners import hash_partitioner
from sparkrdma_tpu.kernels.bucketing import (bucket_records_hash_keyed,
                                             histogram_pids)
from sparkrdma_tpu.kernels.sort import SortStrategy


def _cols(rows):
    """Host rows [N, W] -> columnar jnp [W, N]."""
    return jnp.asarray(np.ascontiguousarray(rows.T))


def test_bucket_records_matches_numpy(rng):
    n, p = 200, 8
    rows = rng.integers(0, 2**32, size=(n, 4), dtype=np.uint32)
    pids_np = rng.integers(0, p, size=n).astype(np.int32)
    sr, counts, offs = bucket_records(_cols(rows), jnp.asarray(pids_np), p)
    np_counts = np.bincount(pids_np, minlength=p)
    np.testing.assert_array_equal(np.asarray(counts), np_counts)
    np.testing.assert_array_equal(
        np.asarray(offs), np.concatenate([[0], np.cumsum(np_counts)[:-1]])
    )
    # stable: records within a bucket keep input order; buckets contiguous
    sr_rows = np.asarray(sr).T
    off = 0
    for part in range(p):
        ref = rows[pids_np == part]
        got = sr_rows[off:off + len(ref)]
        np.testing.assert_array_equal(got, ref)
        off += len(ref)


def _canon(rows):
    return rows[np.lexsort(tuple(rows[:, c] for c in range(rows.shape[1])))]


@pytest.mark.parametrize("strategy", [SortStrategy(), SortStrategy("pack")],
                         ids=["plain", "pack"])
def test_bucket_records_unstable_keeps_index_and_multisets(rng, strategy):
    """``stable=False`` may reorder records within a partition and
    nothing else: counts and offsets exact, every partition's run holds
    its records as a multiset, and a row filter's sentinel pid
    ``num_parts`` sorts to the tail, outside every count."""
    n, p = 300, 8
    rows = rng.integers(0, 2**32, size=(n, 4), dtype=np.uint32)
    pids_np = rng.integers(0, p, size=n).astype(np.int32)
    dropped = rng.random(n) < 0.2
    pids_np[dropped] = p
    sr, counts, offs = bucket_records(_cols(rows), jnp.asarray(pids_np), p,
                                      strategy, stable=False)
    np_counts = np.bincount(pids_np[~dropped], minlength=p)
    np.testing.assert_array_equal(np.asarray(counts), np_counts)
    np.testing.assert_array_equal(
        np.asarray(offs), np.concatenate([[0], np.cumsum(np_counts)[:-1]]))
    assert int(np.asarray(counts).sum()) == n - dropped.sum()
    sr_rows = np.asarray(sr).T
    for part in range(p):
        off = int(offs[part])
        np.testing.assert_array_equal(
            _canon(sr_rows[off:off + np_counts[part]]),
            _canon(rows[pids_np == part]))
    np.testing.assert_array_equal(_canon(sr_rows[n - dropped.sum():]),
                                  _canon(rows[dropped]))
    # the index half is the stable form's, bit for bit
    _, s_counts, s_offs = bucket_records(
        _cols(rows), jnp.asarray(pids_np), p, strategy)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(s_counts))
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(s_offs))


@pytest.mark.parametrize("val_words", [0, 3])
@pytest.mark.parametrize("key_words", [1, 2])
@pytest.mark.parametrize("p", [2, 200, 256, 4096])
def test_bucket_records_hash_keyed_matches_pid_keyed(rng, p, key_words,
                                                     val_words):
    """Keying the bucket sort on the record's hash order key instead of
    a pid operand changes nothing but the order within a partition:
    counts and offsets equal the unstable pid-keyed bucket's, and each
    partition's run holds the same records, every word bit-exact."""
    n = 3000
    rows = rng.integers(0, 2**32, size=(n, key_words + val_words),
                        dtype=np.uint32)
    rows[:4, :key_words] = [[0], [2**32 - 1], [0], [2**32 - 1]]
    rows[:2] = rows[2:4]                  # duplicate records
    part = hash_partitioner(p, key_words)
    cols = _cols(rows)
    sr, counts, offs = jax.jit(
        lambda c: bucket_records_hash_keyed(c, part.hash_order, p))(cols)
    r_sr, r_counts, r_offs = bucket_records(cols, part(cols), p,
                                            stable=False)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(r_counts))
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(r_offs))
    assert sr.shape == r_sr.shape and sr.dtype == r_sr.dtype
    # each record sits in its partition's run; per run, the same multiset
    run = np.repeat(np.arange(p), np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(part(sr)), run)
    got = np.column_stack([run, np.asarray(sr).T])
    ref = np.column_stack([run, np.asarray(r_sr).T])
    np.testing.assert_array_equal(_canon(got), _canon(ref))


def test_fill_round_slots_covers_all_records_across_rounds(rng):
    n, p, cap = 100, 4, 8
    rows = rng.integers(1, 2**32, size=(n, 4), dtype=np.uint32)
    pids_np = (rng.integers(0, p, size=n) ** 2 % p).astype(np.int32)
    sr, counts, offs = bucket_records(_cols(rows), jnp.asarray(pids_np), p)
    rounds = int(np.ceil(np.asarray(counts).max() / cap))
    seen = {part: [] for part in range(p)}
    for r in range(rounds):
        slots, sc = fill_round_slots(sr, counts, offs, p, cap, r)
        slots_np = np.asarray(slots)              # [W, P, C]
        for part in range(p):
            k = int(sc[part])
            assert k <= cap
            seen[part].append(slots_np[:, part, :k].T)
            # padding beyond count is zero
            assert not np.any(slots_np[:, part, k:])
    for part in range(p):
        got = np.concatenate(seen[part]) if seen[part] else np.zeros((0, 4))
        ref = rows[pids_np == part]
        np.testing.assert_array_equal(got, ref)


def test_fill_round_slots_jittable(rng):
    n, p, cap = 64, 8, 4
    rows = rng.integers(0, 2**32, size=(n, 4), dtype=np.uint32)
    pids = jnp.asarray(rng.integers(0, p, size=n).astype(np.int32))

    @jax.jit
    def step(recs, pids, r):
        sr, c, o = bucket_records(recs, pids, p)
        return fill_round_slots(sr, c, o, p, cap, r)

    s0, c0 = step(_cols(rows), pids, 0)
    assert s0.shape == (4, p, cap)
    assert int(c0.sum()) <= n


def test_compact_segments_matches_manual(rng):
    s, c, w = 5, 8, 3
    counts = np.array([3, 0, 8, 1, 5], dtype=np.int32)
    stream = np.zeros((s * c, w), dtype=np.uint32)
    expect = []
    for i in range(s):
        seg = rng.integers(1, 2**32, size=(int(counts[i]), w), dtype=np.uint32)
        stream[i * c:i * c + counts[i]] = seg
        expect.append(seg)
    expect = np.concatenate(expect)
    packed, total = compact_segments(_cols(stream), jnp.asarray(counts), 32)
    assert int(total) == int(counts.sum())
    packed_rows = np.asarray(packed).T
    assert np.array_equal(packed_rows[:int(total)], expect)
    assert np.all(packed_rows[int(total):] == 0)


def test_compact_segments_overflow_reported(rng):
    counts = np.array([4, 4], dtype=np.int32)
    stream = rng.integers(1, 100, size=(8, 2), dtype=np.uint32)
    packed, total = compact_segments(_cols(stream), jnp.asarray(counts), 6)
    assert int(total) == 8  # true count exceeds capacity -> caller detects
    assert packed.shape == (2, 6)


def test_fill_round_slots_program_size_flat_in_parts(rng):
    """Deterministic O(1)-program-size guard: the lowered text of the
    slot-fill must not grow with partition count once past the unroll
    limit (the repartition(256) scaling fix — an unrolled form would be
    ~4x larger at 4x the partitions)."""
    import jax

    def lowered_len(p):
        n, cap, w = 1024, 8, 4
        fn = jax.jit(lambda b, c, o: fill_round_slots(b, c, o, p, cap, 0))
        args = (jax.ShapeDtypeStruct((w, n), jnp.uint32),
                jax.ShapeDtypeStruct((p,), jnp.int32),
                jax.ShapeDtypeStruct((p,), jnp.int32))
        return len(fn.lower(*args).as_text())

    l64, l256 = lowered_len(64), lowered_len(256)
    assert l256 < 1.5 * l64, (l64, l256)


def test_compact_segments_program_size_flat_in_segments(rng):
    import jax

    def lowered_len(s):
        c, w = 8, 4
        fn = jax.jit(lambda st, sc: compact_segments(st, sc, 64))
        args = (jax.ShapeDtypeStruct((w, s * c), jnp.uint32),
                jax.ShapeDtypeStruct((s,), jnp.int32))
        return len(fn.lower(*args).as_text())

    l64, l256 = lowered_len(64), lowered_len(256)
    assert l256 < 1.5 * l64, (l64, l256)


@pytest.mark.parametrize("p,n,hot", [
    (4, 5000, None), (32, 5000, None), (64, 5000, None), (300, 5000, None),
    (8, 100, 3),            # empty partitions + everything in one bucket
    # the outer-product form (P > 32, ids not sorted): N of 1, N around
    # the 1024-id tile of a TPU vector, P off the 16-wide grid
    (33, 1, None), (256, 1, None), (64, 1023, None), (256, 1024, None),
    (300, 1025, None), (1024, 5000, None),
    (33, 700, 32), (256, 5000, 0), (256, 5000, 255), (1024, 1025, 1023),
])
def test_histogram_pids_matches_bincount(rng, p, n, hot):
    """Every form (comparison-sum for small P, the outer product for
    large P, searchsorted for pre-sorted ids) matches numpy bincount for
    in-range pids; ``hot`` puts every id in that one bin."""
    pids = (rng.integers(0, p, size=n) if hot is None
            else np.full(n, hot)).astype(np.int32)
    ref = np.bincount(pids, minlength=p)
    got = np.asarray(histogram_pids(jnp.asarray(pids), p))
    np.testing.assert_array_equal(got, ref)
    got_sorted = np.asarray(histogram_pids(
        jnp.asarray(pids), p, sorted_ids=jnp.sort(jnp.asarray(pids))))
    np.testing.assert_array_equal(got_sorted, ref)


@pytest.mark.parametrize("p", [4, 33, 64, 256, 300, 1024])
def test_histogram_pids_drops_out_of_range(rng, p):
    """Ids outside ``[0, P)`` are dropped, never folded into a bin: the
    plan's record-count guard depends on it. That includes ids that
    land on the outer product's grid past ``P`` (P = 300: 300..303)."""
    grid = 16 * -(-p // 16)
    stray = np.array([-1, -2**31, p, grid - 1, grid, 2**31 - 1], np.int32)
    stray = stray[(stray < 0) | (stray >= p)]
    good = rng.integers(0, p, size=777).astype(np.int32)
    pids = rng.permutation(np.concatenate([good, np.repeat(stray, 5)]))
    ref = np.bincount(good, minlength=p)
    got = np.asarray(histogram_pids(jnp.asarray(pids), p))
    np.testing.assert_array_equal(got, ref)
    got_sorted = np.asarray(histogram_pids(
        jnp.asarray(pids), p, sorted_ids=jnp.sort(jnp.asarray(pids))))
    np.testing.assert_array_equal(got_sorted, ref)


def _dest_major_golden(rng, num_parts, mesh_size, cap, n=200, w=4):
    """Pin fill_round_slots_dest_major bit-equal to reshape+transpose of
    fill_round_slots across every round of a random workload."""
    ppd = num_parts // mesh_size
    rows = rng.integers(1, 2**32, size=(n, w), dtype=np.uint32)
    pids = rng.integers(0, num_parts, size=n).astype(np.int32)
    sr, counts, offs = bucket_records(_cols(rows), jnp.asarray(pids),
                                      num_parts)
    rounds = max(1, int(np.ceil(np.asarray(counts).max() / cap)))
    for r in range(rounds + 1):          # +1: a past-the-end empty round
        ref_slots, ref_sc = fill_round_slots(sr, counts, offs,
                                             num_parts, cap, r)
        got_slots, got_sc = fill_round_slots_dest_major(
            sr, counts, offs, num_parts, mesh_size, cap, r)
        assert got_slots.shape == (mesh_size, ppd, w, cap)
        exp = np.asarray(ref_slots).reshape(w, ppd, mesh_size, cap
                                            ).transpose(2, 1, 0, 3)
        np.testing.assert_array_equal(np.asarray(got_slots), exp)
        np.testing.assert_array_equal(np.asarray(got_sc),
                                      np.asarray(ref_sc))


def test_fill_round_slots_dest_major_golden_unrolled(rng):
    """num_parts <= _UNROLL_LIMIT exercises the static-unroll path."""
    _dest_major_golden(rng, num_parts=12, mesh_size=4, cap=5)


def test_fill_round_slots_dest_major_golden_scan(rng):
    """num_parts > _UNROLL_LIMIT exercises the lax.scan path."""
    from sparkrdma_tpu.kernels.bucketing import _UNROLL_LIMIT

    assert 24 > _UNROLL_LIMIT
    _dest_major_golden(rng, num_parts=24, mesh_size=8, cap=4, n=400)


def test_fill_round_slots_dest_major_single_device(rng):
    """mesh_size == 1: dest-major collapses to one device row holding
    every partition window in partition order."""
    _dest_major_golden(rng, num_parts=6, mesh_size=1, cap=7, n=90)


def test_fill_round_slots_dest_major_jittable(rng):
    n, p, mesh, cap = 64, 8, 4, 4
    rows = rng.integers(0, 2**32, size=(n, 3), dtype=np.uint32)
    pids = jnp.asarray(rng.integers(0, p, size=n).astype(np.int32))

    @jax.jit
    def step(recs, pids, r):
        sr, c, o = bucket_records(recs, pids, p)
        return fill_round_slots_dest_major(sr, c, o, p, mesh, cap, r)

    s0, c0 = step(_cols(rows), pids, 0)
    assert s0.shape == (mesh, p // mesh, 3, cap)
    assert int(c0.sum()) <= n
