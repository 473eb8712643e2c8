"""Exchange-protocol correctness on the 8-device CPU mesh.

Golden test per SURVEY.md §4: the shuffled output must be, per destination
partition, exactly the input records whose partitioner says they belong
there (a permutation grouped by source order) — verified against a pure
numpy reference shuffle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.config import ShuffleConf
from sparkrdma_tpu.exchange.partitioners import (
    hash_partitioner,
    modulo_partitioner,
    range_partitioner,
)
from sparkrdma_tpu.exchange.protocol import ShuffleExchange


@pytest.fixture(scope="module")
def exchange():
    from sparkrdma_tpu import MeshRuntime

    rt = MeshRuntime(ShuffleConf(slot_records=16))
    yield ShuffleExchange(rt.mesh, rt.axis_name, rt.conf), rt
    rt.stop()


def make_global_records(rng, rt, n_per_dev, w=4):
    n = n_per_dev * rt.num_partitions
    x = rng.integers(1, 2**32, size=(n, w), dtype=np.uint32)
    return rt.shard_records(x), x


def collect_valid_rows(out, totals, cap):
    """Valid rows of a padded columnar result, concatenated device order."""
    arr = np.asarray(out)
    return np.concatenate(
        [arr[:, d * cap:d * cap + int(totals[d])].T
         for d in range(len(totals))])


def np_reference_shuffle(x, pids, num_parts, mesh_size, n_per_dev):
    """Expected per-device received sets, honoring (partition, source) order."""
    out = {}
    for d in range(mesh_size):
        rows = []
        for q in range(num_parts // mesh_size):
            p = q * mesh_size + d
            for s in range(mesh_size):
                src_rows = x[s * n_per_dev:(s + 1) * n_per_dev]
                src_pids = pids[s * n_per_dev:(s + 1) * n_per_dev]
                rows.append(src_rows[src_pids == p])
        out[d] = np.concatenate(rows) if rows else np.zeros((0, x.shape[1]))
    return out


def run_and_check(exchange_rt, x_global, x_np, part_fn, num_parts, rng):
    ex, rt = exchange_rt
    pids = np.asarray(part_fn(jnp.asarray(x_np.T)))
    out, totals, plan = ex.shuffle(x_global, part_fn, num_parts=num_parts)
    n_per_dev = x_np.shape[0] // rt.num_partitions
    ref = np_reference_shuffle(x_np, pids, num_parts, rt.num_partitions,
                               n_per_dev)
    cap = plan.out_capacity
    out_np = np.asarray(out)                      # columnar [W, mesh*cap]
    totals_np = np.asarray(totals)
    for d in range(rt.num_partitions):
        k = int(totals_np[d])
        assert k == len(ref[d]), f"device {d}: {k} != {len(ref[d])}"
        dev = out_np[:, d * cap:(d + 1) * cap]
        np.testing.assert_array_equal(dev[:, :k].T, ref[d])
        assert not np.any(dev[:, k:])
    # conservation: every record arrives exactly once
    assert totals_np.sum() == x_np.shape[0]
    return plan


def test_single_round_exchange(exchange, rng):
    _, rt = exchange
    xg, xn = make_global_records(rng, rt, 32)
    plan = run_and_check(exchange, xg, xn, modulo_partitioner(8), 8, rng)
    assert plan.num_rounds == 1


def test_multi_round_streaming(exchange, rng):
    """Skewed partitions larger than one slot stream across rounds."""
    _, rt = exchange
    n_per_dev = 64  # worst case 64 records from one src to one dest > 16
    x = rng.integers(1, 2**32, size=(n_per_dev * 8, 4), dtype=np.uint32)
    x[:, 0] = 0  # every record on device 0..7 hashes to partition 0 % 8
    xg = rt.shard_records(x)
    plan = run_and_check(exchange, xg, x, modulo_partitioner(8), 8, rng)
    assert plan.num_rounds == int(np.ceil(64 / 16))


def test_hash_partitioner_balance_and_correctness(exchange, rng):
    _, rt = exchange
    xg, xn = make_global_records(rng, rt, 64)
    part = hash_partitioner(8)
    run_and_check(exchange, xg, xn, part, 8, rng)
    pids = np.asarray(part(jnp.asarray(xn.T)))
    counts = np.bincount(pids, minlength=8)
    assert counts.min() > 0.5 * counts.mean()  # rough balance on random keys


def test_parts_per_device_gt_one(exchange, rng):
    """num_parts = 2x mesh: two reduce partitions per chip."""
    _, rt = exchange
    xg, xn = make_global_records(rng, rt, 32)
    run_and_check(exchange, xg, xn, modulo_partitioner(16), 16, rng)


def test_range_partitioner_lexicographic(rng):
    spl = np.array([[100, 0], [200, 5]], dtype=np.uint32)
    part = range_partitioner(spl, key_words=2)
    recs = jnp.asarray(np.array(
        [[99, 9999, 0, 0],    # < [100,0]        -> 0
         [100, 0, 0, 0],      # == splitter 0    -> 1
         [100, 1, 0, 0],      # > [100,0]        -> 1
         [200, 4, 0, 0],      # < [200,5]        -> 1
         [200, 5, 0, 0],      # == splitter 1    -> 2
         [4000000000, 0, 0, 0]], dtype=np.uint32).T)  # columnar
    np.testing.assert_array_equal(np.asarray(part(recs)), [0, 1, 1, 1, 2, 2])


def test_empty_partitions_ok(exchange, rng):
    """A partitioner that sends everything to one partition leaves the rest
    empty — totals must still be exact (zero), no crash."""
    _, rt = exchange
    x = rng.integers(1, 2**32, size=(8 * 8, 4), dtype=np.uint32)
    x[:, 0] = 5
    xg = rt.shard_records(x)
    run_and_check(exchange, xg, x, modulo_partitioner(8), 8, rng)


def test_plan_splits_excessive_skew(exchange, rng):
    """One hot partition needing 32 rounds with max_rounds=4: the plan
    must split it into same-device sub-partitions and succeed (SURVEY.md
    §7 hard-part 2), with every record still delivered to the owner
    device of the ORIGINAL partition."""
    ex, rt = exchange
    conf = ShuffleConf(slot_records=2, max_rounds=4)
    ex2 = ShuffleExchange(rt.mesh, rt.axis_name, conf)
    x = rng.integers(1, 2**32, size=(8 * 64, 4), dtype=np.uint32)
    x[:, 0] = 0                       # every record -> partition 0
    xg = rt.shard_records(x)
    plan = ex2.plan(xg, modulo_partitioner(8))
    assert plan.split_factor > 1
    assert plan.num_rounds <= conf.max_rounds
    out, totals, _ = ex2.exchange(xg, modulo_partitioner(8), plan)
    tot = np.asarray(totals)
    # partition 0 is owned by device 0; splitting must not move it
    assert tot[0] == x.shape[0] and tot[1:].sum() == 0
    dev0 = np.asarray(out)[:, :int(tot[0])].T
    canon = lambda a: a[np.lexsort(tuple(a[:, c]
                                         for c in range(a.shape[1])))]
    np.testing.assert_array_equal(canon(dev0), canon(x))


def test_split_plan_serves_partition_range_reads(rng):
    """Ranged reads on a SKEW-SPLIT plan must return exactly the ranged
    partitions' records (the reference's RdmaMappedFile serves any
    partition range unconditionally — splitting is our plan-time
    artifact and must stay invisible to readers). Records land skewed:
    most in partition 0 (forcing the split), some in partitions 1/2."""
    from sparkrdma_tpu import MeshRuntime
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    conf = ShuffleConf(slot_records=2, max_rounds=4)
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        part = modulo_partitioner(8)
        x = rng.integers(1, 2**32, size=(8 * 64, 4), dtype=np.uint32)
        x[:, 0] = np.where(np.arange(x.shape[0]) % 8 < 6, 0,
                           np.arange(x.shape[0]) % 8).astype(np.uint32)
        h = m.register_shuffle(60, 8, part)
        plan = m.get_writer(h).write(m.runtime.shard_records(x)).stop(True)
        assert plan.split_factor > 1
        canon = lambda a: a[np.lexsort(tuple(a[:, c]
                                             for c in range(a.shape[1])))]

        def expect(lo, hi):
            return x[(x[:, 0] % 8 >= lo) & (x[:, 0] % 8 < hi)]

        # full range still exact
        out, totals = m.get_reader(h).read()
        assert int(np.asarray(totals).sum()) == x.shape[0]
        # ranged read over the hot partition + a cold one
        out, totals = m.get_reader(h, 0, 2).read()
        got = collect_valid_rows(out, np.asarray(totals),
                                 plan.out_capacity)
        np.testing.assert_array_equal(canon(got), canon(expect(0, 2)))
        # ranged read excluding the hot partition
        out, totals = m.get_reader(h, 6, 8).read()
        got = collect_valid_rows(out, np.asarray(totals),
                                 plan.out_capacity)
        np.testing.assert_array_equal(canon(got), canon(expect(6, 8)))
        # single-partition host view concatenates the sub-partitions
        p0 = m.get_reader(h).read_partition(0)
        np.testing.assert_array_equal(canon(p0), canon(expect(0, 1)))
        # refcounted per-partition views work too
        view = m.get_reader(h).read_view()
        v2 = np.asarray(view.partition(2)).T
        np.testing.assert_array_equal(canon(v2), canon(expect(2, 3)))
        view.release()
        m.unregister_shuffle(60)


def test_repartition_256_geometry(exchange, rng):
    """BASELINE config 1's geometry: 256 partitions on the 8-chip mesh
    (32 partitions per device), both regimes.

    This is the scaling guard for the loop-form kernels: with 256
    partitions the map side must emit a ``lax.scan`` (not 256 unrolled
    slices per round) and the streaming fold a ``fori_loop`` (not
    ppd*mesh*rounds unrolled blend-writes). Content is checked against
    the numpy reference in BOTH regimes (the fold's index decomposition
    has no other ppd>1 content coverage); program-size scaling is pinned
    deterministically in test_bucketing.test_fill_round_slots_program_size.
    """
    _, rt = exchange
    xg, xn = make_global_records(rng, rt, 512)
    part = hash_partitioner(256)
    plan = run_and_check(exchange, xg, xn, part, 256, rng)
    assert plan.num_rounds == 1  # balanced: auto-sized capacity, one round

    # streaming regime at the same partition count: small explicit slots
    # force multiple rounds through the chunk/fold path (fori_loop fold
    # at ppd=32); full golden content check, not just conservation
    conf = ShuffleConf(slot_records=2, max_rounds=16, max_rounds_in_flight=1)
    ex2 = ShuffleExchange(rt.mesh, rt.axis_name, conf)
    plan2 = ex2.plan(xg, part, num_parts=256, capacity=2)
    assert plan2.num_rounds > 1
    out2, tot2, _ = ex2.exchange(xg, part, plan2)
    pids = np.asarray(part(jnp.asarray(xn.T)))
    n_per_dev = xn.shape[0] // rt.num_partitions
    ref = np_reference_shuffle(xn, pids, 256, rt.num_partitions, n_per_dev)
    out_np, tot_np = np.asarray(out2), np.asarray(tot2)
    cap = plan2.out_capacity
    for d in range(rt.num_partitions):
        k = int(tot_np[d])
        assert k == len(ref[d])
        np.testing.assert_array_equal(
            out_np[:, d * cap:d * cap + k].T, ref[d])
    assert tot_np.sum() == xn.shape[0]


def test_exchange_program_cache_reused(exchange, rng):
    ex, rt = exchange
    xg, xn = make_global_records(rng, rt, 32)
    part = modulo_partitioner(8)
    ex.shuffle(xg, part)
    n_programs = len(ex._exec_cache)
    xg2, _ = make_global_records(rng, rt, 32)
    ex.shuffle(xg2, part)
    assert len(ex._exec_cache) == n_programs  # same geometry -> same program


class TestPallasRingTransport:
    """Parity: transport="pallas_ring" must produce byte-identical results
    to the XLA transport (interpret mode on the CPU mesh). This is the
    RdmaChannel one-sided data plane actually carrying the rounds."""

    @pytest.fixture(scope="class")
    def ring_exchange(self):
        from sparkrdma_tpu import MeshRuntime

        rt = MeshRuntime(ShuffleConf(slot_records=16,
                                     transport="pallas_ring"))
        yield ShuffleExchange(rt.mesh, rt.axis_name, rt.conf), rt
        rt.stop()

    def test_parity_single_round(self, exchange, ring_exchange, rng):
        _, rt = exchange
        xg, xn = make_global_records(rng, rt, 32)
        part = modulo_partitioner(8)
        out_x, tot_x, plan_x = exchange[0].shuffle(xg, part, num_parts=8)
        out_r, tot_r, plan_r = ring_exchange[0].shuffle(xg, part,
                                                        num_parts=8)
        assert plan_x.num_rounds == plan_r.num_rounds
        np.testing.assert_array_equal(np.asarray(tot_x), np.asarray(tot_r))
        np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_r))

    def test_parity_multi_round_ppd(self, exchange, ring_exchange, rng):
        """Multi-round streaming + 2 partitions per device over the ring."""
        _, rt = exchange
        xg, xn = make_global_records(rng, rt, 320)
        part = hash_partitioner(16)
        out_x, tot_x, plan_x = exchange[0].shuffle(xg, part, num_parts=16)
        out_r, tot_r, plan_r = ring_exchange[0].shuffle(xg, part,
                                                        num_parts=16)
        assert plan_r.num_rounds > 1, "geometry must force streaming rounds"
        np.testing.assert_array_equal(np.asarray(tot_x), np.asarray(tot_r))
        np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_r))

    def test_ring_correct_vs_numpy(self, ring_exchange, rng):
        """The ring transport independently passes the golden check."""
        _, rt = ring_exchange
        xg, xn = make_global_records(rng, rt, 24)
        run_and_check(ring_exchange, xg, xn, modulo_partitioner(8), 8, rng)


def test_plan_split_extreme_odd_factor(exchange, rng):
    """33-round skew against max_rounds=4 forces a non-power-of-two
    split factor; the plan must still land within the round budget and
    deliver every record (position splitting is uniform by construction,
    so the post-split give-up raise is defensive-only)."""
    ex, rt = exchange
    conf = ShuffleConf(slot_records=2, max_rounds=4)
    ex2 = ShuffleExchange(rt.mesh, rt.axis_name, conf)
    x = rng.integers(1, 2**32, size=(8 * 65, 4), dtype=np.uint32)
    x[:, 0] = 3                          # all -> partition 3
    xg = rt.shard_records(x)
    plan = ex2.plan(xg, modulo_partitioner(8), capacity=2)
    assert plan.num_rounds <= 4
    assert plan.split_factor >= 9        # ceil(ceil(65/2)/4) = 9
    out, totals, _ = ex2.exchange(xg, modulo_partitioner(8), plan)
    tot = np.asarray(totals)
    assert tot[3] == x.shape[0] and tot.sum() == x.shape[0]


class TestHierarchicalTransport:
    """Two-stage intra-host + inter-host a2a must be byte-identical to
    the flat transport (exchange/hierarchical.py — the multi-slice DCN
    path, staged like NCCL's hierarchical alltoall)."""

    @pytest.mark.parametrize("hosts", [2, 4])
    def test_parity_with_flat(self, exchange, rng, hosts):
        from sparkrdma_tpu import MeshRuntime

        _, rt = exchange
        xg, xn = make_global_records(rng, rt, 48)
        part = hash_partitioner(16)
        out_f, tot_f, plan_f = exchange[0].shuffle(xg, part, num_parts=16)

        conf = ShuffleConf(slot_records=16, transport="hierarchical",
                           hierarchy_hosts=hosts)
        ex_h = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        out_h, tot_h, plan_h = ex_h.shuffle(xg, part, num_parts=16)
        assert plan_f.num_rounds == plan_h.num_rounds
        np.testing.assert_array_equal(np.asarray(tot_f), np.asarray(tot_h))
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_h))

    def test_correct_vs_numpy_multi_round(self, exchange, rng):
        """Hierarchical transport independently passes the golden check,
        including streaming rounds."""
        _, rt = exchange
        conf = ShuffleConf(slot_records=16, transport="hierarchical",
                           hierarchy_hosts=2)
        ex_h = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        xg, xn = make_global_records(rng, rt, 80)
        run_and_check((ex_h, rt), xg, xn, modulo_partitioner(8), 8, rng)

    def test_auto_hosts_single_process_degenerates(self, exchange, rng):
        """hosts auto-resolves to 1 in a single process: flat path, still
        correct (the degenerate-hierarchy branch)."""
        from sparkrdma_tpu.exchange.hierarchical import hierarchy_for

        _, rt = exchange
        assert hierarchy_for(rt.mesh, rt.axis_name, 0) == 1
        conf = ShuffleConf(slot_records=16, transport="hierarchical")
        ex_h = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        xg, xn = make_global_records(rng, rt, 24)
        run_and_check((ex_h, rt), xg, xn, modulo_partitioner(8), 8, rng)

    def test_bad_hosts_rejected(self, exchange):
        from sparkrdma_tpu.exchange.hierarchical import hierarchy_for

        _, rt = exchange
        with pytest.raises(ValueError, match="divide"):
            hierarchy_for(rt.mesh, rt.axis_name, 3)


def test_single_device_degenerate_exchange(rng):
    """mesh=1, num_parts=1: the short-circuited exchange (no slot
    machinery) must still deliver every record and honor the fused sort
    — this is the 1-chip bench's hot path."""
    import jax

    from sparkrdma_tpu import MeshRuntime

    conf = ShuffleConf(slot_records=1 << 20)
    rt = MeshRuntime(conf, devices=jax.devices()[:1])
    try:
        ex = ShuffleExchange(rt.mesh, rt.axis_name, conf, pool=rt.pool)
        x = rng.integers(1, 2**32, size=(1000, 4), dtype=np.uint32)
        xg = rt.shard_records(x)
        part = modulo_partitioner(1)
        plan = ex.plan(xg, part, num_parts=1)
        assert plan.num_rounds == 1
        out, totals, _ = ex.exchange(xg, part, plan, sort_key_words=2)
        assert int(np.asarray(totals)[0]) == 1000
        got = np.asarray(out)[:, :1000].T
        order = np.lexsort((x[:, 1], x[:, 0]))
        np.testing.assert_array_equal(got[:, :2], x[order][:, :2])
        # conservation of full records
        canon = lambda a: a[np.lexsort(tuple(a[:, c] for c in range(4)))]
        np.testing.assert_array_equal(canon(got), canon(x))
    finally:
        rt.stop()


def dup_key_records(rng, rt, n_per_dev, n_keys, w=4):
    """Duplicate-heavy keyed records: key word 1 drawn from a small
    space (word 0 zero), random payload words — the shape the map-side
    combine pass exists for."""
    n = n_per_dev * rt.num_partitions
    x = np.zeros((n, w), dtype=np.uint32)
    x[:, 1] = rng.integers(0, n_keys, size=n, dtype=np.uint32)
    for c in range(2, w):
        x[:, c] = rng.integers(0, 2**32, size=n, dtype=np.uint32)
    return rt.shard_records(x), x


def np_reduce_by_key(x, op="sum", kw=2):
    """{key tuple: reduced payload} with uint32 wraparound sums."""
    ref = {}
    for r in x:
        k = tuple(int(v) for v in r[:kw])
        p = r[kw:].astype(np.uint64)
        if k not in ref:
            ref[k] = p.copy()
        elif op == "sum":
            ref[k] = (ref[k] + p) % (1 << 32)
        elif op == "min":
            ref[k] = np.minimum(ref[k], p)
        else:
            ref[k] = np.maximum(ref[k], p)
    return ref


class TestMapSideCombine:
    """The pre-exchange reduction pass: ``map_side_combine="on"`` must
    be bit-identical to ``"off"`` in every regime (the reader-side
    combine still merges across sources either way — combine only
    changes wire bytes, which :meth:`wire_stats` must show shrinking)."""

    def _pair(self, rt, **conf_kw):
        on = ShuffleExchange(rt.mesh, rt.axis_name,
                             ShuffleConf(map_side_combine="on", **conf_kw))
        off = ShuffleExchange(rt.mesh, rt.axis_name,
                              ShuffleConf(map_side_combine="off", **conf_kw))
        return on, off

    def _run(self, ex, xg, part, num_parts, agg):
        plan = ex.plan(xg, part, num_parts=num_parts)
        out, tot, _ = ex.exchange(xg, part, plan, aggregator=agg)
        return np.asarray(out), np.asarray(tot), plan

    @pytest.mark.parametrize("agg", ["sum", "min"])
    def test_fused_parity_and_wire_reduction(self, exchange, rng, agg):
        _, rt = exchange
        xg, xn = dup_key_records(rng, rt, 48, 13)
        part = hash_partitioner(8)
        ex_on, ex_off = self._pair(rt, slot_records=16,
                                   max_rounds_in_flight=8)
        out_on, tot_on, _ = self._run(ex_on, xg, part, 8, agg)
        out_off, tot_off, _ = self._run(ex_off, xg, part, 8, agg)
        np.testing.assert_array_equal(tot_on, tot_off)
        np.testing.assert_array_equal(out_on, out_off)
        ws = ex_on.wire_stats()
        assert ws["combine_out_records"] < ws["combine_in_records"]
        assert ws["combine_out_bytes"] < ws["combine_in_bytes"]
        assert "combine_in_bytes" not in ex_off.wire_stats()
        # the combined result IS the reduce-by-key answer
        got = collect_valid_rows(out_on, tot_on, out_on.shape[1] // 8)
        ref = np_reduce_by_key(xn, agg)
        assert {tuple(map(int, r[:2])): tuple(map(int, r[2:]))
                for r in got} \
            == {k: tuple(map(int, v)) for k, v in ref.items()}

    def test_streaming_parity(self, exchange, rng):
        """max_rounds_in_flight=1 forces the streaming regime; the
        combined per-round ragged counts ride the size-exchange lane."""
        _, rt = exchange
        xg, xn = dup_key_records(rng, rt, 64, 7)
        part = hash_partitioner(8)
        ex_on, ex_off = self._pair(rt, slot_records=16,
                                   max_rounds_in_flight=1, max_rounds=64)
        out_on, tot_on, plan_on = self._run(ex_on, xg, part, 8, "sum")
        out_off, tot_off, _ = self._run(ex_off, xg, part, 8, "sum")
        assert plan_on.num_rounds > 1, "geometry must force streaming"
        np.testing.assert_array_equal(tot_on, tot_off)
        np.testing.assert_array_equal(out_on, out_off)

    def test_ring_fused_parity(self, exchange, rng):
        """transport="pallas_ring" (fused multi-round kernel, interpret
        mode on CPU): combine on/off parity, and vs the xla transport."""
        _, rt = exchange
        xg, xn = dup_key_records(rng, rt, 40, 9)
        part = hash_partitioner(8)
        ex_on, ex_off = self._pair(rt, slot_records=16,
                                   max_rounds_in_flight=8,
                                   transport="pallas_ring")
        out_on, tot_on, _ = self._run(ex_on, xg, part, 8, "sum")
        out_off, tot_off, _ = self._run(ex_off, xg, part, 8, "sum")
        np.testing.assert_array_equal(tot_on, tot_off)
        np.testing.assert_array_equal(out_on, out_off)
        ex_xla = ShuffleExchange(rt.mesh, rt.axis_name,
                                 ShuffleConf(map_side_combine="on",
                                             slot_records=16,
                                             max_rounds_in_flight=8))
        out_x, tot_x, _ = self._run(ex_xla, xg, part, 8, "sum")
        np.testing.assert_array_equal(tot_on, tot_x)
        np.testing.assert_array_equal(out_on, out_x)

    def test_ragged_compacted_rounds(self, exchange, rng):
        """Skew into one partition (40 records over capacity-16 slots =
        rounds [16, 16, 8]): the combine pass compacts each source's
        contribution, so late rounds go ragged-to-empty — totals and
        content must still match combine-off exactly."""
        _, rt = exchange
        n = 8 * 40
        x = np.zeros((n, 4), dtype=np.uint32)
        x[:, 0] = 5                        # all -> partition 5
        x[:, 1] = rng.integers(0, 11, size=n, dtype=np.uint32)
        x[:, 2] = rng.integers(0, 2**32, size=n, dtype=np.uint32)
        xg = rt.shard_records(x)
        part = modulo_partitioner(8)
        ex_on, ex_off = self._pair(rt, slot_records=16,
                                   max_rounds_in_flight=8)
        out_on, tot_on, plan_on = self._run(ex_on, xg, part, 8, "sum")
        out_off, tot_off, _ = self._run(ex_off, xg, part, 8, "sum")
        assert plan_on.num_rounds == 3     # planned on PRE-combine counts
        np.testing.assert_array_equal(tot_on, tot_off)
        np.testing.assert_array_equal(out_on, out_off)

    def test_single_device_parity(self, rng):
        """mesh=1: the short-circuited exchange honors the combine flag
        both ways and still produces the reduce-by-key answer."""
        import jax

        from sparkrdma_tpu import MeshRuntime

        outs = {}
        for mode in ("on", "off"):
            conf = ShuffleConf(slot_records=1 << 20, map_side_combine=mode)
            rt = MeshRuntime(conf, devices=jax.devices()[:1])
            try:
                ex = ShuffleExchange(rt.mesh, rt.axis_name, conf,
                                     pool=rt.pool)
                n = 600
                x = np.zeros((n, 4), dtype=np.uint32)
                x[:, 1] = rng.integers(0, 9, size=n, dtype=np.uint32)
                x[:, 2] = rng.integers(0, 2**32, size=n, dtype=np.uint32)
                xg = rt.shard_records(x)
                part = modulo_partitioner(1)
                plan = ex.plan(xg, part, num_parts=1)
                out, tot, _ = ex.exchange(xg, part, plan, aggregator="sum")
                k = int(np.asarray(tot)[0])
                outs[mode] = np.asarray(out)[:, :k].T.copy()
            finally:
                rt.stop()
            rng = np.random.default_rng(0)   # same data both modes
        np.testing.assert_array_equal(outs["on"], outs["off"])
        ref = np_reduce_by_key(x, "sum")
        got = {tuple(map(int, r[:2])): tuple(map(int, r[2:]))
               for r in outs["on"]}
        assert got == {k: tuple(map(int, v)) for k, v in ref.items()}

    def test_degradation_ladder_fallback(self, exchange, rng, monkeypatch):
        """A map-side-combine program that fails to construct must
        degrade through the PR-5 ladder: sticky combine-off retry, the
        ``combine.fallbacks`` counter moves, the degradation is noted —
        and the output is still the correct combined answer."""
        from sparkrdma_tpu import faults
        from sparkrdma_tpu.kernels import aggregate
        from sparkrdma_tpu.obs.metrics import MetricsRegistry

        def boom(*a, **kw):
            raise RuntimeError("injected combine construction failure")

        monkeypatch.setattr(aggregate, "map_side_combine_cols", boom)
        _, rt = exchange
        xg, xn = dup_key_records(rng, rt, 32, 7)
        part = hash_partitioner(8)
        reg = MetricsRegistry(enabled=True)
        conf = ShuffleConf(slot_records=16, map_side_combine="on")
        ex = ShuffleExchange(rt.mesh, rt.axis_name, conf, metrics=reg)
        faults.reset_accounting()
        try:
            plan = ex.plan(xg, part, num_parts=8)
            out, tot, _ = ex.exchange(xg, part, plan, aggregator="sum")
            assert int(reg.counter("combine.fallbacks").value) == 1
            assert ex._combine_override, "combine-off must be sticky"
            assert "combine" in faults.active_degradations()
            got = collect_valid_rows(np.asarray(out), np.asarray(tot),
                                     np.asarray(out).shape[1] // 8)
            ref = np_reduce_by_key(xn, "sum")
            assert {tuple(map(int, r[:2])): tuple(map(int, r[2:]))
                    for r in got} \
                == {k: tuple(map(int, v)) for k, v in ref.items()}
            # a second exchange must not retry combine construction
            ex.exchange(xg, part, plan, aggregator="sum")
            assert int(reg.counter("combine.fallbacks").value) == 1
        finally:
            faults.reset_accounting()

    def test_combine_fallback_off_raises(self, exchange, rng, monkeypatch):
        """combine_fallback=False: construction failures surface instead
        of silently shipping uncombined."""
        from sparkrdma_tpu.kernels import aggregate

        def boom(*a, **kw):
            raise RuntimeError("injected combine construction failure")

        monkeypatch.setattr(aggregate, "map_side_combine_cols", boom)
        _, rt = exchange
        xg, _ = dup_key_records(rng, rt, 16, 5)
        part = hash_partitioner(8)
        conf = ShuffleConf(slot_records=16, map_side_combine="on",
                           combine_fallback=False)
        ex = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        plan = ex.plan(xg, part, num_parts=8)
        with pytest.raises(RuntimeError, match="injected combine"):
            ex.exchange(xg, part, plan, aggregator="sum")


class TestPushdownExchange:
    """Predicate/projection pushdown at the exchange layer: dropped rows
    never occupy a slot, dropped words never hit the wire (re-widened
    zero-filled on the reader)."""

    def test_row_filter_matches_prefiltered_shuffle(self, exchange, rng):
        _, rt = exchange
        xg, xn = make_global_records(rng, rt, 32)
        part = modulo_partitioner(8)

        def keep_even(records):
            return (records[2] & 1) == 0

        keep_even.cache_key = ("keep_even_w2",)
        ex = ShuffleExchange(rt.mesh, rt.axis_name,
                             ShuffleConf(slot_records=16))
        plan = ex.plan(xg, part, num_parts=8)
        out, tot, _ = ex.exchange(xg, part, plan, row_filter=keep_even)
        mask = (xn[:, 2] & 1) == 0
        kept = xn[mask]
        pids = np.asarray(part(jnp.asarray(kept.T)))
        # reference: shuffle of the PRE-filtered rows. Source order is
        # preserved within each device, so the reference applies.
        n_per_dev = xn.shape[0] // rt.num_partitions
        dev_of = np.repeat(np.arange(rt.num_partitions), n_per_dev)[mask]
        cap = plan.out_capacity
        out_np, tot_np = np.asarray(out), np.asarray(tot)
        for d in range(rt.num_partitions):
            ref = np.concatenate(
                [kept[(dev_of == s) & (pids == d)]
                 for s in range(rt.num_partitions)])
            k = int(tot_np[d])
            assert k == len(ref)
            np.testing.assert_array_equal(
                out_np[:, d * cap:d * cap + k].T, ref)
        assert tot_np.sum() == mask.sum()
        ws = ex.wire_stats()
        assert ws["pushdown_rows_dropped"] == int((~mask).sum())

    def test_keep_words_projection_zero_fills(self, exchange, rng):
        _, rt = exchange
        xg, xn = make_global_records(rng, rt, 32, w=6)
        part = modulo_partitioner(8)
        conf = ShuffleConf(slot_records=16, val_words=4)
        ex = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        plan = ex.plan(xg, part, num_parts=8)
        out, tot, _ = ex.exchange(xg, part, plan, keep_words=(0, 1, 3, 5))
        # reference: full shuffle of x with words 2 and 4 zeroed
        x_ref = xn.copy()
        x_ref[:, 2] = 0
        x_ref[:, 4] = 0
        ex_full = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        out_f, tot_f, _ = ex_full.exchange(
            rt.shard_records(x_ref), part, ex_full.plan(
                rt.shard_records(x_ref), part, num_parts=8))
        np.testing.assert_array_equal(np.asarray(tot), np.asarray(tot_f))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_f))
        ws = ex.wire_stats()
        assert ws["pushdown_words_dropped"] == 2 * int(np.asarray(tot).sum())

    def test_keep_words_validation(self, exchange, rng):
        _, rt = exchange
        xg, _ = make_global_records(rng, rt, 8)
        part = modulo_partitioner(8)
        ex = ShuffleExchange(rt.mesh, rt.axis_name,
                             ShuffleConf(slot_records=16))
        plan = ex.plan(xg, part, num_parts=8)
        with pytest.raises(ValueError, match="key words"):
            ex.exchange(xg, part, plan, keep_words=(0, 2))   # missing kw 1
        with pytest.raises(ValueError, match="increasing"):
            ex.exchange(xg, part, plan, keep_words=(0, 1, 3, 3))
        with pytest.raises(ValueError, match="out of range"):
            ex.exchange(xg, part, plan, keep_words=(0, 1, 9))

    def test_filter_projection_combine_together(self, exchange, rng):
        """All three pushdowns composed, on/off combine parity."""
        _, rt = exchange
        xg, xn = dup_key_records(rng, rt, 48, 11, w=6)
        part = hash_partitioner(8)

        def keep_small(records):
            return records[1] < 8

        keep_small.cache_key = ("keep_small_k",)
        outs = {}
        for mode in ("on", "off"):
            conf = ShuffleConf(slot_records=16, map_side_combine=mode,
                               val_words=4)
            ex = ShuffleExchange(rt.mesh, rt.axis_name, conf)
            plan = ex.plan(xg, part, num_parts=8)
            out, tot, _ = ex.exchange(xg, part, plan, aggregator="sum",
                                      row_filter=keep_small,
                                      keep_words=(0, 1, 2, 4))
            outs[mode] = (np.asarray(out).copy(), np.asarray(tot).copy())
        np.testing.assert_array_equal(outs["on"][1], outs["off"][1])
        np.testing.assert_array_equal(outs["on"][0], outs["off"][0])
        # vs numpy: filter, project (zero words 3 and 5), reduce
        kept = xn[xn[:, 1] < 8].copy()
        kept[:, 3] = 0
        kept[:, 5] = 0
        ref = np_reduce_by_key(kept, "sum")
        got = collect_valid_rows(outs["on"][0], outs["on"][1],
                                 outs["on"][0].shape[1] // 8)
        assert {tuple(map(int, r[:2])): tuple(map(int, r[2:]))
                for r in got} \
            == {k: tuple(map(int, v)) for k, v in ref.items()}


def test_plan_rejects_out_of_range_partitioner(exchange, rng):
    """A buggy partitioner emitting ids outside [0, num_parts) must fail
    loudly at plan time, not silently understate counts (round-3
    advisor finding on histogram_pids' drop semantics)."""
    ex, rt = exchange
    records, _ = make_global_records(rng, rt, 32)

    def bad_part(records):
        return jnp.full((records.shape[1],), 9, jnp.int32)  # >= num_parts

    bad_part.cache_key = ("bad", 9)
    with pytest.raises(ValueError, match="out-of-range"):
        ex.plan(records, bad_part, num_parts=8)


class TestRingFusedKernel:
    """The multi-round fused kernel (round 8): ``make_ring_exchange``
    pinned bit-equal to R independent ``lax.all_to_all`` rounds in
    interpret mode, plus full-exchange parity for the shapes the
    acceptance bar names (repartition, terasort, streaming, ragged)."""

    @pytest.mark.parametrize("num_rounds", [1, 2, 5])
    def test_kernel_parity_vs_all_to_all(self, runtime, rng, num_rounds):
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from sparkrdma_tpu.exchange.ring import (derive_collective_id,
                                                 make_ring_exchange)

        rt = runtime
        mesh_size = rt.num_partitions
        ex = make_ring_exchange(
            rt.mesh, rt.axis_name, num_rounds,
            collective_id=derive_collective_id(("kernel", num_rounds)))
        g = jnp.asarray(rng.integers(
            0, 2**32, size=(num_rounds, mesh_size * mesh_size, 3, 5),
            dtype=np.uint32))

        def ref_fn(s):
            return jnp.stack([
                lax.all_to_all(s[r], rt.axis_name, 0, 0, tiled=True)
                for r in range(num_rounds)])

        sm = dict(mesh=rt.mesh, in_specs=P(None, rt.axis_name),
                  out_specs=P(None, rt.axis_name), check_vma=False)
        fused = shard_map(ex, **sm)(g)
        ref = shard_map(ref_fn, **sm)(g)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))

    def test_kernel_single_device_identity(self, rng):
        import jax

        from sparkrdma_tpu import MeshRuntime
        from sparkrdma_tpu.exchange.ring import make_ring_exchange

        rt = MeshRuntime(ShuffleConf(slot_records=16),
                         devices=jax.devices()[:1])
        try:
            ex = make_ring_exchange(rt.mesh, rt.axis_name, 3)
            g = jnp.asarray(rng.integers(0, 2**32, size=(3, 1, 2, 4),
                                         dtype=np.uint32))
            np.testing.assert_array_equal(np.asarray(ex(g)), np.asarray(g))
        finally:
            rt.stop()

    def test_kernel_rejects_round_mismatch(self, runtime, rng):
        from sparkrdma_tpu.exchange.ring import make_ring_exchange

        ex = make_ring_exchange(runtime.mesh, runtime.axis_name, 2)
        bad = jnp.zeros((3, 64, 1, 1), jnp.uint32)
        with pytest.raises(ValueError, match="fused exchange built for"):
            ex(bad)


class TestRingFusedExchange:
    """Full-protocol parity: ``pallas_ring`` + ``ring_fused`` (the
    default) must stay byte-identical to ``transport="xla"``."""

    @pytest.fixture(scope="class")
    def xla_exchange(self):
        from sparkrdma_tpu import MeshRuntime

        rt = MeshRuntime(ShuffleConf(slot_records=16,
                                     max_rounds_in_flight=8))
        yield ShuffleExchange(rt.mesh, rt.axis_name, rt.conf), rt
        rt.stop()

    @pytest.fixture(scope="class")
    def fused_exchange(self):
        from sparkrdma_tpu import MeshRuntime

        rt = MeshRuntime(ShuffleConf(slot_records=16,
                                     max_rounds_in_flight=8,
                                     transport="pallas_ring"))
        assert rt.conf.ring_fused  # the default: fused is the ring path
        yield ShuffleExchange(rt.mesh, rt.axis_name, rt.conf), rt
        rt.stop()

    def test_parity_ragged_multi_round(self, xla_exchange, fused_exchange,
                                       rng):
        """Skew forcing several fused-regime rounds with a partially
        filled (ragged) last round: 40 records into one partition over
        capacity-16 slots = rounds [16, 16, 8]."""
        _, rt = xla_exchange
        x = rng.integers(1, 2**32, size=(8 * 40, 4), dtype=np.uint32)
        x[:, 0] = 5                       # all -> partition 5
        xg = rt.shard_records(x)
        part = modulo_partitioner(8)
        out_x, tot_x, plan_x = xla_exchange[0].shuffle(xg, part,
                                                       num_parts=8)
        out_r, tot_r, plan_r = fused_exchange[0].shuffle(xg, part,
                                                         num_parts=8)
        assert plan_r.num_rounds == 3     # ragged: 40 = 16 + 16 + 8
        assert plan_r.num_rounds <= 8     # fused regime, not streaming
        np.testing.assert_array_equal(np.asarray(tot_x), np.asarray(tot_r))
        np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_r))

    def test_parity_terasort_shape(self, xla_exchange, fused_exchange,
                                   rng):
        """The terasort shape: sort_key_words=2 fuses the reduce-side
        sort into the same program as the fused transport."""
        _, rt = xla_exchange
        xg, xn = make_global_records(rng, rt, 48)
        part = hash_partitioner(8)
        ex_x, ex_r = xla_exchange[0], fused_exchange[0]
        plan_x = ex_x.plan(xg, part, num_parts=8)
        plan_r = ex_r.plan(xg, part, num_parts=8)
        out_x, tot_x, _ = ex_x.exchange(xg, part, plan_x,
                                        sort_key_words=2)
        out_r, tot_r, _ = ex_r.exchange(xg, part, plan_r,
                                        sort_key_words=2)
        np.testing.assert_array_equal(np.asarray(tot_x), np.asarray(tot_r))
        np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_r))

    def test_fused_counters_and_unfused_parity(self, fused_exchange, rng):
        """The fused path really ran (trace-time counters moved), and
        ``ring_fused=False`` (per-round kernels) stays byte-identical."""
        from sparkrdma_tpu.obs.metrics import MetricsRegistry

        _, rt = fused_exchange
        reg = MetricsRegistry(enabled=True)
        ex_f = ShuffleExchange(rt.mesh, rt.axis_name, rt.conf, metrics=reg)
        xg, xn = make_global_records(rng, rt, 32)
        part = modulo_partitioner(8)
        out_f, tot_f, _ = ex_f.shuffle(xg, part, num_parts=8)
        assert int(reg.counter("transport.ring.fused_kernels").value) >= 1
        assert int(reg.counter("transport.ring.fused_rounds").value) >= 1
        conf = ShuffleConf(slot_records=16, max_rounds_in_flight=8,
                           transport="pallas_ring", ring_fused=False)
        ex_u = ShuffleExchange(rt.mesh, rt.axis_name, conf)
        out_u, tot_u, _ = ex_u.shuffle(xg, part, num_parts=8)
        np.testing.assert_array_equal(np.asarray(tot_f), np.asarray(tot_u))
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_u))

    def test_fused_golden_vs_numpy(self, fused_exchange, rng):
        """The fused transport independently passes the golden check
        (repartition shape)."""
        _, rt = fused_exchange
        xg, xn = make_global_records(rng, rt, 24)
        run_and_check(fused_exchange, xg, xn, hash_partitioner(16), 16,
                      rng)

    def test_parity_streaming_regime(self, rng):
        """Guaranteed streaming regime (rounds > max_rounds_in_flight):
        72 skewed records over capacity-16 slots = 5 rounds against
        F=2, so _build_chunk's fused path runs 3 chunks with a ragged
        final chunk — byte-identical to the xla transport."""
        from sparkrdma_tpu import MeshRuntime

        rt = MeshRuntime(ShuffleConf(slot_records=16,
                                     max_rounds_in_flight=2,
                                     transport="pallas_ring"))
        try:
            ex_r = ShuffleExchange(rt.mesh, rt.axis_name, rt.conf)
            conf_x = ShuffleConf(slot_records=16, max_rounds_in_flight=2)
            ex_x = ShuffleExchange(rt.mesh, rt.axis_name, conf_x)
            x = np.asarray(np.random.default_rng(7).integers(
                1, 2**32, size=(8 * 72, 4), dtype=np.uint32))
            x[:, 0] = 5                   # all -> partition 5
            xg = rt.shard_records(x)
            part = modulo_partitioner(8)
            out_x, tot_x, plan_x = ex_x.shuffle(xg, part, num_parts=8)
            out_r, tot_r, plan_r = ex_r.shuffle(xg, part, num_parts=8)
            assert plan_r.num_rounds == 5       # 72 = 4*16 + 8 (ragged)
            assert plan_r.num_rounds > rt.conf.max_rounds_in_flight
            np.testing.assert_array_equal(np.asarray(tot_x),
                                          np.asarray(tot_r))
            np.testing.assert_array_equal(np.asarray(out_x),
                                          np.asarray(out_r))
        finally:
            rt.stop()
