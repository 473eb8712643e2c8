"""The shuffle names its phases where the work happens.

- Device: every compiled phase carries its ``sr_*`` name scope into the
  program's op metadata (JAX's name stack), which a profiler trace then
  holds as each op's ``tf_op`` stat.
- Host: the ``shuffle:*`` TraceAnnotations of one job, nested as the
  work is, in a real profiler trace.
- Programs: a cache miss counts ``exchange.programs_built.<kind>``.
"""

import re

import numpy as np
import pytest

import jax

from sparkrdma_tpu import MeshRuntime, ShuffleConf
from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.exchange.partitioners import (hash_partitioner,
                                                 modulo_partitioner,
                                                 range_partitioner)
from sparkrdma_tpu.exchange.protocol import ShuffleExchange, _make_count_fn
from sparkrdma_tpu.meta.sampling import compute_splitters, make_sampler

N_LOCAL = 16
KW = 2
#: payload wide enough for the key+index sort and the gather placement
WIDE = dict(key_words=KW, val_words=2, wide_sort_min_payload=1,
            pack_sort_min_payload=0, wide_sort_ride_words=0)


@pytest.fixture(scope="module")
def lowered(runtime):
    """Debug-info text of each program a shuffle compiles, by name."""
    mesh, ax = runtime.mesh, runtime.axis_name
    parts = runtime.num_partitions
    rows = np.random.default_rng(3).integers(
        0, 2**32, size=(parts * N_LOCAL, 4), dtype=np.uint32)
    x = runtime.shard_records(rows)
    ex = ShuffleExchange(mesh, ax, ShuffleConf(**WIDE))
    part = modulo_partitioner(parts)
    cap, out_cap = N_LOCAL, parts * N_LOCAL

    def text(fn, *args):
        return fn.lower(*args).as_text(debug_info=True)

    return {
        "sample": text(make_sampler(mesh, ax, KW, 4), x),
        "count": text(_make_count_fn(mesh, ax, parts, part), x),
        "sort": text(ex._build_exec(parts, cap, 1, out_cap, 4, part,
                                    sort_key_words=KW), x),
        "combine": text(ex._build_exec(parts, cap, 1, out_cap, 4, part,
                                       aggregator="sum", combine=True), x),
    }


@pytest.mark.parametrize("scope,program", [
    ("sr_sample", "sample"),
    ("sr_count", "count"),
    ("sr_bucket", "sort"),
    ("sr_slots", "sort"),
    ("sr_exchange", "sort"),
    ("sr_compact", "sort"),
    ("sr_sort_keys", "sort"),
    ("sr_sort_gather", "sort"),
    ("sr_combine", "combine"),
])
def test_program_carries_phase_scope(lowered, scope, program):
    # a location's name stack starts the string or follows a parent
    assert re.search(f'["/]{scope}/', lowered[program])


def test_map_side_gather_stays_in_bucket_phase(lowered):
    """The wide map-side bucket places its payload by a gather too; it
    is bucketing, so no op under ``sr_bucket`` names the reduce-side
    gather."""
    assert re.search('["/]sr_bucket/', lowered["sort"])
    assert "sr_bucket/sr_sort_gather" not in lowered["sort"]
    assert "sr_bucket/sr_sort_keys" not in lowered["sort"]


def _count_hlo(runtime, parts):
    """Optimised HLO of the plan's count program over a hash
    partitioner; its ``op_name`` metadata is what a trace's ``tf_op``
    holds."""
    x = runtime.shard_records(_rows(4, runtime.num_partitions)[:, :2])
    fn = _make_count_fn(runtime.mesh, runtime.axis_name, parts,
                        hash_partitioner(parts))
    return fn.lower(x).compile().as_text()


def test_count_program_histograms_without_sort(runtime):
    """repartition(256): the size exchange counts in one outer product
    and sorts nothing; the product's ops stay in ``sr_count``."""
    hlo = _count_hlo(runtime, 256)
    assert not re.search(r"\bsort\(", hlo)
    dots = re.findall(r'op_name="([^"]*dot_general)"', hlo)
    assert dots and all("sr_count/" in d for d in dots), dots


def test_small_count_program_keeps_compare_sum(runtime):
    hlo = _count_hlo(runtime, 8)
    assert "dot_general" not in hlo and not re.search(r"\bsort\(", hlo)
    sums = re.findall(r'op_name="([^"]*reduce_sum)"', hlo)
    assert sums and all("sr_count/" in s for s in sums), sums


def _job(m, sid, rows, splitters=None, end_partition=None):
    """One whole job; ``end_partition`` reads a partition range, which
    the manager key-sorts in a program of its own."""
    rt = m.runtime
    x = rt.shard_records(rows)
    parts = rt.num_partitions
    if splitters is None:
        part = modulo_partitioner(parts)
    else:
        part = range_partitioner(splitters, KW)
    h = m.register_shuffle(sid, parts, part)
    m.get_writer(h).write(x).stop(True)
    out, totals = m.get_reader(h, end_partition=end_partition,
                               key_ordering=end_partition is not None
                               ).read()
    jax.block_until_ready((out, totals))
    m.unregister_shuffle(sid)
    return out, totals


def _built(m, kind):
    return m.metrics.counter(f"exchange.programs_built.{kind}").value


def _keep_even(records):
    return (records[2] & 1) == 0


_keep_even.cache_key = ("keep_even",)


def _rows(seed, parts):
    return np.random.default_rng(seed).integers(
        0, 2**32, size=(parts * N_LOCAL, 4), dtype=np.uint32)


def test_new_splitters_build_new_programs(tmp_path):
    conf = ShuffleConf(slot_records=64,
                       metrics_sink=str(tmp_path / "j.jsonl"))
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        a = compute_splitters(_rows(1, parts)[:64, :KW], parts)
        b = compute_splitters(_rows(2, parts)[:64, :KW], parts)
        _job(m, 1, _rows(5, parts), a)
        first = (_built(m, "count"), _built(m, "exec"))
        assert first == (1, 1)
        _job(m, 2, _rows(6, parts), a)      # same splitters: cache hit
        assert (_built(m, "count"), _built(m, "exec")) == first
        _job(m, 3, _rows(7, parts), b)      # new splitters: both miss
        assert (_built(m, "count"), _built(m, "exec")) == (2, 2)


def test_ranged_sorted_read_counts_sort_program(tmp_path):
    conf = ShuffleConf(slot_records=64,
                       metrics_sink=str(tmp_path / "j.jsonl"))
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        _job(m, 1, _rows(8, parts), end_partition=parts - 1)
        _job(m, 2, _rows(9, parts), end_partition=parts - 1)
        assert _built(m, "sort") == 1


def test_streaming_regime_counts_its_programs(tmp_path):
    conf = ShuffleConf(slot_records=8, max_rounds_in_flight=1,
                       queue_depth=2, metrics_sink=str(tmp_path / "j.jsonl"))
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        rows = _rows(10, parts)
        rows[:, 0] = 0                       # one hot partition: rounds > 1
        _job(m, 1, rows)
        for kind in ("prep", "chunk", "tail"):
            assert _built(m, kind) == 1, kind
        assert _built(m, "fold") >= 1
        assert _built(m, "exec") == 0


#: what each read asks of the exchange, and whether it can observe
#: arrival order within a partition
READS = {
    "unordered": ({}, False),
    "key_ordered": ({"sort_key_words": KW}, True),
    "aggregated": ({"aggregator": "sum"}, True),
    "keyed_after": ({"keyed_after": True}, True),
}


@pytest.mark.parametrize("stable_key_sort", [False, True])
@pytest.mark.parametrize("read", sorted(READS))
def test_bucket_sort_is_stable_where_arrival_order_shows(runtime, read,
                                                         stable_key_sort):
    """Only an unordered, unaggregated read without ``stable_key_sort``
    buckets with the unstable sort; every other read builds the stable
    one, and each dispatch counts which it ran."""
    from sparkrdma_tpu.obs.metrics import MetricsRegistry

    kwargs, observable = READS[read]
    stable = observable or stable_key_sort
    reg = MetricsRegistry(enabled=True)
    ex = ShuffleExchange(runtime.mesh, runtime.axis_name,
                         ShuffleConf(stable_key_sort=stable_key_sort),
                         metrics=reg)
    parts = runtime.num_partitions
    x = runtime.shard_records(_rows(12, parts))
    part = modulo_partitioner(parts)
    plan = ex.plan(x, part)
    for _ in range(2):
        ex.exchange(x, part, plan, **kwargs)
    (fn,) = ex._exec_cache.values()
    sorts = [ln for ln in fn.lower(x).compile().as_text().splitlines()
             if re.search(r"\bsort\(", ln) and "sr_bucket/" in ln]
    assert sorts and all(("is_stable=true" in ln) == stable
                         for ln in sorts), sorts
    counts = {k: reg.counter(f"exchange.bucket_sort.{k}").value
              for k in ("stable", "unstable")}
    assert counts == ({"stable": 2, "unstable": 0} if stable
                      else {"stable": 0, "unstable": 2})


@pytest.mark.parametrize("job,kind", [
    ("repartition", "unstable"),
    ("streaming", "unstable"),
    ("ranged_key_ordered", "stable"),
])
def test_manager_jobs_count_their_bucket_sort(tmp_path, job, kind):
    """Whole jobs through the manager: a repartition counts the unstable
    sort once a job, in either regime; a ranged key-ordered read sorts
    after the exchange, so its map side stays stable."""
    streaming = job == "streaming"
    conf = ShuffleConf(slot_records=8 if streaming else 64,
                       max_rounds_in_flight=1 if streaming else 8,
                       metrics_sink=str(tmp_path / "j.jsonl"))
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        rows = _rows(13, parts)
        if streaming:
            rows[:, 0] = 0                   # one hot partition: rounds > 1
        end = parts - 1 if job == "ranged_key_ordered" else None
        for sid in (1, 2):
            _job(m, sid, rows, end_partition=end)
        if streaming:
            assert _built(m, "prep") == 1
        counts = {k: m.metrics.counter(f"exchange.bucket_sort.{k}").value
                  for k in ("stable", "unstable")}
    assert counts[kind] == 2 and sum(counts.values()) == 2, counts


#: reads of a hash-partitioned job: the reader's arguments, the conf,
#: whether every key is one (a hot partition), and whether the map side
#: buckets on the record's hash
HASH_READS = {
    "unordered": ({}, {}, False, True),
    "streaming": ({}, {"slot_records": 8, "max_rounds_in_flight": 1},
                  True, True),
    "key_ordered": ({"key_ordering": True}, {}, False, False),
    "reduce_by_key": ({"aggregator": "sum"}, {}, False, False),
    "filtered": ({"row_filter": _keep_even}, {}, False, False),
    "split": ({}, {"slot_records": 8, "max_rounds": 1}, True, False),
}


@pytest.mark.parametrize("read", sorted(HASH_READS))
def test_manager_jobs_count_hash_keyed_bucket(tmp_path, read):
    """An unordered hash repartition buckets on the record's hash and
    counts ``exchange.bucket_sort.hash_keyed`` once a job, in either
    regime; a key-ordered read, an aggregation, a filtered read and a
    skew-split partitioner keep their pid-keyed sort and count none."""
    reader_kw, conf_kw, hot, keyed = HASH_READS[read]
    conf = ShuffleConf(**{"slot_records": 64,
                          "metrics_sink": str(tmp_path / "j.jsonl"),
                          **conf_kw})
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        rows = _rows(14, parts)
        if hot:
            rows[:, :KW] = 7                 # one hot partition: rounds > 1
        x = m.runtime.shard_records(rows)
        for sid in (1, 2):
            h = m.register_shuffle(sid, parts, hash_partitioner(parts, KW))
            plan = m.get_writer(h).write(x).stop(True)
            assert (plan.split_factor > 1) == (read == "split")
            assert (plan.num_rounds > 1) == (read == "streaming")
            out, totals = m.get_reader(h, **reader_kw).read()
            jax.block_until_ready((out, totals))
            m.unregister_shuffle(sid)
        counts = {k: m.metrics.counter(f"exchange.bucket_sort.{k}").value
                  for k in ("stable", "unstable", "hash_keyed")}
    assert counts["hash_keyed"] == (2 if keyed else 0), counts
    assert counts["stable"] + counts["unstable"] == 2, counts
    if keyed:
        assert counts["unstable"] == 2, counts


#: each cell's sort form: its conf, partitions, key-sort words, the
#: phase its sort runs in, and the operand types of that sort as the
#: chip's trace names them. TeraSort's tight tail sorts 3 key words
#: and the wide form's index; repartition(256)'s unordered bucket sort
#: carries the hash order key in place of the last key word, and the
#: first: no partition id, no index.
CELL_FORMS = {
    "terasort": (dict(key_words=3, val_words=22, pack_sort_min_payload=0,
                      wide_sort_ride_words=0), 1, 3, "sr_sort_keys",
                 "(u32[{n}], u32[{n}], u32[{n}], s32[{n}])"),
    "repartition": (dict(key_words=2, val_words=0), 16, 0, "sr_bucket",
                    "(u32[{n}], u32[{n}])"),
}


@pytest.mark.parametrize("cell", sorted(CELL_FORMS))
def test_cell_sort_forms_pinned(runtime, cell):
    """The benchmark cells' fused programs on one device, at a small N:
    the sort keeps the operand types the cell's trace reads, and a
    wide placement gathers all 22 payload words."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    conf_kw, parts, skw, scope, form = CELL_FORMS[cell]
    n = 1 << 12
    conf = ShuffleConf(slot_records=n, geometry_classes="fine", **conf_kw)
    w = conf.record_words
    mesh = Mesh(np.asarray(runtime.mesh.devices).reshape(-1)[:1], ("s",))
    ex = ShuffleExchange(mesh, "s", conf)
    part = (range_partitioner(np.zeros((0, 3), np.uint32), 3)
            if parts == 1 else hash_partitioner(parts))
    fn = ex._build_exec(parts, n, 1, n, w, part, sort_key_words=skw,
                        tight_out=parts == 1, stable_buckets=False)
    x = jax.ShapeDtypeStruct((w, n), np.uint32,
                             sharding=NamedSharding(mesh, P(None, "s")))
    hlo = re.sub(r"\{[0-9,]*\}", "", fn.lower(x).compile().as_text())
    sorts = [ln for ln in hlo.splitlines()
             if re.search(r"\bsort\(", ln) and f"/{scope}/" in ln]
    assert len(sorts) == 1 and f"{form.format(n=n)} sort(" in sorts[0], \
        sorts
    if parts == 1:
        assert re.search(rf"u32\[{n},22\] \w+\(.*/sr_sort_gather/", hlo)


#: host spans of one range-partitioned job, and the span each one must
#: lie inside
NESTED = {
    "shuffle:plan/count": "shuffle:plan",
    "shuffle:plan/geometry": "shuffle:plan",
    "shuffle:exchange/program": "shuffle:exchange",
    "shuffle:exchange/dispatch": "shuffle:exchange",
    "shuffle:exchange/buffers": "shuffle:exchange/dispatch",
}
TOP = ("shuffle:splitters", "shuffle:plan", "shuffle:plan/publish",
       "shuffle:exchange", "shuffle:read/barrier", "shuffle:unregister")


def test_profiler_trace_holds_host_phases(tmp_path):
    from perfbench.trace_reduce import find_xplane, host_spans

    conf = ShuffleConf(slot_records=64)
    with ShuffleManager(MeshRuntime(conf), conf) as m:
        parts = m.runtime.num_partitions
        rows = _rows(11, parts)
        x = m.runtime.shard_records(rows)
        sampler = make_sampler(m.runtime.mesh, m.runtime.axis_name, KW, 8)
        _job(m, 1, rows, compute_splitters(np.asarray(sampler(x)), parts))
        jax.profiler.start_trace(str(tmp_path))
        try:
            _job(m, 2, rows, compute_splitters(np.asarray(sampler(x)),
                                               parts))
        finally:
            jax.profiler.stop_trace()
    spans = host_spans(jax.profiler.ProfileData.from_file(
        find_xplane(str(tmp_path))))
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    for name in TOP + tuple(NESTED):
        assert name in by_name, name
    for child, parent in NESTED.items():
        for s, e in by_name[child]:
            assert any(ps <= s and e <= pe for ps, pe in by_name[parent]), \
                (child, parent)
