"""TPC-DS-shaped multi-join queries — BASELINE.md config 3, PLANNER-run.

The benchmark queries are shuffle-bound because every join first
co-partitions both sides across the cluster, and the query ends in a
grouped aggregate — q64 chains fact ⋈ dim ⋈ dim ... GROUP BY. These
workloads are written NAIVELY against the query planner
(:mod:`sparkrdma_tpu.plan`) — join, filter, select, reduce in SQL
order — and the optimizer's rewrites recover what the old hand-tuned
SPI versions hard-coded:

  pushdown      the post-join ``key != 0`` filter is DISCOVERED and
                fused into the final exchange's ``row_filter`` (and
                sunk below layout-preserving exchanges), so dead rows
                never occupy a wire slot;
  broadcast     dimension sides under ``plan_broadcast_records``
                replicate to every device and the co-partition
                exchanges are skipped entirely;
  reuse         exchanges with identical fingerprints adopt a prior
                run's segments instead of re-shuffling;
  overlap       deferred host tables encode in the background while
                an earlier exchange drains.

With every ``plan_*`` knob off the same plans replay the naive
dataflow bit-identically — that on/off identity is pinned in
tests/test_plan.py.

TPU-native design points: dimension joins are primary-key lookups, so
the join output has the FACT's shape (fixed — no variable-length row
stream, the XLA-hostile thing); padding rows carry key 0 end-to-end
(real keys are 1-based) and aggregate into a discarded null group
instead of needing compaction; exchange outputs stay device-resident
columnar batches between stages — bytes never leave HBM.

q64 record layout (W=4): [key_hi=0, key_lo, payload0, payload1].
  fact:            key=item_key,  payload=(store_key, value)
  after join 1:    key=store_key, payload=(category, value)
  after join 2:    key=category,  payload=(region attr, value)

The star-schema suite (:func:`run_star_suite`) needs ``val_words=4``
(W=6) and chains three dimension joins; see its docstring for layout.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from sparkrdma_tpu.api.shuffle_manager import ShuffleManager
from sparkrdma_tpu.obs import trace as _trace
from sparkrdma_tpu.utils.stats import barrier


@dataclasses.dataclass
class QueryResult:
    fact_rows: int
    groups: int                  # distinct non-null categories in output
    total_value: int             # sum over qualifying fact rows
    shuffle_s: float
    verified: Optional[bool] = None


_lookup_cache: "weakref.WeakKeyDictionary[ShuffleManager, Dict[Tuple, Callable]]" \
    = weakref.WeakKeyDictionary()


def _drop_null_key(records):
    """Pushed-down region predicate for exchange 3: stage 2 marked
    non-qualifying rows with the null key 0, so dropping key-0 rows at
    the exchange ships only qualifying bytes. Output is unchanged —
    the key-0 group was discarded host-side anyway."""
    return records[1] != jnp.uint32(0)


_drop_null_key.cache_key = ("tpcds_drop_null",)


def run_q64_shape(
    manager: ShuffleManager,
    fact_rows_per_device: int = 256,
    n_items: int = 256,
    n_stores: int = 64,
    n_categories: int = 16,
    region_cutoff: int = 3,
    n_regions: int = 8,
    seed: int = 0,
    shuffle_ids: Tuple[int, int, int, int, int] = (40, 41, 42, 43, 44),
    verify: bool = True,
    executor=None,
) -> QueryResult:
    """Run the q64 shape THROUGH THE QUERY PLANNER and verify grouped
    sums against numpy.

    The query is written naively — join item, join the region-qualified
    stores, then a post-join ``key != 0`` filter, then the grouped sum
    — and the planner's rewrites do what the old hand-tuned SPI
    version hard-coded: the null-key filter is DISCOVERED by the
    pushdown pass and fused into the group_agg exchange's
    ``row_filter``; the dimension sides broadcast when small enough
    (skipping the co-partition exchanges entirely); the combine-gate
    decision is hoisted to the plan. With every ``plan_*`` knob off the
    same plan replays the naive 3-exchange dataflow bit-identically.

    ``shuffle_ids`` is vestigial (the planner draws Dataset-layer ids);
    kept for signature compatibility. Pass ``executor`` to share a
    :class:`~sparkrdma_tpu.plan.executor.PlanExecutor`'s exchange-reuse
    memo across queries.
    """
    del shuffle_ids  # planner-drawn ids; accepted for compatibility
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.plan import LogicalPlan, PlanExecutor

    rt = manager.runtime
    mesh = rt.num_partitions
    rng = np.random.default_rng(seed)
    nf = mesh * fact_rows_per_device

    # --- tables (1-based keys; 0 is the null/padding key) --------------
    fact = np.zeros((nf, 4), dtype=np.uint32)
    fact[:, 1] = rng.integers(1, n_items + 1, size=nf)        # item_key
    fact[:, 2] = rng.integers(1, n_stores + 1, size=nf)       # store_key
    fact[:, 3] = rng.integers(1, 100, size=nf)                # value

    item = np.zeros((max(mesh, n_items), 4), dtype=np.uint32)
    item[:n_items, 1] = np.arange(1, n_items + 1)             # PK
    item[:n_items, 2] = rng.integers(1, n_categories + 1, size=n_items)

    store = np.zeros((max(mesh, n_stores), 4), dtype=np.uint32)
    store[:n_stores, 1] = np.arange(1, n_stores + 1)          # PK
    store[:n_stores, 2] = rng.integers(0, n_regions, size=n_stores)

    def region_pred(r, _c=region_cutoff):
        return r[2] < jnp.uint32(_c)

    region_pred.cache_key = ("tpcds_region", region_cutoff)

    t0 = time.perf_counter()
    fact_p = LogicalPlan.dataset(Dataset.from_host_rows(manager, fact),
                                 name="tpcds_fact")
    item_p = LogicalPlan.dataset(Dataset.from_host_rows(manager, item),
                                 name="tpcds_item")
    store_p = LogicalPlan.dataset(Dataset.from_host_rows(manager, store),
                                  name="tpcds_store")
    # WHERE region < cutoff lives on the DIM side: non-qualifying
    # stores leave the dim table, so their fact rows come out of the
    # store join unmatched (key 0) and the naive null-key filter below
    # — the one the pushdown rewrite discovers — drops them pre-wire.
    q = (fact_p
         .join(item_p, key_from=0, attr_to=0, stage="item_join")
         .join(store_p.filter(region_pred), key_from=0, attr_to=0,
               stage="store_join")
         .filter(_drop_null_key)
         .reduce_by_key("sum", stage="group_agg"))
    ex = executor or PlanExecutor(manager)
    out = ex.run(q, job_name="tpcds_q64")
    barrier(out.records)
    shuffle_s = time.perf_counter() - t0

    # after join 2: key = category, payload0 = region attr, payload1 =
    # value — the grouped sums ride payload1
    groups: Dict[int, int] = {}
    for row in out.to_host_rows():
        key = int(row[1])
        if key:                                  # drop the null group
            groups[key] = groups.get(key, 0) + int(row[3])

    verified = None
    if verify:
        cat_of = {int(item[i, 1]): int(item[i, 2]) for i in range(n_items)}
        reg_of = {int(store[i, 1]): int(store[i, 2])
                  for i in range(n_stores)}
        # WHERE-before-GROUP-BY reference: a category with no
        # qualifying rows has no group at all (the pushed-down filter
        # drops its rows pre-wire; the old masking implementation
        # shipped them as value-0 rows and emitted empty groups)
        ref: Dict[int, int] = {}
        for i in range(nf):
            if reg_of[int(fact[i, 2])] < region_cutoff:
                cat = cat_of[int(fact[i, 1])]
                ref[cat] = ref.get(cat, 0) + int(fact[i, 3])
        verified = groups == ref

    return QueryResult(
        fact_rows=nf,
        groups=len(groups),
        total_value=sum(groups.values()),
        shuffle_s=shuffle_s,
        verified=verified,
    )


@dataclasses.dataclass
class Q95Result:
    sales_rows: int
    qualifying: int
    net_sum: float
    shuffle_s: float
    verified: Optional[bool] = None


def run_q95_shape(
    manager: ShuffleManager,
    sales_rows_per_device: int = 256,
    return_rows_per_device: int = 64,
    n_orders: int = 512,
    n_warehouses: int = 8,
    return_order_offset: int = 0,
    seed: int = 0,
    shuffle_ids: Tuple[int, int] = (45, 46),
    verify: bool = True,
) -> Q95Result:
    """TPC-DS q95 shape: a self-SEMI-join plus an ANTI-join, both
    requiring co-partitioning, then a global aggregate.

    q95 counts web sales whose order ALSO ships from a different
    warehouse (EXISTS over the same table) and was never returned
    (NOT EXISTS against web_returns). Here: sales(order, warehouse,
    net) and returns(order) are hash-co-partitioned by order key (two
    exchanges through the public SPI); the per-device leg sorts sales by
    (order, warehouse) so EXISTS-different-warehouse reduces to "first
    and last warehouse in my order's run differ" (distinct>=2 iff
    min!=max on a sorted run) and NOT-EXISTS is one searchsorted probe
    into the sorted returns; `psum` folds count and net across the mesh.
    """
    rt = manager.runtime
    mesh = rt.num_partitions
    rng = np.random.default_rng(seed)
    ns = mesh * sales_rows_per_device
    nr = mesh * return_rows_per_device

    sales = np.zeros((ns, 4), dtype=np.uint32)
    sales[:, 1] = rng.integers(1, n_orders + 1, size=ns)      # order key
    sales[:, 2] = rng.integers(0, n_warehouses, size=ns)      # warehouse
    sales[:, 3] = rng.integers(1, 1000, size=ns)              # net paid
    returns = np.zeros((nr, 4), dtype=np.uint32)
    # return_order_offset shifts return keys out of the sales order
    # space (offset >= n_orders = the provably-zero-returns path)
    returns[:, 1] = (rng.integers(1, n_orders + 1, size=nr)
                     + return_order_offset)

    del shuffle_ids  # planner-drawn ids; accepted for compatibility
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.plan import LogicalPlan, PlanExecutor

    ex = PlanExecutor(manager)
    t0 = time.perf_counter()

    outs = []
    # stage 1 under ``manager.job(...)``: both co-partition exchanges,
    # planner-run INLINE (run_inline executes under this explicit stage
    # scope, so the job's two-stage profile is unchanged) — which gives
    # the exchanges fingerprints, reuse eligibility and plan journaling
    with _trace.stage("co_partition"):
        for name, table in (("q95_sales", sales), ("q95_returns",
                                                   returns)):
            ds = ex.run_inline(
                LogicalPlan.dataset(
                    Dataset.from_host_rows(manager, table),
                    name=name).repartition())
            outs.append((ds.records, ds.totals,
                         ds.records.shape[1] // mesh))

    (so, st, sc), (ro, rtot, rc) = outs
    ax = rt.axis_name

    def local(sales_c, s_tot, ret_c, r_tot):
        ns_c, nr_c = s_tot[0], r_tot[0]
        sv = jnp.arange(sc) < ns_c
        rv = jnp.arange(rc) < nr_c
        key = jnp.where(sv, sales_c[1], jnp.uint32(0xFFFFFFFF))
        # sort by (order, warehouse): run min/max warehouse are the ends
        sk, swh, snet, svv = jax.lax.sort(
            (key, sales_c[2], sales_c[3], sv), num_keys=2, is_stable=True)
        lo = jnp.searchsorted(sk, sk, side="left")
        hi = jnp.searchsorted(sk, sk, side="right")
        wmin = jnp.take(swh, lo)
        wmax = jnp.take(swh, jnp.maximum(hi - 1, 0))
        exists_other = (wmin != wmax) & svv
        rkey = jnp.where(rv, ret_c[1], jnp.uint32(0xFFFFFFFF))
        rsorted = jnp.sort(rkey)
        ridx = jnp.minimum(jnp.searchsorted(rsorted, sk), rc - 1)
        returned = (jnp.take(rsorted, ridx) == sk) & svv
        qual = exists_other & ~returned
        count = jnp.sum(qual).astype(jnp.int32)
        net = jnp.sum(jnp.where(qual, snet, 0).astype(jnp.float32))
        return (jax.lax.psum(count, ax)[None],
                jax.lax.psum(net, ax)[None])

    barrier(ro)   # ro is dispatched last: syncing it covers BOTH exchanges
    shuffle_s = time.perf_counter() - t0   # exchanges only, not compile

    # stage 2: the semi/anti probe join over co-partitioned tables
    with _trace.stage("probe_join"):
        cache = _lookup_cache.setdefault(manager, {})
        ckey = ("q95", sc, rc)
        fn = cache.get(ckey)
        if fn is None:
            fn = jax.jit(shard_map(
                local, mesh=rt.mesh,
                in_specs=(P(None, ax), P(ax), P(None, ax), P(ax)),
                out_specs=(P(ax), P(ax)),
            ))
            cache[ckey] = fn
        cnt, net = fn(so, st, ro, rtot)
        count = int(np.asarray(cnt)[0])
        net_sum = float(np.asarray(net)[0])

    verified = None
    if verify:
        wh_by_order: Dict[int, set] = {}
        for i in range(ns):
            wh_by_order.setdefault(int(sales[i, 1]), set()).add(
                int(sales[i, 2]))
        returned_orders = set(int(returns[i, 1]) for i in range(nr))
        ref_cnt, ref_net = 0, 0.0
        for i in range(ns):
            o = int(sales[i, 1])
            if len(wh_by_order[o]) >= 2 and o not in returned_orders:
                ref_cnt += 1
                ref_net += float(sales[i, 3])
        verified = (count == ref_cnt
                    and abs(net_sum - ref_net) <= 1e-6 * max(1.0, ref_net))

    return Q95Result(sales_rows=ns, qualifying=count, net_sum=net_sum,
                     shuffle_s=shuffle_s, verified=verified)


@dataclasses.dataclass
class StarResult:
    """One star-schema suite run: two queries over a shared fact."""

    fact_rows: int
    rev_groups: int              # q_star_rev: qualifying groups
    rev_total: int               # q_star_rev: summed value
    all_groups: int              # q_star_all: all groups
    all_total: int               # q_star_all: summed value
    suite_s: float
    verified: Optional[bool] = None


def _star_tables(mesh: int, fact_rows_per_device: int, scale: int,
                 seed: int):
    """Fact + three dimension tables for the star shape (W=6).

    Fact rows ``[0, d1k, d2k, d3k, value, 0]``; each dim table
    ``[0, pk, attr, 0, 0, 0]`` with 1-based unique PKs and 1-based
    attributes (attr 1 of dim1 becomes the FINAL group key, so it must
    never be the null key 0). Dim row counts are padded up to a mesh
    multiple with key-0 rows (``from_host_rows`` wants N % mesh == 0;
    key 0 never matches a lookup).
    """
    rng = np.random.default_rng(seed)
    nf = mesh * fact_rows_per_device * scale
    n1, n2, n3 = 64 * scale, 32 * scale, 16 * scale
    n_a1 = 8 * scale

    def dim(n_rows: int, n_attr: int):
        n_pad = -(-n_rows // mesh) * mesh
        t = np.zeros((n_pad, 6), dtype=np.uint32)
        t[:n_rows, 1] = np.arange(1, n_rows + 1)          # unique PK
        t[:n_rows, 2] = rng.integers(1, n_attr + 1, size=n_rows)
        return t

    fact = np.zeros((nf, 6), dtype=np.uint32)
    fact[:, 1] = rng.integers(1, n1 + 1, size=nf)         # dim1 key
    fact[:, 2] = rng.integers(1, n2 + 1, size=nf)         # dim2 key
    fact[:, 3] = rng.integers(1, n3 + 1, size=nf)         # dim3 key
    fact[:, 4] = rng.integers(1, 100, size=nf)            # value
    return fact, dim(n1, n_a1), dim(n2, 8), dim(n3, 16)


def _star_pred(r):
    """Naive post-join WHERE: qualifying a2 band, non-null group key.
    Written AFTER the pre-aggregate repartition so the pushdown pass
    has something to sink (and fuse into that exchange's wire side)."""
    return (r[2] < jnp.uint32(5)) & (r[1] != jnp.uint32(0))


_star_pred.cache_key = ("star_rev_band", 5)


def run_star_suite(
    manager: ShuffleManager,
    fact_rows_per_device: int = 128,
    scale: int = 1,
    seed: int = 0,
    executor=None,
    verify: bool = True,
) -> StarResult:
    """Star-schema multi-join suite: two planner-run queries sharing
    one repartitioned fact table — the workload the DAG optimizer's
    four rewrites were built for, all firing in one run:

    - both queries chain three DIMENSION joins off the shared
      ``star_fact`` repartition; the second query's identical fact
      exchange adopts the first's output (``plan.reuse_hits``);
    - the dims are small, so every join BROADCASTS
      (``plan.broadcast_joins``) and the co-partition exchanges vanish;
    - they are deferred host tables, so their encode OVERLAPS the fact
      exchange (``plan.overlapped_stages``);
    - ``q_star_rev`` writes filter + ``select("value")`` naively AFTER
      its pre-aggregate repartition; the pushdown pass SINKS both below
      it (``plan.pushdown_sunk``), so that exchange ships only
      qualifying 3-word rows instead of everything at full width.

    Word layout through the chain (key_words=2, val_words=4 — the
    suite REQUIRES ``conf.val_words == 4``):

      fact:         key=d1k, payload=(d2k, d3k, value, 0)
      after join 1 (key_from=0, attr_to=3): key=d2k, p=(d2k, d3k, value, a1)
      after join 2 (key_from=1, attr_to=0): key=d3k, p=(a2, d3k, value, a1)
      after join 3 (key_from=3, attr_to=1): key=a1,  p=(a2, a3, value, a1)

    so the declared join-3 output schema is (a2, a3, value, a1) and the
    final ``reduce_by_key("sum")`` groups by a1 with the summed value
    riding payload word 2. Both queries verify against numpy; with
    every ``plan_*`` knob off the suite replays the naive dataflow
    bit-identically (pinned in tests/test_plan.py).
    """
    from sparkrdma_tpu.api.dataset import Dataset
    from sparkrdma_tpu.api.serde import RowSchema
    from sparkrdma_tpu.plan import LogicalPlan, PlanExecutor

    if manager.conf.val_words != 4:
        raise ValueError(
            f"run_star_suite needs val_words=4 (W=6) for the 3-join "
            f"chain; manager has val_words={manager.conf.val_words}")
    rt = manager.runtime
    mesh = rt.num_partitions
    fact, dim1, dim2, dim3 = _star_tables(
        mesh, fact_rows_per_device, scale, seed)
    nf = fact.shape[0]

    out_schema = RowSchema([("a2", "uint32"), ("a3", "uint32"),
                            ("value", "uint32"), ("a1", "uint32")])

    t0 = time.perf_counter()
    # deferred dim sources (overlap-eligible); the fact repartition is
    # ONE shared plan handle, so both queries' fact exchanges carry the
    # same fingerprint and the second adopts the first's output
    fact_r = LogicalPlan.dataset(
        Dataset.from_host_rows(manager, fact),
        name=f"star_fact_s{scale}_r{seed}").repartition(stage="fact_part")
    d1 = LogicalPlan.from_host_rows(manager, dim1,
                                    name=f"star_dim1_s{scale}_r{seed}")
    d2 = LogicalPlan.from_host_rows(manager, dim2,
                                    name=f"star_dim2_s{scale}_r{seed}")
    d3 = LogicalPlan.from_host_rows(manager, dim3,
                                    name=f"star_dim3_s{scale}_r{seed}")

    def joined(left: "LogicalPlan") -> "LogicalPlan":
        return (left
                .join(d1, key_from=0, attr_to=3, stage="dim1_join")
                .join(d2, key_from=1, attr_to=0, stage="dim2_join")
                .join(d3, key_from=3, attr_to=1, schema=out_schema,
                      stage="dim3_join"))

    q_rev = (joined(fact_r)
             .repartition(stage="qual_part")
             .filter(_star_pred)
             .select("value")
             .reduce_by_key("sum", stage="star_agg"))
    q_all = joined(fact_r).reduce_by_key("sum", stage="star_agg")

    ex = executor or PlanExecutor(manager)
    rev = ex.run(q_rev, job_name=f"star_rev_s{scale}")
    barrier(rev.records)
    alls = ex.run(q_all, job_name=f"star_all_s{scale}")
    barrier(alls.records)
    suite_s = time.perf_counter() - t0

    def groups_of(ds) -> Dict[int, int]:
        g: Dict[int, int] = {}
        for row in ds.to_host_rows():
            key = int(row[1])
            if key:                          # discard the null group
                g[key] = g.get(key, 0) + int(row[4])
        return g

    rev_g, all_g = groups_of(rev), groups_of(alls)

    verified = None
    if verify:
        a_of = [{int(t[i, 1]): int(t[i, 2]) for i in range(t.shape[0])
                 if t[i, 1]} for t in (dim1, dim2, dim3)]
        ref_rev: Dict[int, int] = {}
        ref_all: Dict[int, int] = {}
        for i in range(nf):
            a1 = a_of[0][int(fact[i, 1])]
            a2 = a_of[1][int(fact[i, 2])]
            v = int(fact[i, 4])
            ref_all[a1] = ref_all.get(a1, 0) + v
            if a2 < 5:
                ref_rev[a1] = ref_rev.get(a1, 0) + v
        verified = rev_g == ref_rev and all_g == ref_all

    return StarResult(
        fact_rows=nf,
        rev_groups=len(rev_g), rev_total=sum(rev_g.values()),
        all_groups=len(all_g), all_total=sum(all_g.values()),
        suite_s=suite_s, verified=verified,
    )


__all__ = ["run_q64_shape", "run_q95_shape", "run_star_suite",
           "QueryResult", "Q95Result", "StarResult"]
