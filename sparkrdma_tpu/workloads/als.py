"""ALS collaborative filtering — the iterative factor-shuffle workload
(BASELINE.md config 4, "MLlib ALS on MovieLens-20M").

Spark MLlib's ALS is the reference's most shuffle-intensive ML workload:
every half-iteration shuffles factor vectors from the blocks that own them
to the blocks that need them (its InBlock/OutBlock structure), then solves
per-entity normal equations. The plugin accelerates exactly that factor
shuffle; everything else is local linear algebra.

TPU-native layout mirroring that structure:

- user ``u`` is owned by device ``u % mesh``; item ``i`` by ``i % mesh``
  (round-robin, matching the exchange's partition placement);
- ratings are held twice, statically: sharded by item owner (for the
  user-update half-step) and by user owner (for the item-update half-step)
  — the OutBlock analogue;
- each half-step builds PARTIAL NORMAL-EQUATION records ``key=(0,
  dst_entity)``, ``payload = [r·f (k words), upper-tri(f f^T)
  (k(k+1)/2 words)]`` on the factor's owner device and runs the slotted
  exchange as a map-side-combined ``reduce_by_key``: ``aggregator="sum"``
  engages both the PRE-exchange combine pass (same-destination partials
  fold on the source device before bucketing, gated on the sampled
  duplicate ratio) and the reader's fused aggregator, so the receiving
  device gets ONE summed ``(A, b)`` per owned entity and just solves the
  batched k×k systems (``jnp.linalg.solve`` — MXU-batched, no per-entity
  loop). Shipping partials instead of raw factors is what makes the
  shuffle combinable at all: factor vectors can't be summed, their
  normal-equation contributions can.

Both exchange *plans* are computed once and reused every iteration: the
rating graph is static so the counts matrices never change — the same
caching the reference applies to RdmaMapTaskOutput tables (SURVEY.md §3.3
"cached").
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from sparkrdma_tpu.exchange.partitioners import modulo_partitioner
from sparkrdma_tpu.exchange.protocol import ShuffleExchange
from sparkrdma_tpu.obs import trace as _trace
from sparkrdma_tpu.runtime.mesh import MeshRuntime
from sparkrdma_tpu.utils.stats import barrier


@dataclasses.dataclass
class ALSResult:
    num_users: int
    num_items: int
    num_ratings: int
    rank: int
    iterations: int
    user_factors: np.ndarray      # [U, k]
    item_factors: np.ndarray      # [I, k]
    rmse: float
    total_s: float
    per_iter_s: float
    verified: Optional[bool] = None


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _owner_layout(x: np.ndarray, mesh: int) -> np.ndarray:
    """Dense [Npad, k] -> owner-major [mesh * per, k] (device d gets rows
    d, d+mesh, ...) suitable for ``MeshRuntime.shard_rows``."""
    npad, k = x.shape
    per = npad // mesh
    return x.reshape(per, mesh, k).transpose(1, 0, 2).reshape(mesh * per, k)


def _from_owner_layout(x: np.ndarray, mesh: int, n: int) -> np.ndarray:
    per = x.shape[0] // mesh
    return x.reshape(mesh, per, -1).transpose(1, 0, 2).reshape(mesh * per,
                                                               -1)[:n]


def _edge_tables(ratings: np.ndarray, owner_col: int, mesh: int):
    """Group rating triples by owner of ``owner_col`` into per-device padded
    tables. Returns (table [mesh, epad, 3] float64-safe int/float mix as
    (u, i, r) columns, mask [mesh, epad])."""
    owner = ratings[:, owner_col].astype(np.int64) % mesh
    order = np.argsort(owner, kind="stable")
    r_sorted = ratings[order]
    counts = np.bincount(owner, minlength=mesh)
    epad = max(1, int(counts.max()))
    tab = np.zeros((mesh, epad, 3), dtype=np.float64)
    mask = np.zeros((mesh, epad), dtype=bool)
    off = 0
    for d in range(mesh):
        c = int(counts[d])
        tab[d, :c] = r_sorted[off:off + c]
        mask[d, :c] = True
        off += c
    return tab, mask


def _make_build_fn(runtime: MeshRuntime, k: int, w: int):
    """records = static base with payload <- the edge's PARTIAL normal
    equations ``[r·f (k), upper-tri(f f^T) (k(k+1)/2)]`` — an associative
    sum payload, so the map-side combine pass and the reader's fused
    ``sum`` aggregator can both fold same-destination records."""
    ax = runtime.axis_name
    tri_i, tri_j = (jnp.asarray(x) for x in np.triu_indices(k))

    def build(factors_local, base_local, srcidx_local, rating_local,
              mask_local):
        # base_local: columnar [w, E]
        f = jnp.take(factors_local, srcidx_local[:, 0], axis=0)  # [E, k]
        f = jnp.where(mask_local, f, 0.0)
        r = jnp.where(mask_local[:, 0], rating_local[:, 0], 0.0)
        b_p = r[:, None] * f                       # [E, k]
        a_p = f[:, tri_i] * f[:, tri_j]            # [E, k(k+1)/2]
        payload = jax.lax.bitcast_convert_type(
            jnp.concatenate([b_p, a_p], axis=1), jnp.uint32)
        return jnp.concatenate([base_local[:2], payload.T], axis=0)

    return jax.jit(shard_map(
        build, mesh=runtime.mesh,
        in_specs=(P(ax), P(None, ax), P(ax), P(ax), P(ax)),
        out_specs=P(None, ax),
    ))


def _make_update_fn(runtime: MeshRuntime, k: int, per: int, out_cap: int,
                    mesh: int, lam: float):
    """Received (already key-summed) partial normal equations -> solved
    factors for locally-owned entities.

    The exchange's combine + fused aggregator already folded ``A`` and
    ``b`` per destination entity, so this just scatters each entity's
    summed partials into the owner slice (mode="drop" for padding),
    unpacks the symmetric upper triangle, and runs one batched
    linalg.solve (maps to MXU-batched triangular solves)."""
    ax = runtime.axis_name
    ntri = k * (k + 1) // 2
    tri_i, tri_j = (jnp.asarray(x) for x in np.triu_indices(k))

    def update(received, total):
        # received: columnar [w, out_cap]
        valid = jnp.arange(out_cap) < total[0]
        dst = received[1].astype(jnp.int32)
        fr = jax.lax.bitcast_convert_type(received[2:2 + k + ntri],
                                          jnp.float32)
        b_rows = jnp.where(valid[None], fr[:k], 0.0).T      # [cap, k]
        a_rows = jnp.where(valid[None], fr[k:], 0.0).T      # [cap, ntri]
        idx = jnp.where(valid, dst // mesh, per)
        b = jnp.zeros((per, k), jnp.float32).at[idx].add(
            b_rows, mode="drop")
        a_tri = jnp.zeros((per, ntri), jnp.float32).at[idx].add(
            a_rows, mode="drop")
        A = jnp.zeros((per, k, k), jnp.float32)
        A = A.at[:, tri_i, tri_j].set(a_tri)
        A = A.at[:, tri_j, tri_i].set(a_tri)   # diagonal rewrites itself
        A = A + lam * jnp.eye(k, dtype=jnp.float32)[None]
        return jnp.linalg.solve(A, b[:, :, None])[:, :, 0]      # [per, k]

    return jax.jit(shard_map(
        update, mesh=runtime.mesh,
        in_specs=(P(None, ax), P(ax)),
        out_specs=P(ax),
    ))


def run_als(
    runtime: MeshRuntime,
    ratings: np.ndarray,          # [N, 3] columns (user, item, rating)
    num_users: int,
    num_items: int,
    rank: int = 8,
    iterations: int = 5,
    lam: float = 0.1,
    seed: int = 0,
    verify: bool = True,
    slot_records: Optional[int] = None,
    map_side_combine: Optional[str] = None,
) -> ALSResult:
    """Run ALS with a per-half-iteration map-side-combined partial-sum
    exchange. ``map_side_combine`` forces the combine gate ("on"/"off")
    for benchmarking; the default defers to the runtime conf ("auto")."""
    mesh = runtime.num_partitions
    conf = runtime.conf.replace(
        val_words=rank + rank * (rank + 1) // 2)
    if slot_records is not None:
        conf = conf.replace(slot_records=slot_records)
    if map_side_combine is not None:
        conf = conf.replace(map_side_combine=map_side_combine)
    ex = ShuffleExchange(runtime.mesh, runtime.axis_name, conf)
    part = modulo_partitioner(mesh, key_word=1)
    w = conf.record_words
    k = rank

    ratings = np.asarray(ratings, dtype=np.float64)
    upad, ipad = _pad_to(num_users, mesh), _pad_to(num_items, mesh)
    uper, iper = upad // mesh, ipad // mesh

    # --- static structures per half-step direction ---------------------
    # user step: records built on ITEM owners, dst key = user id
    itab, imask = _edge_tables(ratings, owner_col=1, mesh=mesh)
    # item step: records built on USER owners, dst key = item id
    utab, umask = _edge_tables(ratings, owner_col=0, mesh=mesh)

    def prep(tab, mask, dst_col, src_col):
        e = tab.shape[1]
        base = np.zeros((mesh * e, w), dtype=np.uint32)
        base[:, 1] = tab[:, :, dst_col].reshape(-1).astype(np.uint32)
        srcidx = (tab[:, :, src_col].reshape(-1).astype(np.int64)
                  // mesh).astype(np.int32)
        return (runtime.shard_records(base),    # columnar [w, mesh*e]
                runtime.shard_rows(srcidx[:, None]),
                runtime.shard_rows(
                    tab[:, :, 2].reshape(-1, 1).astype(np.float32)),
                runtime.shard_rows(mask.reshape(-1, 1)))

    ubase, usrc, urate, umask_g = prep(itab, imask, dst_col=0, src_col=1)
    ibase, isrc, irate, imask_g = prep(utab, umask, dst_col=1, src_col=0)

    uplan = ex.plan(ubase, part, mesh)
    iplan = ex.plan(ibase, part, mesh)

    build_fn = _make_build_fn(runtime, k, w)
    user_update = _make_update_fn(runtime, k, uper, uplan.out_capacity,
                                  mesh, lam)
    item_update = _make_update_fn(runtime, k, iper, iplan.out_capacity,
                                  mesh, lam)

    rng = np.random.default_rng(seed)
    v0 = np.zeros((ipad, k), np.float32)
    v0[:num_items] = rng.standard_normal((num_items, k),
                                         dtype=np.float32) * 0.1
    V = runtime.shard_rows(_owner_layout(v0, mesh))
    U = runtime.shard_rows(np.zeros((mesh * uper, k), np.float32))

    t0 = time.perf_counter()
    for it in range(iterations):
        # Each ALS half-step is one job-trace stage (attempt = iteration
        # index; a no-op outside ``manager.job(...)`` — this path runs a
        # journal-less ShuffleExchange so stage wall-clocks come from the
        # JobTrace clock, not spans).
        with _trace.stage("update_users", attempt=it):
            # user half-step: shuffle item-side partial sums to user
            # owners
            rec = build_fn(V, ubase, usrc, urate, umask_g)
            out, totals, _ = ex.exchange(rec, part, uplan, mesh,
                                         aggregator="sum",
                                         float_payload=True)
            U = user_update(out, totals)
        with _trace.stage("update_items", attempt=it):
            # item half-step: shuffle user-side partial sums to item
            # owners
            rec = build_fn(U, ibase, isrc, irate, imask_g)
            out, totals, _ = ex.exchange(rec, part, iplan, mesh,
                                         aggregator="sum",
                                         float_payload=True)
            # Stage barrier per half-iteration pair (see pagerank.py
            # note).
            V = item_update(out, totals)
            barrier(V)
    total_s = time.perf_counter() - t0

    u_np = _from_owner_layout(np.asarray(U), mesh, num_users)
    v_np = _from_owner_layout(np.asarray(V), mesh, num_items)
    uu = ratings[:, 0].astype(np.int64)
    ii = ratings[:, 1].astype(np.int64)
    pred = np.sum(u_np[uu] * v_np[ii], axis=1)
    rmse = float(np.sqrt(np.mean((pred - ratings[:, 2]) ** 2)))

    verified = None
    if verify:
        u_ref, v_ref = _numpy_als(ratings, num_users, num_items, k,
                                  iterations, lam, v0[:num_items])
        verified = bool(
            np.allclose(u_np, u_ref, rtol=2e-3, atol=2e-4)
            and np.allclose(v_np, v_ref, rtol=2e-3, atol=2e-4)
        )
    return ALSResult(
        num_users=num_users, num_items=num_items,
        num_ratings=ratings.shape[0], rank=k, iterations=iterations,
        user_factors=u_np, item_factors=v_np, rmse=rmse, total_s=total_s,
        per_iter_s=total_s / max(iterations, 1), verified=verified,
    )


def _numpy_als(ratings, num_users, num_items, k, iterations, lam, v0):
    """Float32 host reference with identical update math."""
    uu = ratings[:, 0].astype(np.int64)
    ii = ratings[:, 1].astype(np.int64)
    rr = ratings[:, 2].astype(np.float32)
    V = v0.astype(np.float32).copy()
    U = np.zeros((num_users, k), np.float32)

    def solve_side(n_dst, dst, src_f, r):
        A = np.zeros((n_dst, k, k), np.float32)
        b = np.zeros((n_dst, k), np.float32)
        f = src_f
        np.add.at(A, dst, f[:, :, None] * f[:, None, :])
        np.add.at(b, dst, r[:, None] * f)
        A += lam * np.eye(k, dtype=np.float32)[None]
        return np.linalg.solve(A, b[:, :, None])[:, :, 0]

    for _ in range(iterations):
        U = solve_side(num_users, uu, V[ii], rr)
        V = solve_side(num_items, ii, U[uu], rr)
    return U, V


__all__ = ["run_als", "ALSResult"]
