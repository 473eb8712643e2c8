"""PageRank — the iterative edge-shuffle workload (BASELINE.md config 5).

GraphX PageRank on Spark shuffles every edge's contribution from the
source-vertex partition to the destination-vertex partition each
iteration — the multi-round all-to-all the reference accelerates.

TPU-native layout: vertex v is owned by device ``v % mesh`` (round-robin,
matching the exchange's partition placement); edges live with their source
owner. Each iteration builds contribution records (key = dst vertex,
payload = float32 bits of rank[src]/outdeg[src]), runs the slotted
exchange, combines by key in HBM, and scatters the sums into the owner's
dense rank slice.

The per-iteration shuffle is a map-side-combined ``reduce_by_key``: the
exchange carries ``aggregator="sum"``, so the PRE-exchange combine pass
(exchange/protocol.py §map-side combine) folds same-destination-vertex
contributions on the source device before bucketing whenever the gate's
sampled duplicate-ratio clears the threshold — a power-law graph ships
one record per (device, dst) instead of one per edge. ``map_side_combine``
forces the gate for benchmarking ("on"/"off"); the default defers to the
runtime conf ("auto" gates on the measured ratio).

The exchange *plan* is computed once and reused for every iteration: the
graph is static, so the counts matrix never changes — the same observation
that lets the reference cache RdmaMapTaskOutput tables across fetches
instead of re-reading them (SURVEY.md §3.3 "cached").
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
class PageRankResult:
    num_vertices: int
    num_edges: int
    iterations: int
    ranks: np.ndarray           # [V] final ranks, host-side
    total_s: float
    per_iter_s: float
    verified: Optional[bool] = None


def _pad_to_mesh(n: int, mesh: int) -> int:
    return ((n + mesh - 1) // mesh) * mesh


def run_pagerank(
    runtime: MeshRuntime,
    edges: np.ndarray,            # int [E, 2] (src, dst)
    num_vertices: int,
    iterations: int = 10,
    damping: float = 0.85,
    verify: bool = True,
    slot_records: Optional[int] = None,
    map_side_combine: Optional[str] = None,
) -> PageRankResult:
    mesh = runtime.num_partitions
    ax = runtime.axis_name
    conf = runtime.conf
    if slot_records is not None:
        conf = conf.replace(slot_records=slot_records)
    if map_side_combine is not None:
        conf = conf.replace(map_side_combine=map_side_combine)
    ex = ShuffleExchange(runtime.mesh, ax, conf)
    part = modulo_partitioner(mesh, key_word=1)  # dst vertex owner, lo word

    edges = np.asarray(edges, dtype=np.int64)
    e = edges.shape[0]
    v = num_vertices
    vpad = _pad_to_mesh(v, mesh)
    vper = vpad // mesh

    outdeg = np.bincount(edges[:, 0], minlength=v).astype(np.float32)
    outdeg = np.maximum(outdeg, 1.0)  # dangling vertices contribute nothing

    # edge records sharded by source owner (src % mesh), grouped per device
    order = np.argsort(edges[:, 0] % mesh, kind="stable")
    edges_by_owner = edges[order]
    counts_per_dev = np.bincount(edges[:, 0] % mesh, minlength=mesh)
    epad = _pad_to_mesh(int(counts_per_dev.max()), 1)
    # per-device padded edge table [mesh, epad, 2]; padding uses src=dst=0
    # with a zero-contribution mask
    etab = np.zeros((mesh, epad, 2), dtype=np.int64)
    emask = np.zeros((mesh, epad), dtype=bool)
    off = 0
    for d in range(mesh):
        k = int(counts_per_dev[d])
        etab[d, :k] = edges_by_owner[off:off + k]
        emask[d, :k] = True
        off += k

    w = conf.record_words
    if w < 3 or conf.key_words != 2:
        # the record layout below hardcodes key words [0, 1] and payload
        # word 2; the fused "sum" aggregator groups by conf.key_words, so
        # any other key geometry would combine on the wrong words
        raise ValueError("pagerank needs key_words == 2 and "
                         "record_words >= 3 (2 key + 1 payload)")

    # static record keys: [hi=0, lo=dst]; payload word 2 = rank contribution
    base = np.zeros((mesh * epad, w), dtype=np.uint32)
    base[:, 1] = etab[:, :, 1].reshape(-1).astype(np.uint32)
    base_global = runtime.shard_records(base)   # columnar [w, mesh*epad]

    # plan once on the static keys (counts depend only on dst)
    # padding rows go to partition dst=0's owner; they carry zero payload
    plan = ex.plan(base_global, part, mesh)

    # per-device static table: each edge's src index into the owner slice
    src_idx = runtime.shard_rows(
        (etab[:, :, 0].reshape(-1, 1) // mesh).astype(np.int32))
    emask_global = runtime.shard_rows(emask.reshape(-1, 1))
    outdeg_pad = np.ones((vpad,), np.float32)
    outdeg_pad[:v] = outdeg
    # owner layout: device d holds vertices d, d+mesh, ... -> [mesh, vper]
    outdeg_owner = runtime.shard_rows(
        outdeg_pad.reshape(vper, mesh).T.reshape(mesh * vper, 1))

    ranks0 = np.full((vpad,), 1.0 / v, np.float32)
    ranks0[v:] = 0.0
    ranks_owner = runtime.shard_rows(
        ranks0.reshape(vper, mesh).T.reshape(mesh * vper, 1))

    out_cap = plan.out_capacity

    def build_records(ranks_local, base_local, srcidx_local, emask_local,
                      outdeg_local):
        # contribution = rank[src]/outdeg[src] for local edges
        # base_local: columnar [w, epad]
        r = jnp.take(ranks_local[:, 0], srcidx_local[:, 0], axis=0)
        dg = jnp.take(outdeg_local[:, 0], srcidx_local[:, 0], axis=0)
        contrib = jnp.where(emask_local[:, 0], r / dg, 0.0)
        payload = jax.lax.bitcast_convert_type(contrib, jnp.uint32)
        return base_local.at[2].set(payload)

    def update_ranks(received, total, outdeg_local):
        # received is already combined by dst key (the exchange fuses the
        # "sum" aggregator — the reader-level Aggregator stage); scatter
        # the per-key sums into the owner's dense rank slice.
        # received: columnar [w, out_cap], total[0] = unique keys
        dst = received[1].astype(jnp.int32)
        sums = jax.lax.bitcast_convert_type(received[2], jnp.float32)
        live = jnp.arange(out_cap) < total[0]
        idx = jnp.where(live, dst // mesh, vper)
        acc = jnp.zeros((vper,), jnp.float32).at[idx].add(
            jnp.where(live, sums, 0.0), mode="drop")
        new = (1.0 - damping) / v + damping * acc
        # zero padding vertices (id >= v)
        dev = jax.lax.axis_index(ax)
        vid = jnp.arange(vper) * mesh + dev
        new = jnp.where(vid < v, new, 0.0)
        del outdeg_local
        return new[:, None]

    build_fn = jax.jit(shard_map(
        build_records, mesh=runtime.mesh,
        in_specs=(P(ax), P(None, ax), P(ax), P(ax), P(ax)),
        out_specs=P(None, ax),
    ))
    update_fn = jax.jit(shard_map(
        update_ranks, mesh=runtime.mesh,
        in_specs=(P(None, ax), P(ax), P(ax)),
        out_specs=P(ax),
    ))

    t0 = time.perf_counter()
    ranks = ranks_owner
    for it in range(iterations):
        # job tracing: each BSP iteration is one "rank_update" stage,
        # attempt = iteration index (no-op outside ``manager.job(...)``;
        # this path runs a journal-less ShuffleExchange, so stage
        # wall-clocks come from the JobTrace clock, not spans)
        with _trace.stage("rank_update", attempt=it):
            records = build_fn(ranks, base_global, src_idx, emask_global,
                               outdeg_owner)
            out, totals, _ = ex.exchange(records, part, plan, mesh,
                                         aggregator="sum",
                                         float_payload=True)
            ranks = update_fn(out, totals, outdeg_owner)
            # Per-iteration barrier: each shuffle iteration is a Spark
            # stage boundary (BSP). Also keeps the async dispatch queue
            # shallow — on forced-host CPU meshes, piling up collective
            # programs can starve XLA's single-core rendezvous scheduler
            # — and makes the timing honest on backends where
            # block_until_ready is unreliable.
            barrier(ranks)
    total_s = time.perf_counter() - t0

    # owner layout [mesh*vper] -> dense [v]
    r_np = np.asarray(ranks)[:, 0].reshape(mesh, vper).T.reshape(-1)[:v]

    verified = None
    if verify:
        ref = _numpy_pagerank(edges, v, iterations, damping)
        verified = bool(np.allclose(r_np, ref, rtol=1e-4, atol=1e-7))
    return PageRankResult(
        num_vertices=v, num_edges=e, iterations=iterations, ranks=r_np,
        total_s=total_s, per_iter_s=total_s / max(iterations, 1),
        verified=verified,
    )


def _numpy_pagerank(edges: np.ndarray, v: int, iterations: int,
                    damping: float) -> np.ndarray:
    outdeg = np.bincount(edges[:, 0], minlength=v).astype(np.float64)
    outdeg = np.maximum(outdeg, 1.0)
    r = np.full(v, 1.0 / v)
    for _ in range(iterations):
        contrib = r[edges[:, 0]] / outdeg[edges[:, 0]]
        acc = np.zeros(v)
        np.add.at(acc, edges[:, 1], contrib)
        r = (1 - damping) / v + damping * acc
    return r.astype(np.float32)


__all__ = ["run_pagerank", "PageRankResult"]
