"""Stage-DAG executor: runs an optimized plan on a ShuffleManager.

``PlanExecutor.run`` optimizes the DAG (plan/optimizer.py) under a
``plan_optimize`` trace stage, journals every planner decision as a
``{"kind": "plan"}`` line (schema v13, field set frozen in
:data:`PLAN_FIELDS` and lint-enforced by srlint's plan-schema-sync
rule), then walks the DAG bottom-up executing each node through the
Dataset verb layer. One executor can run a whole query SUITE: its
exchange-reuse memo (fingerprint -> exchange output) spans ``run``
calls, which is what turns two queries sharing a co-partitioned fact
table into one exchange plus one adoption.

Execution semantics per rewrite gate:

- ``plan_pushdown`` OFF: every filter/select node materializes eagerly
  (``_materialize_pending``) — filtered rows become filler that still
  ships on the wire, the naive-control arm. ON: nodes stay lazy so the
  consuming exchange fuses them into ``row_filter``/``keep_words``,
  and each ``reduce_by_key`` node's combine-gate decision is hoisted
  here (one ``plan_combine`` sample per NODE, handed back through the
  exchange's ``combine_hint``).
- ``plan_reuse`` ON: exchange outputs memoize by canonical fingerprint;
  with a MapOutputStore configured they also persist via
  ``checkpoint_segments(sid, ..., plan=None)`` under a deterministic
  fingerprint-derived shuffle id, so a RESTARTED process adopts them
  through ``resume_segments`` + the tiered store instead of
  re-exchanging.
- ``plan_broadcast_join`` ON: joins the optimizer marked broadcast pull
  the dim side to host (``broadcast_build`` stage), replicate its
  sorted key/attr arrays to every device, and skip both sides'
  exchanges. A build failure (duplicate primary keys) degrades to the
  shuffle join via ``faults.note_degradation("broadcast_join")`` — the
  same ladder every other fast path rides.
- ``plan_overlap`` ON: deferred host-row dim sources marked by the
  optimizer encode on a background ``HostPrefetcher`` worker while the
  fact subtree's exchanges drain.

All four rewrites are bit-identical on/off at the ``to_host_rows``
level (tests/test_plan.py pins each one): pushdown-off ships doomed
rows as filler the host exit drops anyway; reuse returns the same
records; a broadcast join produces the same row multiset as the
shuffle join with only placement differing, which the downstream
aggregate's hash exchange re-canonicalizes; overlap is encode-side
only (pipeline placement equivalence).
"""

from __future__ import annotations

import logging
import time
from contextlib import nullcontext
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from sparkrdma_tpu import faults as _faults
from sparkrdma_tpu.api.dataset import (Dataset, _low_word_hash,
                                       _valid_nonfiller)
from sparkrdma_tpu.obs import trace as _trace
from sparkrdma_tpu.obs.journal import SCHEMA_VERSION
from sparkrdma_tpu.plan.nodes import LogicalPlan, PlanNode, fingerprint_hex
from sparkrdma_tpu.plan.optimizer import optimize

log = logging.getLogger("sparkrdma_tpu.plan")

#: Frozen field set of every ``{"kind": "plan"}`` journal line — the
#: plan-schema-sync srlint rule checks the literal emitter dict below
#: and the CLI readers' ``pl.get("...")`` accesses against this set,
#: both directions. Extend the set and the emitter TOGETHER.
PLAN_FIELDS = frozenset({
    "kind", "schema", "ts", "trace_id", "job", "node", "op", "rewrite",
    "fingerprint", "rows", "bytes_saved", "detail",
})

#: Durable reuse-cache shuffle ids: derived from the exchange
#: fingerprint so a restarted process computes the same id, parked in
#: their own range above the Dataset layer's ``1 << 20`` counter. The
#: span keeps all 44 low bits of the 48-bit fingerprint (birthday
#: collisions are negligible at any plausible cache size), and the
#: manifest additionally records the FULL fingerprint — ``_persist``
#: never overwrites a different fingerprint's entry, ``_try_resume``
#: treats a mismatch as a miss — so even a colliding id can only cost
#: a cache slot, never serve wrong segments.
_REUSE_ID_BASE = 1 << 24
_REUSE_ID_SPAN = 1 << 44


def reuse_shuffle_id(fp: str) -> int:
    """Deterministic checkpoint shuffle id for an exchange fingerprint."""
    return _REUSE_ID_BASE + int(fp, 16) % _REUSE_ID_SPAN


def plan_line(node: str, op: str, rewrite: str, fingerprint: str,
              rows: int = 0, bytes_saved: int = 0,
              detail: str = "") -> dict:
    """Build one ``{"kind": "plan"}`` journal line (schema v13).

    ``rewrite`` is one of ``pushdown`` / ``reuse`` / ``broadcast_join``
    / ``overlap`` / ``combine_hoist``. The drift check is a plain
    RuntimeError (not an assert) so it survives ``python -O``.
    """
    tc = _trace.current_trace()
    line = {
        "kind": "plan",
        "schema": SCHEMA_VERSION,
        "ts": time.time(),
        "trace_id": tc.trace_id if tc else "",
        "job": tc.job if tc else "",
        "node": node,
        "op": op,
        "rewrite": rewrite,
        "fingerprint": fingerprint,
        "rows": int(rows),
        "bytes_saved": int(bytes_saved),
        "detail": detail,
    }
    if set(line) != PLAN_FIELDS:
        raise RuntimeError("plan journal line drifted from PLAN_FIELDS "
                           "— update the frozen set and this emitter "
                           "together")
    return line


class BroadcastBuildError(RuntimeError):
    """Broadcast dim build failed (duplicate primary keys) — the
    executor catches this and degrades to the shuffle join."""


class PlanExecutor:
    """Executes optimized :class:`LogicalPlan` DAGs on one manager."""

    def __init__(self, manager):
        self.manager = manager
        #: exchange-reuse memo: fingerprint -> (records, totals, schema,
        #: projected). Shared across run() calls — suite-level reuse.
        self._memo: Dict[str, Tuple] = {}
        #: per-run source results (object identity, not a rewrite)
        self._results: Dict[int, object] = {}
        #: compiled lookup-join programs keyed by geometry
        self._programs: Dict[Tuple, Callable] = {}
        self._prefetcher = None
        self._prefetched: set = set()

    # ------------------------------------------------------------------
    def run(self, plan: LogicalPlan, job_name: str = ""):
        """Optimize + execute; returns host rows for a ``sink`` root, a
        ``GroupedData`` for a ``group_by_key`` root, else a Dataset."""
        m = self.manager
        self._reset_run_state()
        with m.job(job_name or plan.name or "plan"):
            with _trace.stage("plan_optimize"):
                root, decisions = optimize(plan.root, m.conf)
            self._journal_decisions(decisions)
            return self._exec(root)

    def run_inline(self, plan: LogicalPlan):
        """Optimize + execute under the CALLER's job/stage scopes: no
        job of its own, no ``plan_optimize`` stage. For embedding a
        planner-built fragment inside an explicitly staged workload
        (tpcds q95's ``co_partition`` stage) without changing the
        job's stage profile."""
        self._reset_run_state()
        root, decisions = optimize(plan.root, self.manager.conf)
        self._journal_decisions(decisions)
        return self._exec(root)

    def _reset_run_state(self) -> None:
        """Run-boundary reset: per-run source results AND the prefetch
        bookkeeping. A prior run (especially an aborted one) may have
        left unconsumed encode futures in the prefetcher; draining them
        here keeps a stale Dataset from ever being handed to a later
        run's source node."""
        self._results = {}
        self._prefetched.clear()
        if self._prefetcher is not None:
            self._prefetcher.drain()

    def _journal_decisions(self, decisions) -> None:
        m = self.manager
        for d in decisions:
            if d.rewrite == "pushdown" and d.detail.startswith("fused"):
                m.metrics.counter("plan.pushdown_sunk").inc()
            m.journal.emit_raw(plan_line(
                d.node, d.op, d.rewrite, d.fingerprint,
                rows=d.rows, bytes_saved=d.bytes_saved, detail=d.detail))

    # ------------------------------------------------------------------
    # node dispatch
    # ------------------------------------------------------------------
    def _exec(self, node: PlanNode):
        op = node.op
        if op == "source":
            return self._exec_source(node)
        if op == "filter":
            ds = self._exec(node.children[0])
            return self._eager(ds.filter(node.pred,
                                         cache_key=node.pred_key))
        if op == "select":
            ds = self._exec(node.children[0])
            return self._eager(ds.select(*node.columns))
        if op == "sink":
            return self._exec(node.children[0]).to_host_rows()
        if op == "join":
            return self._exec_join(node)
        # single-input exchange verbs
        ds = self._exec(node.children[0])
        with self._maybe_stage(node.stage):
            if op == "repartition":
                return self._memo_exchange(
                    node.fp, node,
                    lambda: ds.repartition(node.num_parts))
            if op == "sort_by_key":
                return self._memo_exchange(
                    node.fp, node,
                    lambda: ds.sort_by_key(node.samples_per_device))
            if op == "reduce_by_key":
                hint = self._hoist_combine(node, ds)
                return self._memo_exchange(
                    node.fp, node,
                    lambda: ds.reduce_by_key(
                        node.agg, float_payload=node.float_payload,
                        combine_hint=hint))
            if op == "group_by_key":
                # CSR result — not memoized (Dataset-shaped memo only)
                return ds.group_by_key()
        raise ValueError(f"unknown plan op {op!r}")

    def _maybe_stage(self, name: str):
        return _trace.stage(name) if name else nullcontext()

    def _eager(self, ds: Dataset) -> Dataset:
        """Pushdown gate: OFF forces the naive eager materialization
        (filtered rows become wire-visible filler); ON leaves the
        pending ops to fuse into the next exchange."""
        if self.manager.conf.plan_pushdown:
            return ds
        return ds._materialize_pending()

    def _exec_source(self, node: PlanNode) -> Dataset:
        hit = self._results.get(id(node))
        if hit is not None:
            return hit
        if node.dataset is not None:
            ds = node.dataset
        else:
            m = node.manager or self.manager
            ds = None
            if self._prefetcher is not None and \
                    node.fp in self._prefetched:
                self._prefetched.discard(node.fp)
                try:
                    ds = self._prefetcher.take(node.fp)
                except Exception as exc:
                    # overlap is a pure latency optimization: a wedged
                    # or failed background encode must degrade to the
                    # synchronous path, never fail the query
                    _faults.note_degradation("plan_overlap",
                                             reason=str(exc))
                    self.manager.journal.emit_raw(plan_line(
                        node.label, node.op, "overlap", node.fp,
                        detail=f"prefetch failed, synchronous encode "
                               f"fallback: {exc}"))
                    log.warning(
                        "plan overlap prefetch of %s failed (%s); "
                        "encoding synchronously", node.label or "source",
                        exc)
                    ds = None
            if ds is None:
                ds = Dataset.from_host_rows(m, node.rows,
                                            schema=node.schema)
        self._results[id(node)] = ds
        return ds

    # ------------------------------------------------------------------
    # combine-gate hoist (rewrite 1, decision half)
    # ------------------------------------------------------------------
    def _hoist_combine(self, node: PlanNode,
                       ds: Dataset) -> Optional[Tuple[bool, float]]:
        m = self.manager
        if not m.conf.plan_pushdown:
            return None
        use, ratio = m._exchange.plan_combine(ds.records, node.agg)
        m.journal.emit_raw(plan_line(
            node.label, node.op, "combine_hoist", node.fp,
            detail=f"use={use} ratio={ratio:.3f}"))
        return (use, ratio)

    # ------------------------------------------------------------------
    # shuffle-output reuse (rewrite 2)
    # ------------------------------------------------------------------
    def _memo_exchange(self, fp: str, node: PlanNode,
                       run: Callable[[], Dataset]) -> Dataset:
        m = self.manager
        if not m.conf.plan_reuse:
            return run()
        hit = self._memo.get(fp)
        via = "memo"
        if hit is None and m.store is not None:
            hit = self._try_resume(fp, node)
            via = "resume_segments"
        if hit is not None:
            records, totals, schema, projected = hit
            rows = int(np.asarray(totals).sum())
            saved = rows * int(records.shape[0]) * 4
            m.metrics.counter("plan.reuse_hits").inc()
            m.journal.emit_raw(plan_line(
                node.label, node.op, "reuse", fp, rows=rows,
                bytes_saved=saved, detail=f"adopted via {via}"))
            ds = Dataset(m, records, totals, schema=schema)
            ds.projected = projected
            return ds
        out = run()
        self._memo[fp] = (out.records, out.totals, out.schema,
                          out.projected)
        if m.store is not None:
            self._persist(fp, out)
        return out

    def _persist(self, fp: str, ds: Dataset) -> None:
        m = self.manager
        sid = reuse_shuffle_id(fp)
        try:
            try:
                existing = m.store.load_segment_meta(sid)
            except KeyError:
                existing = None
            if existing is not None and \
                    existing.get("plan_fp") not in (None, fp):
                # derived-id collision: keep the first entry — evicting
                # it would silently shrink the durable cache, and the
                # colliding fingerprint simply stays memo-only
                log.warning(
                    "plan reuse id collision: shuffle id %d already "
                    "holds fingerprint %s; keeping it, not persisting "
                    "%s", sid, existing.get("plan_fp"), fp)
                return
            m.checkpoint_segments(
                sid,
                [(f"plan{fp}:cols", np.asarray(ds.records)),
                 (f"plan{fp}:totals", np.asarray(ds.totals))],
                plan=None, num_parts=m.runtime.num_partitions,
                extra_meta={"plan_fp": fp})
        except Exception as exc:           # cache write, never fatal
            log.warning("plan reuse persist of %s failed: %s", fp, exc)

    def _try_resume(self, fp: str, node: PlanNode) -> Optional[Tuple]:
        """Cross-restart adoption: segment checkpoint -> tiered store.

        The manifest must carry OUR full fingerprint: the checkpoint
        shuffle id keeps only 44 fingerprint bits, so a missing or
        different ``plan_fp`` (id collision, pre-fingerprint manifest)
        reads as a miss, never as someone else's segments."""
        m = self.manager
        sid = reuse_shuffle_id(fp)
        try:
            meta = m.store.load_segment_meta(sid)
        except KeyError:
            return None
        except Exception as exc:
            log.warning("plan reuse manifest of %s unreadable: %s",
                        fp, exc)
            return None
        if meta.get("plan_fp") != fp:
            log.info("plan reuse: shuffle id %d holds fingerprint %s, "
                     "wanted %s — miss", sid, meta.get("plan_fp"), fp)
            return None
        try:
            m.resume_segments(sid)
            cols = m.tiered.get(f"plan{fp}:cols")
            totals = m.tiered.get(f"plan{fp}:totals")
        except KeyError:
            return None
        except Exception as exc:
            log.warning("plan reuse resume of %s failed: %s", fp, exc)
            return None
        records = m.runtime.shard_records(
            np.ascontiguousarray(cols).T)
        return (records, jnp.asarray(np.asarray(totals)),
                self._subtree_schema(node), None)

    @staticmethod
    def _subtree_schema(node: PlanNode):
        """Output schema of a resumed exchange: the source schema if
        every op on the path is layout-preserving (the runtime rule
        ``Dataset._exchange_traced`` applies), else None."""
        while node.children:
            if node.op in ("reduce_by_key", "group_by_key", "join"):
                return None
            node = node.children[0]
        return node.schema

    # ------------------------------------------------------------------
    # joins (rewrites 3 + 4)
    # ------------------------------------------------------------------
    def _exec_join(self, node: PlanNode) -> Dataset:
        m = self.manager
        left_node, dim_node = node.children
        self._maybe_prefetch(dim_node)
        left = self._exec(left_node)
        with self._maybe_stage(node.stage):
            if node.broadcast and m.conf.plan_broadcast_join:
                try:
                    return self._broadcast_join(node, left, dim_node)
                except BroadcastBuildError as exc:
                    _faults.note_degradation("broadcast_join",
                                             reason=str(exc))
                    m.journal.emit_raw(plan_line(
                        node.label, node.op, "broadcast_join", node.fp,
                        detail=f"degraded to shuffle join: {exc}"))
            return self._shuffle_join(node, left, dim_node)

    def _maybe_prefetch(self, dim_node: PlanNode) -> None:
        """Rewrite 4: start the marked dim source's host encode on a
        background worker before the fact subtree executes. Keyed by
        the node FINGERPRINT, not ``id()`` — fingerprints are
        content-stable and non-recyclable, so a garbage-collected prior
        run's node can never alias a fresh one (CPython reuses ids)."""
        src = dim_node
        while src.children:
            src = src.children[0]
        if not (self.manager.conf.plan_overlap
                and src.op == "source" and src.prefetch
                and src.rows is not None and src.fp):
            return
        if src.fp in self._prefetched or id(src) in self._results:
            return
        if self._prefetcher is None:
            from sparkrdma_tpu.api.pipeline import HostPrefetcher

            self._prefetcher = HostPrefetcher()
        manager = src.manager or self.manager
        rows, schema = src.rows, src.schema
        self._prefetched.add(src.fp)
        self._prefetcher.submit(
            src.fp,
            lambda: Dataset.from_host_rows(manager, rows, schema=schema))
        self.manager.metrics.counter("plan.overlapped_stages").inc()

    def _shuffle_join(self, node: PlanNode, left: Dataset,
                      dim_node: PlanNode) -> Dataset:
        """Co-partition both sides on the low key word, then run the
        per-device PK lookup (the tpcds ``_pk_lookup_program`` shape)."""
        m = self.manager
        mesh = m.runtime.num_partitions
        key_ix = m.conf.key_words - 1
        part = _low_word_hash(mesh, key_ix)
        fp_l = fingerprint_hex(("xjoin_left", node.children[0].fp,
                                key_ix, mesh))
        fp_d = fingerprint_hex(("xjoin_dim", dim_node.fp, key_ix, mesh))
        l2 = self._memo_exchange(
            fp_l, node, lambda: left._exchange(part, mesh, op="join"))
        dim = self._exec(dim_node)
        d2 = self._memo_exchange(
            fp_d, node, lambda: dim._exchange(part, mesh, op="join"))
        cap_l = l2.records.shape[1] // mesh
        cap_d = d2.records.shape[1] // mesh
        fn = self._lookup_program(cap_l, cap_d, node.key_from,
                                  node.attr_to)
        out = fn(l2.records, l2.totals, d2.records, d2.totals)
        return Dataset(m, out, l2.totals, schema=node.schema)

    def _broadcast_join(self, node: PlanNode, left: Dataset,
                        dim_node: PlanNode) -> Dataset:
        """Rewrite 3: replicate the (small) dim table to every device —
        neither side exchanges. Bit-identical row multiset to the
        shuffle join; only placement differs."""
        m = self.manager
        with _trace.auto_stage("broadcast_build"):
            sd, attrs = self._broadcast_build(dim_node)
        left = left._materialize_pending()
        mesh = m.runtime.num_partitions
        cap_l = left.records.shape[1] // mesh
        fn = self._broadcast_program(cap_l, int(sd.shape[0]),
                                     node.key_from, node.attr_to)
        out = fn(left.records, left.totals, sd, attrs)
        m.metrics.counter("plan.broadcast_joins").inc()
        m.journal.emit_raw(plan_line(
            node.label, node.op, "broadcast_join", node.fp,
            rows=int(np.asarray(left.totals).sum()),
            detail=f"dim replicated ({int(sd.shape[0])} slots)"))
        return Dataset(m, out, left.totals, schema=node.schema)

    def _broadcast_build(self, dim_node: PlanNode):
        """Pull the dim side to host; sorted unique PK array + riding
        attribute, padded to a power-of-two slot count (bounds compiled
        program variants). Duplicate keys are a construction failure."""
        dim = self._exec(dim_node)
        rows = dim.to_host_rows()
        kw = self.manager.conf.key_words
        keys = rows[:, kw - 1].astype(np.uint32)
        attrs = rows[:, kw].astype(np.uint32)
        live = keys != 0          # key 0 = null/padding rows, never match
        keys, attrs = keys[live], attrs[live]
        if len(keys) and len(np.unique(keys)) != len(keys):
            raise BroadcastBuildError(
                f"dim side has duplicate primary keys "
                f"({len(keys) - len(np.unique(keys))} collisions)")
        order = np.argsort(keys, kind="stable")
        keys, attrs = keys[order], attrs[order]
        n_pad = 1 << max(0, int(len(keys) - 1).bit_length()) \
            if len(keys) else 1
        pad = n_pad - len(keys)
        sd = np.concatenate([keys, np.full(pad, 0xFFFFFFFF, np.uint32)])
        at = np.concatenate([attrs, np.zeros(pad, np.uint32)])
        return jnp.asarray(sd), jnp.asarray(at)

    # ------------------------------------------------------------------
    # compiled lookup programs (tpcds _pk_lookup_program generalized)
    # ------------------------------------------------------------------
    def _lookup_local(self, cap_l: int, cap_d_or_pad: int, key_from: int,
                      attr_to: int, broadcast: bool) -> Callable:
        m = self.manager
        kw = m.conf.key_words
        vw = m.conf.val_words
        key_ix = kw - 1

        def lookup(lc, lt, sd, attrs):
            vl = _valid_nonfiller(lc, lt, cap_l, kw)
            lk = lc[key_ix]
            idx = jnp.minimum(jnp.searchsorted(sd, lk), cap_d_or_pad - 1)
            # keys 0 / 0xFFFFFFFF are the null-group / filler-pad
            # sentinels — a left row carrying either never matches
            # (identical rule in both the shuffle and broadcast paths)
            live = (lk != jnp.uint32(0)) & (lk != jnp.uint32(0xFFFFFFFF))
            found = (jnp.take(sd, idx) == lk) & vl & live
            a = jnp.take(attrs, idx)
            zero = jnp.zeros_like(lk)
            out = [zero] * (kw - 1)
            out.append(jnp.where(found, lc[kw + key_from], 0))
            for j in range(vw):
                if j == attr_to:
                    out.append(jnp.where(found, a, 0))
                else:
                    out.append(jnp.where(found, lc[kw + j], 0))
            return jnp.stack(out)

        if broadcast:
            return lookup

        def local(lc, lt, dc, dt):
            vd = _valid_nonfiller(dc, dt, cap_d_or_pad, kw)
            dk = jnp.where(vd, dc[key_ix], jnp.uint32(0xFFFFFFFF))
            sd, attrs = jax.lax.sort((dk, dc[kw]), num_keys=1,
                                     is_stable=True)
            return lookup(lc, lt, sd, attrs)

        return local

    def _lookup_program(self, cap_l: int, cap_d: int, key_from: int,
                        attr_to: int) -> Callable:
        key = ("shuffle", cap_l, cap_d, key_from, attr_to)
        fn = self._programs.get(key)
        if fn is None:
            rt = self.manager.runtime
            ax = rt.axis_name
            fn = jax.jit(shard_map(
                self._lookup_local(cap_l, cap_d, key_from, attr_to,
                                   broadcast=False),
                mesh=rt.mesh,
                in_specs=(P(None, ax), P(ax), P(None, ax), P(ax)),
                out_specs=P(None, ax)))
            self._programs[key] = fn
        return fn

    def _broadcast_program(self, cap_l: int, n_pad: int, key_from: int,
                           attr_to: int) -> Callable:
        key = ("broadcast", cap_l, n_pad, key_from, attr_to)
        fn = self._programs.get(key)
        if fn is None:
            rt = self.manager.runtime
            ax = rt.axis_name
            fn = jax.jit(shard_map(
                self._lookup_local(cap_l, n_pad, key_from, attr_to,
                                   broadcast=True),
                mesh=rt.mesh,
                in_specs=(P(None, ax), P(ax), P(None), P(None)),
                out_specs=P(None, ax)))
            self._programs[key] = fn
        return fn

    # ------------------------------------------------------------------
    def invalidate_reuse(self) -> None:
        """Explicit reuse-cache invalidation: drop the in-memory memo
        and delete every durable plan-reuse checkpoint in the manager's
        store. The escape hatch for the named-source contract (see
        plan/nodes.py): sources whose content the planner cannot digest
        are adopted on the promise that their name means stable data —
        call this when that promise breaks (a named table was reloaded
        with new rows) before running the next plan."""
        self._memo.clear()
        m = self.manager
        if m.store is None:
            return
        for sid in m.store.list_segment_checkpoints():
            if sid < _REUSE_ID_BASE:
                continue
            try:
                is_plan = "plan_fp" in m.store.load_segment_meta(sid)
            except (KeyError, ValueError):
                continue
            if is_plan:
                m.store.delete(sid)

    def close(self) -> None:
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None


__all__ = ["PlanExecutor", "PLAN_FIELDS", "plan_line",
           "reuse_shuffle_id", "BroadcastBuildError"]
