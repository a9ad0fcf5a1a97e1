"""Bucketed plan execution: the bridge between the service scheduler and
the executors' stacked entry points.

The scheduler (:mod:`repro.serve.service`) thinks in *shape signatures*
(:meth:`~repro.core.plan.ContractionPlan.shape_signature` — its quota and
metrics unit); the executors stack on the stricter
:func:`~repro.core.executors.plan_stack_key` (same topology AND array
sizes).  :func:`execute_bucketed` sits between the two: it chops an
arbitrary mix of compiled plans into same-shape micro-batches of at most
``max_batch_size``, hands each to
:meth:`~repro.core.executors.Executor.positive_batch` (which re-groups by
stack key and vmaps what it can, loops what it can't), and reports each
micro-batch's latency to the service metrics.

:func:`execute_complete_bucketed` is the same bridge for **complete-CT
queries** (positive + Möbius negative phase): the positive sub-queries of
every complete query are enumerated up front
(:func:`~repro.core.mobius.positive_queries`), deduplicated through the
positive policy, and executed via :func:`execute_bucketed`; the negative
phase then runs through :func:`~repro.core.mobius.complete_ct_many`, on
the host in the engine's dtype, with one block memo across the batch.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.contract import CostStats
from ..core.ct import CtTable
from ..core.database import RelationalDB
from ..core.engine import CountingEngine
from ..core.executors import Executor, plan_input_arrays, plan_stack_key
from ..core.mobius import complete_ct_many, positive_queries
from ..core.plan import ContractionPlan, group_by_signature
from ..core.variables import CtVar, LatticePoint
from ..obs.trace import NULL_TRACER, NullTracer
from .metrics import ServiceMetrics

__all__ = ["TableMerger", "execute_bucketed", "execute_bucketed_multi",
           "execute_complete_bucketed", "plan_input_arrays",
           "plan_stack_key"]


class TableMerger:
    """Batched device-side reduction of per-shard count tables.

    Count-table merging is exact addition, so it belongs on the device:
    instead of ``n_shards - 1`` sequential eager adds per query (the old
    host-side Python loop in :class:`~repro.serve.router.RouterTicket`),
    same-shape shard tables — across MANY queries at once — are stacked
    and tree-merged in ONE jitted dispatch per ``(n_partials, shape)``
    group.  Inside the trace the reduction is
    :func:`~repro.core.distributed.merge_stacked`: a ``psum`` over a
    ``data`` mesh when one device per partial exists, a stacked
    ``jnp.sum`` on one host.  The query axis is padded to the next power
    of two (replaying query 0) so the jit cache stays keyed by a handful
    of sizes.

    One instance per router; thread-safe (concurrent floods share the
    traced reducers).

    Usage::

        merged = TableMerger().merge_tables([[tab_shard0, tab_shard1]])
    """

    def __init__(self):
        self._fns: Dict[Tuple, object] = {}
        self._lock = threading.Lock()

    def _reduce_fn(self, n_partials: int, q_pad: int,
                   shape: Tuple[int, ...]):
        key = (n_partials, q_pad, shape)
        fn = self._fns.get(key)
        if fn is None:
            from ..core.distributed import merge_stacked

            def run(*flat):
                # flat is partial-major: shard s's tables for every query
                # are flat[s*q_pad : (s+1)*q_pad]
                stacked = jnp.stack(flat).reshape(
                    (n_partials, q_pad) + shape)
                out = merge_stacked(stacked)
                # per-query slices INSIDE the jit — callers get ready
                # tables, not q eager gather dispatches
                return tuple(out[i] for i in range(q_pad))

            with self._lock:
                fn = self._fns.setdefault(key, jax.jit(run))
        return fn

    def reduce_arrays(self, arrays: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """Merge one query's partial count arrays (same shape) in one
        jitted dispatch — the overlapped path's partial fold."""
        arrays = list(arrays)
        if len(arrays) == 1:
            return arrays[0]
        fn = self._reduce_fn(len(arrays), 1, tuple(arrays[0].shape))
        (out,) = fn(*arrays)
        return out

    def merge_tables(self, per_query: Sequence[Sequence[CtTable]]
                     ) -> Tuple[List[CtTable], int]:
        """Merge many queries' per-shard tables, batched by shape.

        Args:
            per_query: one list of same-``vars`` shard tables per query
                (per-shard plans are compiled against the same schema, so
                shard tables of one query always align axis-for-axis).

        Returns:
            ``(merged, dispatches)``: one merged table per query in input
            order — each holding the device array straight out of the
            batched reduction, no host copy — and the number of jitted
            merge dispatches issued.

        Usage::

            merged, n_disp = merger.merge_tables(shard_tables)
        """
        merged: List[Optional[CtTable]] = [None] * len(per_query)
        groups: Dict[Tuple, List[int]] = {}
        for i, tabs in enumerate(per_query):
            if len(tabs) == 1:
                merged[i] = tabs[0]
                continue
            groups.setdefault(
                (len(tabs), tuple(tabs[0].counts.shape)), []).append(i)
        dispatches = 0
        for (n_partials, shape), idxs in groups.items():
            q = len(idxs)
            q_pad = 1 << max(q - 1, 0).bit_length()
            fn = self._reduce_fn(n_partials, q_pad, shape)
            flat: List[jnp.ndarray] = []
            for s in range(n_partials):          # partial-major layout
                flat.extend(per_query[i][s].counts for i in idxs)
                flat.extend([per_query[idxs[0]][s].counts] * (q_pad - q))
            out = fn(*flat)
            dispatches += 1
            for j, i in enumerate(idxs):
                merged[i] = CtTable(per_query[i][0].vars, out[j])
        return merged, dispatches                          # type: ignore


def execute_bucketed(executor: Executor, db: RelationalDB,
                     plans: Sequence[ContractionPlan],
                     stats: Optional[CostStats] = None,
                     max_batch_size: Optional[int] = None,
                     metrics: Optional[ServiceMetrics] = None,
                     tracer: NullTracer = NULL_TRACER
                     ) -> List[CtTable]:
    """Evaluate ``plans`` in shape-signature micro-batches.

    Results align positionally with ``plans`` and are numerically identical
    to per-plan :meth:`~repro.core.executors.Executor.positive` execution;
    only the dispatch granularity changes.

    Args:
        executor: the backend to evaluate with.
        db: the database the plans were compiled against.
        plans: compiled :class:`~repro.core.plan.ContractionPlan` list.
        stats: optional :class:`~repro.core.contract.CostStats` for
            join/row accounting.
        max_batch_size: cap per micro-batch (``None``/0 = one batch per
            signature bucket).
        metrics: optional :class:`~repro.serve.metrics.ServiceMetrics`
            that receives one ``observe_batch`` per micro-batch.
        tracer: optional :class:`~repro.obs.trace.Tracer`; the whole
            call is one ``count.positive`` span (``tables``: the plans
            contracted; ``hops``: their relationship hops) and each micro-batch dispatch a ``batch.dispatch``
            span inside it.

    Returns:
        One :class:`~repro.core.ct.CtTable` per plan, in input order.

    Usage::

        tabs = execute_bucketed(engine.executor, db, plans, engine.stats)
    """
    with tracer.span("count.positive") as sp:
        if tracer.enabled:
            sp.set(tables=len(plans), hops=sum(p.hops for p in plans))
        results: List[Optional[CtTable]] = [None] * len(plans)
        for sig, idxs in group_by_signature(plans, key="shape").items():
            step = max_batch_size if max_batch_size else len(idxs)
            for s in range(0, len(idxs), max(step, 1)):
                chunk = idxs[s:s + max(step, 1)]
                t0 = time.perf_counter()
                with (tracer.span("batch.dispatch", sig=sig,
                                  queries=len(chunk))
                      if tracer.enabled else nullcontext()):
                    tabs = executor.positive_batch(
                        db, [plans[i] for i in chunk], stats)
                dt = time.perf_counter() - t0
                if metrics is not None:
                    metrics.observe_batch(sig, len(chunk), dt)
                for i, tab in zip(chunk, tabs):
                    results[i] = tab
        return results


def execute_bucketed_multi(executor: Executor,
                           dbs: Sequence[RelationalDB],
                           plans: Sequence[ContractionPlan],
                           stats_list: Optional[Sequence[
                               Optional[CostStats]]] = None,
                           max_batch_size: Optional[int] = None,
                           metrics_list: Optional[Sequence[
                               Optional[ServiceMetrics]]] = None,
                           tracer: NullTracer = NULL_TRACER
                           ) -> List[CtTable]:
    """:func:`execute_bucketed` across MANY databases — the cross-tenant
    dispatch path.  Item ``i`` is ``plans[i]`` against ``dbs[i]``; plans
    from different databases that share a shape signature land in the
    same micro-batch and (when their stack keys also match) the same
    jitted dispatch via
    :meth:`~repro.core.executors.Executor.positive_batch_multi`.

    Args:
        executor: the SHARED backend (its trace/staging caches are what
            cross-tenant batching amortises).
        dbs: one database per plan.
        plans: compiled plans, positionally paired with ``dbs``.
        stats_list: optional per-item :class:`~repro.core.contract
            .CostStats` (each tenant engine's).
        max_batch_size: cap per micro-batch (``None``/0 = one batch per
            signature bucket).
        metrics_list: optional per-item
            :class:`~repro.serve.metrics.ServiceMetrics`; each distinct
            instance in a micro-batch receives one ``observe_batch`` with
            its own query count and its wall-time share of the dispatch.
        tracer: optional tracer; the whole call is one
            ``count.positive`` span and each micro-batch a
            ``batch.dispatch`` span carrying the tenant fan-in.

    Returns:
        One :class:`~repro.core.ct.CtTable` per item, in input order.

    Usage::

        tabs = execute_bucketed_multi(executor, dbs, plans)
    """
    with tracer.span("count.positive") as sp:
        if tracer.enabled:
            sp.set(tables=len(plans), hops=sum(p.hops for p in plans))
        results: List[Optional[CtTable]] = [None] * len(plans)
        for sig, idxs in group_by_signature(plans, key="shape").items():
            step = max_batch_size if max_batch_size else len(idxs)
            for s in range(0, len(idxs), max(step, 1)):
                chunk = idxs[s:s + max(step, 1)]
                c_dbs = [dbs[i] for i in chunk]
                c_plans = [plans[i] for i in chunk]
                c_stats = ([stats_list[i] for i in chunk]
                           if stats_list is not None else None)
                t0 = time.perf_counter()
                with (tracer.span("batch.dispatch", sig=sig,
                                  queries=len(chunk),
                                  dbs=len({id(d) for d in c_dbs}))
                      if tracer.enabled else nullcontext()):
                    tabs = executor.positive_batch_multi(c_dbs, c_plans,
                                                         c_stats)
                dt = time.perf_counter() - t0
                if metrics_list is not None:
                    shares: Dict[int, Tuple[ServiceMetrics, int]] = {}
                    for i in chunk:
                        m = metrics_list[i]
                        if m is not None:
                            _, n = shares.get(id(m), (m, 0))
                            shares[id(m)] = (m, n + 1)
                    for m, n in shares.values():
                        m.observe_batch(sig, n, dt * n / len(chunk))
                for i, tab in zip(chunk, tabs):
                    results[i] = tab
        return results


def execute_complete_bucketed(engine: CountingEngine, policy,
                              queries: Sequence[Tuple[LatticePoint,
                                                      Sequence[CtVar]]],
                              stats: Optional[CostStats] = None,
                              max_batch_size: Optional[int] = None,
                              metrics: Optional[ServiceMetrics] = None,
                              use_butterfly: bool = True) -> List[CtTable]:
    """Evaluate complete-CT queries (positive + negative phases) batched.

    Phase 1 (positive): the positive sub-queries every query's Möbius join
    will issue are enumerated, filtered to what ``policy`` would contract
    from data (:meth:`~repro.core.engine._Policy.batchable_misses`),
    executed through :func:`execute_bucketed` in signature-bucketed
    stacked dispatches, and absorbed back into the policy's cache.  Phase
    2 (negative): :func:`~repro.core.mobius.complete_ct_many` joins every
    query on the host from the warmed cache, one block memo across them.

    Results align positionally with ``queries`` and are numerically
    identical to per-query :func:`~repro.core.mobius.complete_ct`.  Time
    accounting matches the strategy path: data access lands in
    ``time_positive``, the transform in ``time_negative`` (disjointly).
    The call is one ``count.complete`` span on the engine's tracer,
    carrying ``subqueries`` (the distinct positive sub-queries, by atoms
    and keep, that the Möbius joins need) and ``from_data`` (the tables
    ``policy`` contracted from data for them).

    Args:
        engine: the planner/executor/cache stack to execute against.
        policy: a positive policy from :mod:`repro.core.engine`
            (``batchable_misses``/``absorb``/``positive``/``hist``).
        queries: ``(point, keep)`` pairs; ``keep`` may contain attr and
            rind axes (edge-attr axes fall back to blockwise per query).
        stats: optional :class:`~repro.core.contract.CostStats`.
        max_batch_size: positive-phase micro-batch cap (see
            :func:`execute_bucketed`).
        metrics: optional :class:`~repro.serve.metrics.ServiceMetrics`;
            receives ``observe_batch`` per positive micro-batch and one
            ``observe_mobius`` for the negative phase.
        use_butterfly: evaluation order, as in
            :func:`~repro.core.mobius.complete_ct`.

    Returns:
        One complete :class:`~repro.core.ct.CtTable` per query.

    Usage::

        tabs = execute_complete_bucketed(engine, policy, queries)
    """
    queries = [(point, tuple(keep)) for point, keep in queries]
    timer = ((lambda which: stats.timer(which)) if stats is not None
             else (lambda which: nullcontext()))
    tracer = getattr(engine, "tracer", NULL_TRACER)
    with tracer.span("count.complete") as sp:
        pos: List[Tuple[LatticePoint, Tuple[CtVar, ...]]] = []
        for point, keep in queries:
            pos.extend(positive_queries(point, keep, use_butterfly))
        todo = policy.batchable_misses(pos)
        if tracer.enabled:
            sp.set(subqueries=len({(p.atoms, k) for p, k in pos}),
                   from_data=len(todo))
        if todo:
            plans = [engine.plan(p, k) for p, k in todo]
            with timer("positive"):
                tabs = execute_bucketed(engine.executor, engine.db, plans,
                                        stats, max_batch_size, metrics,
                                        tracer=tracer)
            for (p, _), plan, tab in zip(todo, plans, tabs):
                policy.absorb(p, plan.keep, tab)

        # any residual data access (unwarmed misses, eviction recomputes)
        # times itself in the policy; the disjoint timer subtracts its
        # growth to keep the Fig. 3 decomposition disjoint
        t0 = time.perf_counter()
        with (stats.disjoint_timer("negative") if stats is not None
              else nullcontext()):
            tabs = complete_ct_many(queries, policy, stats,
                                    use_butterfly=use_butterfly,
                                    tracer=tracer, dtype=engine.dtype)
        if metrics is not None:
            metrics.observe_mobius(len(queries), time.perf_counter() - t0)
        return tabs
