"""PRECOUNT / ONDEMAND / HYBRID / TUPLEID counts-caching strategies
(paper Algs. 1-3 + the tuple-ID future-work variant).

All four expose the same interface to structure search:

    prepare(db, lattice)                    # pre-search phase
    family_ct(point, keep_vars) -> CtTable  # during search

and record the paper's instrumentation (Fig. 3 time decomposition into
metadata / positive / negative, Fig. 4 memory, Table 5 ct sizes) in
``stats``.

Since the planner/executor/cache refactor each strategy is a *thin policy*
over shared machinery (:mod:`repro.core.engine`): it picks a positive-table
policy, decides what runs at ``prepare`` time vs. search time, and shares
one byte-budgeted :class:`~repro.core.cache.CtCache` across positives,
messages, family memos and histograms.  The contraction backend is
pluggable (``executor="dense" | "sparse"``); the Möbius negative phase
runs on the host, subtracting in ``dtype`` (:mod:`repro.core.mobius`).

* PRECOUNT — prepare() contracts the positive ct-table for every lattice
  point AND runs the Möbius join to the complete table over *all* variables
  of the point; family_ct() is a pure projection.  Pays the Eq. (3) blowup.
* ONDEMAND — prepare() builds only per-variable histograms (metadata);
  family_ct() contracts the family's positive tables from the raw data (the
  expensive JOINs, re-run per family) then runs a small Möbius join.
* HYBRID — prepare() contracts and caches only the *positive* ct-table per
  lattice point (JOINs once, like PRECOUNT); family_ct() projects the
  cached positives down to the family and runs a small Möbius join (like
  ONDEMAND, but with zero data access).
* TUPLEID — prepare() caches per-relationship message matrices (tuple-ID
  propagation); family positives recombine them with zero edge access.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

from .contract import CostStats
from .ct import CtTable
from .database import RelationalDB
from .engine import (CachedFullPositives, CountingEngine, OnDemandPositives,
                     TupleIdPositives)
from .mobius import complete_ct, complete_ct_many, positive_queries
from .variables import CtVar, LatticePoint


def _freeze(point: LatticePoint, keep: Sequence[CtVar]) -> Tuple:
    return (point.atoms, tuple(keep))


@dataclass
class Strategy:
    """Base policy: shared engine, unified family memo, Möbius wiring.

    Subclasses set ``_policy_cls`` and ``_precount_complete`` /
    ``_warm_hists`` flags — everything else (caching, stats, executor and
    Möbius dispatch) lives in the shared machinery.
    """

    name: str = "base"
    dtype: object = jnp.float32
    use_butterfly: bool = True
    stats: CostStats = field(default_factory=CostStats)
    executor: object = "dense"             # name or Executor instance
    cache_budget_bytes: Optional[int] = None

    _policy_cls = None                     # set by subclasses
    _precount_complete = False             # PRECOUNT: complete tables upfront
    _warm_hists = False                    # ONDEMAND: hists are the metadata

    # -- pre-search phase ----------------------------------------------------
    def prepare(self, db: RelationalDB,
                lattice: Sequence[LatticePoint]) -> None:
        """The pre-search phase, inside one ``strategy.prepare`` span on
        the executor's tracer."""
        from .executors import make_executor
        ex = (self.executor if not isinstance(self.executor, str)
              else make_executor(self.executor, dtype=self.dtype))
        tr = ex.tracer
        with tr.span("strategy.prepare") as sp:
            if tr.enabled:
                sp.set(strategy=self.name)
            self.db, self.lattice = db, list(lattice)
            with self.stats.timer("metadata"):
                self.engine = CountingEngine(
                    db, ex, self.stats,
                    cache_budget_bytes=self.cache_budget_bytes,
                    dtype=self.dtype)
                self.provider = self._policy_cls(self.engine)
                self._service = None       # rebuilt lazily over this engine
                self._rows_counted = set()
                if self._warm_hists:
                    for point in lattice:
                        for v in point.vars:
                            self.provider.hist(v, ())
            # data access inside the policy times itself (-> time_positive),
            # including any eviction-driven recompute later on
            self.provider.precompute(lattice)
            if self._precount_complete:
                for point in lattice:
                    self._complete_full(point)

    # -- complete tables -----------------------------------------------------
    def _timed_complete(self, point: LatticePoint,
                        keep: Tuple[CtVar, ...]) -> CtTable:
        """Möbius join timed as negative-phase work; positive contractions
        nested inside it (ONDEMAND joins, eviction recomputes) time
        themselves in the policy, so the disjoint timer subtracts that
        growth to keep the Fig. 3 decomposition disjoint."""
        with self.stats.disjoint_timer("negative"):
            return complete_ct(point, keep, self.provider, self.stats,
                               use_butterfly=self.use_butterfly,
                               tracer=self.engine.tracer,
                               dtype=self.engine.dtype)

    def _complete_full(self, point: LatticePoint) -> CtTable:
        """Complete (positive+negative) table over *all* axes of a point —
        the PRECOUNT global ct.  Cached; recomputed if evicted.  Keyed by
        ``(atoms, keep)`` so the delta path can reconstruct the exact
        query and push butterfly deltas onto the resident table."""
        keep = tuple(point.all_ct_vars(self.db.schema, include_rind=True))
        key = ("complete", point.atoms, keep)
        hit = self.engine.cache.get(key)
        if hit is None:
            hit = self._timed_complete(point, keep)
            if key not in self._rows_counted:    # once per point, not per
                self._rows_counted.add(key)      # eviction recompute
                self.stats.ct_rows += hit.nnz_rows(self.engine.tracer)
            self.engine.cache.put(key, hit)
        return hit

    # -- search phase --------------------------------------------------------
    def family_ct(self, point: LatticePoint,
                  keep: Sequence[CtVar]) -> CtTable:
        if self._precount_complete:
            return self._complete_full(point).project(keep)
        key = ("fam",) + _freeze(point, keep)
        hit = self.engine.cache.get(key)
        if hit is not None:
            return hit
        tab = self._timed_complete(point, tuple(keep))
        self.engine.cache.put(key, tab)
        return tab

    # -- batched search phase (the serve layer as counting backend) ----------
    def service(self):
        """Lazy per-strategy :class:`~repro.serve.service.CountingService`
        over the shared engine — the batching front-end for this
        strategy's positive contractions.  Its complete-CT queries read
        positives through this strategy's own policy, as
        :meth:`family_ct_many` does: HYBRID and PRECOUNT project the
        pre-counted ``"full"`` tables, ONDEMAND contracts from data,
        TUPLEID recombines its messages."""
        svc = getattr(self, "_service", None)
        if svc is None:
            from ..serve.service import CountingService
            svc = self._service = CountingService(self.engine,
                                                  positives=self.provider)
        return svc

    # -- mutations -----------------------------------------------------------
    def apply_delta(self, delta, **kw):
        """Reconcile this strategy's cache after a store mutation —
        delegates to :meth:`~repro.core.engine.CountingEngine
        .apply_delta` (fine-grained invalidation + in-place delta updates
        of positive artefacts).

        Usage::

            delta = db.insert_facts("Rated", src, dst, {"rating": vals})
            report = strategy.apply_delta(delta)
        """
        return self.engine.apply_delta(delta, **kw)

    def family_ct_many(self, point: LatticePoint,
                       keeps: Sequence[Sequence[CtVar]]) -> list:
        """Fetch a whole round of family tables at once — both Möbius
        phases batched.

        The positive sub-queries every missing family's Möbius join will
        issue are enumerated up front (:func:`~repro.core.mobius
        .positive_queries`), filtered to what the positive policy would
        actually contract from data, and executed through the counting
        service in signature-bucketed stacked dispatches.  The *negative*
        phase of the missing families then runs through
        :func:`~repro.core.mobius.complete_ct_many`, whose block memo
        assembles each sub-pattern block the round shares once.  Results
        — including the recompute semantics
        under cache eviction — are numerically identical to per-family
        :meth:`family_ct`, which serves the final answers from the warmed
        ``"fam"`` cache."""
        keeps = [tuple(k) for k in keeps]
        if self._precount_complete or len(keeps) <= 1:
            return [self.family_ct(point, keep) for keep in keeps]
        cache = self.engine.cache
        missing = [keep for keep in keeps
                   if ("fam",) + _freeze(point, keep) not in cache]
        missing = list(dict.fromkeys(missing))
        if missing and self.provider.supports_batch_prefetch:
            queries = []
            for keep in missing:
                queries.extend(positive_queries(point, keep,
                                                self.use_butterfly))
            self.service().prefetch(self.provider, queries)
        fresh = {}
        if missing:
            with self.stats.disjoint_timer("negative"):
                tabs = complete_ct_many(
                    [(point, keep) for keep in missing], self.provider,
                    self.stats, use_butterfly=self.use_butterfly,
                    tracer=self.engine.tracer, dtype=self.engine.dtype)
            for keep, tab in zip(missing, tabs):
                cache.put(("fam",) + _freeze(point, keep), tab)
                fresh[keep] = tab      # return directly: under a tight
                                       # budget the puts may evict each
                                       # other, and a cache round-trip
                                       # would recompute per family
        return [fresh[keep] if keep in fresh
                else self.family_ct(point, keep) for keep in keeps]


class OnDemand(Strategy):
    _policy_cls = OnDemandPositives
    _warm_hists = True

    def __init__(self, **kw):
        super().__init__(name="ONDEMAND", **kw)


class Precount(Strategy):
    _policy_cls = CachedFullPositives
    _precount_complete = True

    def __init__(self, **kw):
        super().__init__(name="PRECOUNT", **kw)


class Hybrid(Strategy):
    _policy_cls = CachedFullPositives

    def __init__(self, **kw):
        super().__init__(name="HYBRID", **kw)


class TupleId(Strategy):
    """The paper's future-work pre-count variant: tuple-ID propagation."""

    _policy_cls = TupleIdPositives

    def __init__(self, **kw):
        super().__init__(name="TUPLEID", **kw)


STRATEGIES = {"PRECOUNT": Precount, "ONDEMAND": OnDemand, "HYBRID": Hybrid,
              "TUPLEID": TupleId}


def make_strategy(name: str, **kw) -> Strategy:
    return STRATEGIES[name.upper()](**kw)


# ---------------------------------------------------------------------------
# compatibility constructors for the pre-refactor provider classes (tests
# and external callers build these directly around complete_ct)
# ---------------------------------------------------------------------------

def _engine(db, stats, dtype):
    return CountingEngine(db, "dense", stats, dtype=dtype)


def _OnDemandProvider(db, stats, dtype=jnp.float32) -> OnDemandPositives:
    return OnDemandPositives(_engine(db, stats, dtype))


def _CachedPositiveProvider(db, stats, dtype=jnp.float32) -> CachedFullPositives:
    return CachedFullPositives(_engine(db, stats, dtype))


def _TupleIdProvider(db, stats, dtype=jnp.float32) -> TupleIdPositives:
    return TupleIdPositives(_engine(db, stats, dtype))
