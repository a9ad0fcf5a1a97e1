"""Counting engine: planner + executor + cache, shared by every strategy.

This is the machinery layer of the planner/executor/cache architecture.
A :class:`CountingEngine` owns

* the database handle,
* one :class:`~repro.core.executors.Executor` (``"dense"``, ``"sparse"``,
  or ``"sparse_sharded"`` — the mesh-parallel sparse backend from
  :mod:`repro.core.distributed`; in a horizontally partitioned deployment
  each shard of a :class:`~repro.core.database.ShardedDatabase` gets its
  own engine, see :mod:`repro.serve.router`),
* one :class:`~repro.core.cache.CtCache` (byte-budgeted LRU, shared by all
  namespaces: positives, messages, family tables, histograms),
* the shared :class:`~repro.core.contract.CostStats` instrumentation.

On top sit three *positive-table policies* — they all satisfy the
:class:`~repro.core.mobius.PositiveProvider` protocol consumed by the
Möbius join, and differ only in WHEN joins run and WHAT is cached:

* :class:`OnDemandPositives` — contract from raw data per request, memoise
  the result (the paper's post-counting data access pattern);
* :class:`CachedFullPositives` — contract each lattice point once at full
  attribute resolution up front; serve requests by projection
  (PRECOUNT / HYBRID pre-counting);
* :class:`TupleIdPositives` — cache per-(relationship, direction) message
  matrices up front (tuple-ID propagation, Yin et al. 2004); serve
  requests by projecting + recombining cached messages with zero edge
  table access.

Eviction is always safe: every policy recomputes on miss.

**Mutations.**  The engine is version-aware: cache entries are stamped
with the ``(db.version, dependency-tag set)`` they were computed under
(:func:`key_deps` derives the tags — relation names plus
``("attr", etype, name)`` attribute tuples — from the key itself, so no
call site changes), and :meth:`CountingEngine.apply_delta` reconciles the
cache after a :class:`~repro.core.database.FactDelta` or
:class:`~repro.core.database.AttrDelta` is applied to the store.
Reconciliation re-derives the paper's pre/post trade-off *over time*:
positive artefacts (``"pos"``/``"full"`` tables, ``"msg"`` matrices) are
multilinear in each relationship's edge multiset, so a small fact delta
**updates them in place** by counting just the delta edges — batched,
surviving same-executor entries run through ONE
:meth:`~repro.core.executors.Executor.positive_batch` dispatch over the
delta view.  Derived ``"fam"``/``"complete"`` tables are ALSO updated in
place: the Möbius transform is linear, so the positive block deltas push
through the butterfly (:func:`~repro.core.mobius.complete_ct_delta_many`,
on the host) and add onto the resident tables.  Above
the cost threshold the entry is dropped instead and recomputed on next
miss (post-counting the write).  Entries whose
dependency tags miss the delta — including every ``"hist"`` on a fact
delta — are retained untouched.  Attribute deltas invalidate exactly the
entries whose tags intersect the written ``(etype, attr)`` columns
(positive counts are *not* linear in attribute values, so there is no
in-place path) and retain everything else.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import (Dict, FrozenSet, Hashable, List, Optional, Sequence, Set,
                    Tuple)

import jax.numpy as jnp

from .cache import CtCache
from .contract import CostStats
from .ct import CtTable, on_host
from .database import AttrDelta, FactDelta, RelationalDB
from .executors import Executor, make_executor, project_columns
from .mobius import complete_ct_delta_many
from .plan import ContractionPlan, compile_plan_cached
from .variables import Atom, CtVar, LatticePoint, Var, attr_var, edge_var

# the executor's hop counters that ``contract`` puts on its spans
_HOP_COUNTERS = ("edges_resident", "edges_uploaded", "blocked_hops",
                 "scattered_hops")


def _attr_tags(keep) -> Set[Tuple]:
    """``("attr", etype, name)`` tags for the entity-attr axes of a keep
    tuple (edge-attr and rind axes are covered by the relation name)."""
    return {("attr", v.owner[0].etype, v.owner[1])
            for v in keep if v.kind == "attr"}


def key_deps(key: Tuple) -> Optional[FrozenSet[Hashable]]:
    """The dependency tags a cache entry was derived from, read off the
    key itself (every namespace embeds its pattern).  Tags mix relationship
    names (edge-table dependencies) with ``("attr", etype, name)`` tuples
    (entity-attribute-column dependencies) and the ``("attr*", etype)``
    wildcard for entries that read every attribute of a type:

    * ``("pos", executor, atoms, keep)`` — the atoms' relations + the kept
      entity-attr columns;
    * ``("full", executor, atoms)`` — the atoms' relations + the
      ``("attr*", etype)`` wildcard per pattern variable (full attribute
      resolution reads every column of each variable's type);
    * ``("fam", atoms, keep)`` / ``("complete", atoms, keep)`` — the
      atoms' relations + the kept entity-attr columns;
    * ``("msg", executor, atom, child, parent)`` — the atom's relation +
      ``("attr*", child_etype)`` (messages carry the child's full
      attribute resolution);
    * ``("hist", executor, var, keep)`` — the kept entity-attr columns
      (no relation tags: histograms are immune to fact deltas; entity
      table sizes are immutable);
    * anything else — ``None`` (unknown; invalidation drops it
      conservatively).
    """
    try:
        ns = key[0]
        if ns == "pos":
            return frozenset({a.rel for a in key[2]} | _attr_tags(key[3]))
        if ns == "full":
            etypes = {v.etype for a in key[2] for v in (a.src, a.dst)}
            return frozenset({a.rel for a in key[2]}
                             | {("attr*", et) for et in etypes})
        if ns in ("fam", "complete"):
            return frozenset({a.rel for a in key[1]} | _attr_tags(key[2]))
        if ns == "msg":
            return frozenset({key[2].rel, ("attr*", key[3].etype)})
        if ns == "hist":
            return frozenset(_attr_tags(key[3]))
    except (TypeError, AttributeError, IndexError):
        pass
    return None


@dataclass
class DeltaReport:
    """What one :meth:`CountingEngine.apply_delta` reconciliation did to
    the cache: entries refreshed in place (``updated``), dropped
    (``invalidated``) and left untouched (``retained``)."""

    rel: str
    op: str
    num_edges: int
    updated: int = 0
    invalidated: int = 0
    retained: int = 0
    version: int = 0

    def as_dict(self) -> dict:
        return dict(rel=self.rel, op=self.op, num_edges=self.num_edges,
                    updated=self.updated, invalidated=self.invalidated,
                    retained=self.retained, version=self.version)


class CountingEngine:
    """Shared planner/executor/cache machinery."""

    def __init__(self, db: RelationalDB, executor="dense",
                 stats: Optional[CostStats] = None,
                 cache: Optional[CtCache] = None,
                 cache_budget_bytes: Optional[int] = None,
                 dtype=jnp.float32):
        self.db = db
        self.stats = stats if stats is not None else CostStats()
        self.executor: Executor = (executor if isinstance(executor, Executor)
                                   else make_executor(executor, dtype=dtype))
        # the request tracer follows the executor, the long-lived object
        # CountingService.set_tracer wires, so an engine built on it (a
        # strategy's pre-count) is traced from its first contraction
        self.tracer = self.executor.tracer
        self.cache = cache if cache is not None else CtCache(
            cache_budget_bytes, self.stats)
        if cache is None:
            self.cache.tracer = self.tracer
        # freshness stamps: every entry records the relations it depends on
        # (derived from the key, so no call-site changes) and the store
        # version it was computed under
        self.cache.deps_fn = key_deps
        self.cache.version_fn = lambda: self.db.version
        self.dtype = self.executor.dtype     # the join subtracts in it too
        # one rows-counted set per engine: policies AND the counting
        # service share artefact key namespaces ("pos"/"full"/...), so
        # Table 5's "once per distinct artefact" accounting must be shared
        # too, or a service-computed table recomputed by a policy after
        # eviction would be counted twice
        self.rows_counted: Set[Tuple] = set()
        # default-keep memo: all_ct_vars walks the schema per call and a
        # serve flood resolves keep=None for the same points every round
        self._keep_cache: Dict[Tuple, Tuple[CtVar, ...]] = {}

    def count_rows_once(self, key: Tuple, tab: CtTable) -> None:
        if key not in self.rows_counted:
            self.rows_counted.add(key)
            self.stats.ct_rows += tab.nnz_rows(self.tracer)

    def plan(self, point: LatticePoint,
             keep: Optional[Sequence[CtVar]] = None) -> ContractionPlan:
        if keep is None:
            keep = self._keep_cache.get(point.atoms)
            if keep is None:
                keep = tuple(point.all_ct_vars(self.db.schema,
                                               include_rind=False))
                self._keep_cache[point.atoms] = keep
        return compile_plan_cached(self.db.schema, point, tuple(keep))

    def contract(self, point: LatticePoint,
                 keep: Optional[Sequence[CtVar]] = None) -> CtTable:
        """Positive ct-table straight from the data (counts as JOIN work),
        inside a ``count.positive`` span of one table.  The span carries
        the executor's hop counters: ``edges_resident``, hops whose edge
        columns were already on the device, ``edges_uploaded``, hops
        that had to upload them, ``blocked_hops``, hops that ran the
        blocked kernel over a parent-sorted layout, and
        ``scattered_hops``, hops that scattered flat segment ids."""
        tr = self.tracer
        ex = self.executor
        with tr.span("count.positive") as sp:
            plan = self.plan(point, keep)
            if not tr.enabled:
                return ex.positive(self.db, plan, self.stats)
            sp.set(tables=1, hops=plan.hops)
            before = [getattr(ex, c) for c in _HOP_COUNTERS]
            tab = ex.positive(self.db, plan, self.stats)
            sp.set(**{c: getattr(ex, c) - b
                      for c, b in zip(_HOP_COUNTERS, before)})
            return tab

    def hist(self, var: Var, keep: Tuple[CtVar, ...]) -> CtTable:
        """A variable's histogram over ``keep``, cached on the host in
        float64: the Möbius join multiplies histograms into its blocks
        there."""
        key = ("hist", self.executor.name, var, tuple(keep))
        hit = self.cache.get(key)
        if hit is None:
            hit = self.cache.put(key, on_host(self.executor.hist(
                self.db, var, tuple(keep), self.stats), self.tracer))
        return hit

    # -- delta count maintenance --------------------------------------------
    def apply_delta(self, delta,
                    max_update_fraction: float = 0.25) -> DeltaReport:
        """Reconcile the cache after ``delta`` was applied to ``self.db``.

        Accepts a :class:`~repro.core.database.FactDelta` (relationship
        writes) or an :class:`~repro.core.database.AttrDelta`
        (entity-attribute writes).  For a fact delta, walks the resident
        entries once and, per entry:

        * dependency tags miss ``delta.rel`` → **retained** untouched
          (this is the fine-grained invalidation: a write to one relation
          leaves every other relation's artefacts hot);
        * positive artefact (``"pos"``/``"full"`` table, ``"msg"``
          matrix) and the delta is *small* (``delta.num_edges <=
          max_update_fraction *`` the relation's post-delta edge count) →
          **updated in place**: the entry's own contraction plan runs over
          a delta view of the database (just the changed edges) and the
          result is added/subtracted — exact, because positive counts are
          multilinear in each relationship's edge multiset and lattice
          patterns use distinct relations.  All surviving ``"pos"`` /
          ``"full"`` entries go through ONE
          :meth:`~repro.core.executors.Executor.positive_batch` dispatch
          (grouped by plan signature internally) instead of one dispatch
          per entry;
        * derived ``"fam"``/``"complete"`` table and the delta is small →
          **updated in place through the butterfly**: the Möbius transform
          is linear, so the block deltas (delta-view contractions) push
          through :func:`~repro.core.mobius.complete_ct_delta_many` and
          add onto the resident tables, bit-exact vs recompute.  Entries
          whose kept indicators sum ``delta.rel`` out are provably
          unaffected and retained;
        * otherwise → **invalidated** (dropped; recomputed on next miss —
          the post-count fallback of the pre/post trade-off, applied to
          writes).

        An attribute delta has no in-place path (counts are not linear in
        attribute *values*): entries whose tags intersect the written
        ``(etype, attr)`` columns are invalidated, everything else —
        including every artefact over other types' attributes and all
        purely relational entries — is retained.

        Deltas must be reconciled in application order, one per call:
        ``delta.new_version`` must equal the store's current version
        (otherwise a second delta to an overlapping pattern would double
        the cross terms).

        Args:
            delta: the applied :class:`~repro.core.database.FactDelta` or
                :class:`~repro.core.database.AttrDelta`.
            max_update_fraction: in-place-update cost threshold, as a
                fraction of the relation's current edge count.

        Returns:
            A :class:`DeltaReport` with updated/invalidated/retained
            counts.

        Raises:
            ValueError: ``delta`` is not the store's latest version
                (reconcile each delta immediately after applying it, or
                fall back to ``cache.invalidate({delta.rel})``).

        Usage::

            delta = db.insert_facts("Rated", src, dst, {"rating": vals})
            report = engine.apply_delta(delta)
        """
        if delta.new_version != self.db.version:
            raise ValueError(
                f"delta version {delta.new_version} != store version "
                f"{self.db.version}; reconcile deltas in application order")
        if isinstance(delta, AttrDelta):
            return self._apply_attr_delta(delta)
        rel = delta.rel
        report = DeltaReport(rel, delta.op, delta.num_edges,
                             version=self.db.version)
        rel_edges = self.db.relations[rel].num_edges
        small = delta.num_edges <= max_update_fraction * max(rel_edges, 1)
        delta_db = delta.as_db(self.db) if small else None
        cache = self.cache
        ex = self.executor
        with self.tracer.span("engine.apply_delta", rel=rel, op=delta.op,
                              num_edges=delta.num_edges,
                              small=small) as sp:
            # one classification walk over a stable snapshot, then one
            # batched dispatch per artefact family
            pos_items: List[Tuple[Tuple, CtTable, ContractionPlan]] = []
            msg_keys: List[Tuple] = []
            fam_items: List[Tuple[Tuple, LatticePoint,
                                  Tuple[CtVar, ...]]] = []
            for key in cache.keys_snapshot():
                meta = cache.entry_meta(key)
                if meta is None:           # concurrently evicted
                    continue
                deps, _version = meta
                if deps is not None and rel not in deps:
                    report.retained += 1
                    continue
                bucket = self._classify_for_delta(key) if small else None
                if bucket is None:
                    if cache.discard(key):
                        report.invalidated += 1
                    continue
                kind, payload = bucket
                if kind == "pos":
                    pos_items.append((key,) + payload)
                elif kind == "msg":
                    msg_keys.append(key)
                else:
                    fam_items.append((key,) + payload)

            # (b) surviving positive tables: ONE batched dispatch over the
            # delta view, grouped by plan signature inside positive_batch
            if pos_items:
                with self.stats.timer("positive"), ex.local_mode():
                    dtabs = ex.positive_batch(
                        delta_db, [p for _, _, p in pos_items], self.stats)
                for (key, old, _), dtab in zip(pos_items, dtabs):
                    new = old + dtab.scale(delta.sign)
                    cache.put(key, new, nbytes=new.nbytes)
                    cache.count_delta_updates()
                    report.updated += 1

            # message matrices: per-relationship segment-sums (a different
            # primitive; at most a handful per relation survive the sweep)
            for key in msg_keys:
                new_val, nb = self._delta_update_msg(key, delta_db,
                                                     delta.sign)
                if new_val is not None:
                    cache.put(key, new_val, nbytes=nb)
                    cache.count_delta_updates()
                    report.updated += 1
                elif cache.discard(key):
                    report.invalidated += 1

            # (a) derived tables: push the block deltas through the
            # butterfly and add onto the resident tables
            if fam_items:
                provider = _DeltaPositives(self, delta_db)
                outs = complete_ct_delta_many(
                    [(point, keep) for _, point, keep in fam_items], rel,
                    provider, self.stats, self.dtype)
                for (key, _, _), (status, dtab) in zip(fam_items, outs):
                    if status == "zero":
                        report.retained += 1
                        continue
                    old = cache.peek(key) if status == "delta" else None
                    if old is None:
                        if cache.discard(key):
                            report.invalidated += 1
                        continue
                    new = old + dtab.scale(delta.sign)
                    cache.put(key, new, nbytes=new.nbytes)
                    cache.count_delta_updates()
                    report.updated += 1
            sp.set(updated=report.updated, invalidated=report.invalidated,
                   retained=report.retained)
        return report

    def _apply_attr_delta(self, delta: AttrDelta) -> DeltaReport:
        """Reconcile after an entity-attribute write: drop exactly the
        entries whose dependency tags intersect the written columns (or
        whose deps are unknown), retain the rest."""
        tags = delta.dep_tags()
        report = DeltaReport(delta.etype, "update_attrs", delta.num_rows,
                             version=self.db.version)
        cache = self.cache
        with self.tracer.span("engine.apply_delta", etype=delta.etype,
                              op="update_attrs",
                              num_rows=delta.num_rows) as sp:
            for key in cache.keys_snapshot():
                meta = cache.entry_meta(key)
                if meta is None:
                    continue
                deps, _version = meta
                if deps is not None and not (deps & tags):
                    report.retained += 1
                    continue
                if cache.discard(key):
                    report.invalidated += 1
            sp.set(updated=0, invalidated=report.invalidated,
                   retained=report.retained)
        return report

    def _classify_for_delta(self, key: Tuple):
        """Sort one affected resident entry into its delta-update family:
        ``("pos", (old, plan))`` for positive tables, ``("msg", ())`` for
        message matrices, ``("fam", (point, keep))`` for derived tables —
        or ``None`` when the entry cannot be delta-updated (unknown
        namespace, other executor's artefact, unplannable key) and must be
        dropped."""
        ns = key[0]
        ex = self.executor
        try:
            if ns == "pos" and key[1] == ex.name:
                old = self.cache.peek(key)
                if old is None:
                    return None
                plan = compile_plan_cached(self.db.schema,
                                           LatticePoint(key[2]),
                                           tuple(key[3]))
                return "pos", (old, plan)
            if ns == "full" and key[1] == ex.name:
                old = self.cache.peek(key)
                if old is None:
                    return None
                return "pos", (old, self.plan(LatticePoint(key[2]), None))
            if ns == "msg" and key[1] == ex.name:
                return "msg", ()
            if ns in ("fam", "complete"):
                return "fam", (LatticePoint(key[1]), tuple(key[2]))
        except (KeyError, ValueError, TypeError):
            pass
        return None

    def _delta_update_msg(self, key: Tuple, delta_db: RelationalDB,
                          sign: int) -> Tuple[Optional[object],
                                              Optional[int]]:
        """Tuple-ID message matrices are per-relationship segment-sums —
        linear in the edge list by construction, so the delta hop simply
        adds on."""
        _, _, atom, child, parent = key
        hit = self.cache.peek(key)
        if hit is None:
            return None, None
        m, mvars = hit
        schema = self.db.schema
        cattrs = tuple(attr_var(child, a.name, a.card)
                       for a in schema.entity(child.etype).attrs)
        rel_t = schema.relationship(atom.rel)
        eattrs = tuple(edge_var(rel_t.name, a.name, a.card)
                       for a in rel_t.attrs)
        ex = self.executor
        with self.stats.timer("positive"), ex.local_mode():
            dm, dvars = ex.leaf_hop(delta_db, atom, child, parent,
                                    cattrs, eattrs, self.stats)
        if tuple(dvars) != tuple(mvars):
            return None, None          # layout drifted: drop instead
        new_m = m + sign * dm
        return (new_m, tuple(mvars)), int(new_m.nbytes)


class _DeltaPositives:
    """Positive provider over a delta view, for
    :func:`~repro.core.mobius.complete_ct_delta_many`: contractions hit
    the delta edges only (exact per-block deltas, by multilinearity) while
    histograms serve FULL values through the engine's cache (the delta
    view shares the entity tables, so full histograms are exactly the
    unchanged factors of the delta's product form).  Results memoise
    per-call only — delta-view positives must never land in the real
    cache."""

    def __init__(self, engine: CountingEngine, delta_db: RelationalDB):
        self.engine = engine
        self.delta_db = delta_db
        self._memo: Dict[Tuple, CtTable] = {}

    def positive(self, point: LatticePoint,
                 keep: Tuple[CtVar, ...]) -> CtTable:
        key = (point.atoms, tuple(keep))
        hit = self._memo.get(key)
        if hit is None:
            eng = self.engine
            plan = compile_plan_cached(eng.db.schema, point, tuple(keep))
            with eng.stats.timer("positive"), eng.executor.local_mode():
                hit = eng.executor.positive(self.delta_db, plan, eng.stats)
            self._memo[key] = hit
        return hit

    def hist(self, var: Var, keep: Tuple[CtVar, ...]) -> CtTable:
        return self.engine.hist(var, keep)


class _Policy:
    """Base: delegate histograms; subclasses implement ``positive``.

    All data-access work (contractions, message propagation) is timed here
    under ``time_positive`` — including eviction-driven *recomputes* — so
    the Fig. 3 decomposition stays truthful under a cache budget.
    ``ct_rows`` (Table 5) is bumped once per distinct artefact, not per
    recompute."""

    def __init__(self, engine: CountingEngine):
        self.engine = engine

    def _count_rows_once(self, key: Tuple, tab: CtTable) -> None:
        self.engine.count_rows_once(key, tab)

    def hist(self, var: Var, keep: Tuple[CtVar, ...]) -> CtTable:
        return self.engine.hist(var, keep)

    def precompute(self, lattice: Sequence[LatticePoint]) -> None:
        pass

    # -- serve-layer integration --------------------------------------------
    supports_batch_prefetch = False    # callers skip query enumeration when
                                       # a policy can never batch (TUPLEID)

    def batchable_misses(self, queries: Sequence[Tuple[LatticePoint,
                                                       Tuple[CtVar, ...]]]
                         ) -> List[Tuple[LatticePoint,
                                         Optional[Tuple[CtVar, ...]]]]:
        """Of the positive queries a Möbius join is about to issue, the
        deduplicated subset this policy would contract *from data* on miss
        — i.e. what a batching service should execute as one
        signature-bucketed dispatch.  Policies whose misses are not plan
        contractions (tuple-ID message recombination) return []."""
        return []

    def absorb(self, point: LatticePoint,
               keep: Optional[Tuple[CtVar, ...]], tab: CtTable) -> None:
        """Accept a service-computed positive table for a query previously
        reported by :meth:`batchable_misses` (same caching + row
        accounting as the policy's own miss path)."""
        raise NotImplementedError


class OnDemandPositives(_Policy):
    """Contract positives from the database per request (counts JOINs);
    memoised in the shared cache (the paper's post-count cache)."""

    supports_batch_prefetch = True

    def _key(self, point: LatticePoint, keep: Tuple[CtVar, ...]) -> Tuple:
        return ("pos", self.engine.executor.name, point.atoms, tuple(keep))

    def positive(self, point: LatticePoint,
                 keep: Tuple[CtVar, ...]) -> CtTable:
        eng = self.engine
        key = self._key(point, keep)
        hit = eng.cache.get(key)
        if hit is None:
            with eng.stats.timer("positive"):   # the per-family JOIN cost
                hit = eng.contract(point, keep)
            self._count_rows_once(key, hit)
            eng.cache.put(key, hit)
        return hit

    def batchable_misses(self, queries):
        out, seen = [], set()
        for point, keep in queries:
            key = self._key(point, keep)
            if key not in self.engine.cache and key not in seen:
                seen.add(key)
                out.append((point, tuple(keep)))
        return out

    def absorb(self, point, keep, tab):
        key = self._key(point, keep)
        self._count_rows_once(key, tab)
        self.engine.cache.put(key, tab)


class CachedFullPositives(_Policy):
    """Serve positives by *projection* from full-attribute positive tables
    contracted once per lattice point — zero data access afterwards
    (HYBRID / PRECOUNT).  Evicted entries are re-contracted on miss.

    A full table is read to the host once, as it is contracted, and
    cached there in float64 (its bytes count against the cache budget):
    its cells fit the executor's dtype, while a projection sums them past
    float32's 2**24, and stays exact on the host."""

    supports_batch_prefetch = True

    def precompute(self, lattice: Sequence[LatticePoint]) -> None:
        for point in lattice:
            self._full(point)

    def _full_key(self, point: LatticePoint) -> Tuple:
        # keyed by the atoms (not just the rel set) so the delta path can
        # recompile the exact plan the cached table came from
        return ("full", self.engine.executor.name, point.atoms)

    def _full(self, point: LatticePoint) -> CtTable:
        eng = self.engine
        key = self._full_key(point)
        hit = eng.cache.get(key)
        if hit is None:
            with eng.stats.timer("positive"):
                hit = on_host(eng.contract(point, None), eng.tracer)
            self._count_rows_once(key, hit)
            eng.cache.put(key, hit)
        return hit

    def batchable_misses(self, queries):
        # misses here are evicted full-resolution tables: one (point, None)
        # re-contraction per distinct sub-point no longer resident
        out, seen = [], set()
        for point, _ in queries:
            key = self._full_key(point)
            if key not in self.engine.cache and key not in seen:
                seen.add(key)
                out.append((point, None))
        return out

    def absorb(self, point, keep, tab):
        key = self._full_key(point)
        tab = on_host(tab, self.engine.tracer)
        self._count_rows_once(key, tab)
        self.engine.cache.put(key, tab)

    def positive(self, point: LatticePoint,
                 keep: Tuple[CtVar, ...]) -> CtTable:
        # NOTE §Perf H3 it.3: memoising these projections by (atoms, keep)
        # was tried and REFUTED — CtVar-tuple hashing overhead exceeded the
        # projection cost at every dataset size measured.
        return self._full(point).project(keep)


class TupleIdPositives(_Policy):
    """Positive tables via tuple-ID propagation (the paper's 'Pre-Count
    Variants' future-work section, realised in tensors).

    ``precompute`` caches, per (relationship, direction), the full-resolution
    message matrix ``M[parent_entity, D_child_attrs x D_edge_attrs]`` — the
    mass each parent node receives through that relationship.  A family
    positive is then a pure contraction of cached entity-indexed matrices
    (column projection + root reduce): edge tables are never touched again.
    Cost profile per the paper: scales well in predicates (one matrix per
    relationship), less well in rows (matrices are entity-indexed)."""

    def _full_resolution(self, atom: Atom, child: Var
                         ) -> Tuple[Tuple[CtVar, ...], Tuple[CtVar, ...]]:
        schema = self.engine.db.schema
        cattrs = tuple(attr_var(child, a.name, a.card)
                       for a in schema.entity(child.etype).attrs)
        rel = schema.relationship(atom.rel)
        eattrs = tuple(edge_var(rel.name, a.name, a.card) for a in rel.attrs)
        return cattrs, eattrs

    def _msg(self, atom: Atom, child: Var, parent: Var):
        eng = self.engine
        # keyed by the full atom (not just the rel name): the delta path
        # re-runs the hop, and for self-relationships the atom carries the
        # direction the message was propagated in
        key = ("msg", eng.executor.name, atom, child, parent)
        hit = eng.cache.get(key)
        if hit is None:
            cattrs, eattrs = self._full_resolution(atom, child)
            with eng.stats.timer("positive"):
                m, mvars = eng.executor.leaf_hop(eng.db, atom, child, parent,
                                                 cattrs, eattrs, eng.stats)
            hit = eng.cache.put(key, (m, tuple(mvars)), nbytes=int(m.nbytes))
        return hit

    def precompute(self, lattice: Sequence[LatticePoint]) -> None:
        seen: Set[Tuple] = set()
        for point in lattice:
            for atom in point.atoms:
                for child, parent in ((atom.src, atom.dst),
                                      (atom.dst, atom.src)):
                    if (atom.rel, child, parent) not in seen:
                        seen.add((atom.rel, child, parent))
                        self._msg(atom, child, parent)

    def positive(self, point: LatticePoint,
                 keep: Tuple[CtVar, ...]) -> CtTable:
        eng = self.engine
        keep = tuple(keep)
        plan = eng.plan(point, keep)
        factors: List[Tuple[jnp.ndarray, Tuple[CtVar, ...]]] = []
        for hop in plan.root.hops:
            if hop.is_leaf_hop:
                m, mvars = self._msg(hop.atom, hop.child, hop.parent)
                factors.append(project_columns(m, mvars, keep))
            else:   # deeper subtree (chains of length > 2): propagate live
                factors.append(eng.executor.hop_message(eng.db, hop,
                                                        eng.stats))
        return eng.executor.root_reduce(eng.db, plan.root.own, factors,
                                        keep, eng.stats)


POSITIVE_POLICIES = {
    "ondemand": OnDemandPositives,
    "cached_full": CachedFullPositives,
    "tupleid": TupleIdPositives,
}
