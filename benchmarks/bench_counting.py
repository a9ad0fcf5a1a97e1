"""Counting-strategy benchmarks — one function per paper table/figure.

The paper's experiment (Figs. 3-4, Table 5) measures ct-table construction
inside a FACTORBASE structure-learning run, per caching strategy.  The search
loop itself is strategy-independent (the same family stream is scored), so we
benchmark each strategy against a *fixed, deterministic family workload*:
``prepare()`` (the pre-search phase) followed by ``family_ct`` + BDeu for an
enumerated set of (child, parents) families per lattice point.  This isolates
exactly the quantity the paper reports — ct construction time — without the
hill-climb's move-evaluation noise.

Each (dataset x strategy) run yields all three artefacts at once:
  * fig3_runtime  — time decomposition metadata / positive ct / negative ct
  * fig4_memory   — peak cache footprint (resident ct bytes)
  * table5_sizes  — summed family-ct rows vs the global PRECOUNT ct rows

plus the serve-layer dimensions:
  * service_flood — same-signature query flood, per-query executor
    dispatch vs the CountingService's signature-bucketed stacked path
    (the serve subsystem's headline speedup).
  * negative_flood — same-signature COMPLETE-CT flood (positive + Möbius
    negative phase): per-family ``complete_ct`` dispatch vs the
    service's fully batched complete path (stacked positives + one
    butterfly transform per shape group).
  * sharded_flood (``--shards``) — the same flood against a horizontally
    hash-partitioned database behind the CountingRouter (one service per
    shard, counts merged at the front-end) vs the single-database
    service, sparse executor on both sides.
  * tenant_flood — a multi-tenant fleet (N logical databases behind one
    TenantRegistry, tiered GREEN/YELLOW/RED workloads): per-tenant
    serial dispatch vs cross-tenant batched dispatch (same-shape plans
    from different tenants stacked into one jit).
  * mutation_flood — an insert-heavy write flood against warmed caches:
    delta count maintenance (fine-grained invalidation + in-place
    updates over just the delta edges) vs recount-from-scratch (the
    pre-mutations freshness model: every write flushes the cache and the
    next read re-contracts from raw data).

Output layout: ``results/bench/counting.json`` is the ONE canonical
artifact (runs, paper views, flood records, and the ``trajectory``
section).  ``BENCH_counting.json`` at the repo root is *derived* from the
trajectory section — new rows are appended to whatever is already
recorded there, so the file accumulates the cross-PR perf trajectory.
"""

from __future__ import annotations

import itertools
import json
import statistics
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.bdeu import family_score
from repro.core.contract import CostStats
from repro.core.database import (PAPER_DATASETS, RelationalDB,
                                 paper_benchmark_db, synth_db)
from repro.core.engine import CountingEngine
from repro.core.schema import Attribute, EntityType, Relationship, Schema
from repro.core.strategies import STRATEGIES, make_strategy
from repro.core.variables import build_lattice

# Per-dataset scale factors: keep CPU wall-time per (dataset x strategy) run
# in the tens of seconds while preserving the paper's size *ordering*
# (UW < ... < VisualGenome).  --scale multiplies these.
DEFAULT_SCALES: Dict[str, float] = {
    "UW": 1.0, "Mondial": 1.0, "Mutagenesis": 1.0, "Hepatitis": 1.0,
    "MovieLens": 1.0, "Financial": 0.4, "IMDb": 0.15, "VisualGenome": 0.05,
}
# ONDEMAND re-runs the JOINs per family; the paper reports it timing out on
# the two largest databases.  We enforce the same behaviour with a soft
# per-run budget (seconds) checked between families.
TIME_BUDGET_S = 300.0


def family_workload(db: RelationalDB, lattice, max_parents: int = 3,
                    per_point: int = 400) -> List[Tuple]:
    """Deterministic stream of (point, keep) families, mimicking what
    hill-climbing generates: every child with parent sets of size 0..k,
    round-robin over children, capped per lattice point.  The cap is sized
    so each point sees a realistic search stream (hundreds of families) —
    this is what makes ONDEMAND re-run its JOINs, as in the paper."""
    out: List[Tuple] = []
    for point in lattice:
        nodes = list(point.all_ct_vars(db.schema, include_rind=True))
        fams = []
        for child in nodes:
            others = [v for v in nodes if v != child]
            for k in range(0, max_parents + 1):
                for parents in itertools.combinations(others[:7], k):
                    fams.append((point, tuple(sorted(parents)) + (child,)))
        # interleave children so truncation keeps diversity
        fams.sort(key=lambda f: (len(f[1]), str(f[1][-1])))
        out.extend(fams[:per_point])
    return out


@dataclass
class RunRecord:
    dataset: str
    strategy: str
    executor: str
    rows: int
    families: int
    completed: bool
    wall_s: float
    time_metadata: float
    time_positive: float
    time_negative: float
    joins: int
    rows_scanned: int
    peak_bytes: int
    ct_rows: int

    def as_dict(self) -> dict:
        return self.__dict__.copy()


def run_one(name: str, strategy_name: str, scale: Optional[float] = None,
            budget_s: float = TIME_BUDGET_S, seed: int = 0,
            executor: str = "dense",
            cache_budget_bytes: Optional[int] = None) -> RunRecord:
    scale = DEFAULT_SCALES[name] if scale is None else scale
    db = paper_benchmark_db(name, seed=seed, scale=scale)
    lattice = build_lattice(db.schema, max_length=2)
    work = family_workload(db, lattice)

    strat = make_strategy(strategy_name, executor=executor,
                          cache_budget_bytes=cache_budget_bytes)

    t0 = time.perf_counter()
    completed = True
    strat.prepare(db, lattice)
    done = 0
    for point, keep in work:
        if time.perf_counter() - t0 > budget_s:
            completed = False            # the paper's "exceeded runtime limit"
            break
        tab = strat.family_ct(point, keep)
        family_score(tab, keep[-1])
        done += 1
    wall = time.perf_counter() - t0
    st = strat.stats
    return RunRecord(
        dataset=name, strategy=strategy_name, executor=executor,
        rows=db.total_rows,
        families=done, completed=completed, wall_s=round(wall, 2),
        time_metadata=round(st.time_metadata, 3),
        time_positive=round(st.time_positive, 3),
        time_negative=round(st.time_negative, 3),
        joins=st.joins, rows_scanned=st.rows_scanned,
        peak_bytes=st.peak_bytes, ct_rows=st.ct_rows)


def run_all(datasets: Sequence[str] = PAPER_DATASETS,
            strategies: Sequence[str] = ("PRECOUNT", "ONDEMAND", "HYBRID"),
            scale: Optional[float] = None,
            budget_s: float = TIME_BUDGET_S,
            executors: Sequence[str] = ("dense", "sparse"),
            cache_budget_bytes: Optional[int] = None) -> List[RunRecord]:
    recs = []
    for name in datasets:
        for s in strategies:
            for ex in executors:
                r = run_one(name, s, scale=scale, budget_s=budget_s,
                            executor=ex,
                            cache_budget_bytes=cache_budget_bytes)
                flag = "" if r.completed else "  [TIMEOUT]"
                print(f"[counting] {name:13s} {s:9s} {ex:6s} "
                      f"wall={r.wall_s:7.2f}s "
                      f"meta={r.time_metadata:6.2f} pos={r.time_positive:6.2f} "
                      f"neg={r.time_negative:6.2f} joins={r.joins:5d} "
                      f"peakMB={r.peak_bytes / 1e6:9.2f}{flag}", flush=True)
                recs.append(r)
    return recs


# ------------------------------------------------------------- paper views --

def fig3_runtime(recs: List[RunRecord]) -> List[dict]:
    """Fig. 3: stacked time decomposition per (dataset, strategy, executor)."""
    return [{"dataset": r.dataset, "strategy": r.strategy,
             "executor": r.executor,
             "metadata_s": r.time_metadata, "positive_s": r.time_positive,
             "negative_s": r.time_negative,
             "total_s": round(r.time_metadata + r.time_positive
                              + r.time_negative, 3),
             "completed": r.completed} for r in recs]


def fig4_memory(recs: List[RunRecord]) -> List[dict]:
    """Fig. 4: peak resident ct-cache bytes per (dataset, strategy,
    executor)."""
    return [{"dataset": r.dataset, "strategy": r.strategy,
             "executor": r.executor,
             "peak_mb": round(r.peak_bytes / 1e6, 3)} for r in recs]


def table5_sizes(recs: List[RunRecord]) -> List[dict]:
    """Table 5: summed family-ct rows (ONDEMAND/HYBRID) vs global-ct rows
    (PRECOUNT) per dataset (first executor seen; ct sizes are
    backend-invariant)."""
    by = {}
    for r in recs:
        by.setdefault((r.dataset, r.strategy), r)
    out = []
    for name in dict.fromkeys(r.dataset for r in recs):
        row = {"dataset": name}
        h = by.get((name, "HYBRID"))
        p = by.get((name, "PRECOUNT"))
        if h:
            row["ct_family_rows"] = h.ct_rows
        if p:
            row["ct_database_rows"] = p.ct_rows
        out.append(row)
    return out


def bench_trajectory(recs: List[RunRecord]) -> List[dict]:
    """The cross-PR perf trajectory: (strategy × dataset × executor) →
    wall time / peak bytes / ct rows.  Written to BENCH_counting.json."""
    return [{"dataset": r.dataset, "strategy": r.strategy,
             "executor": r.executor, "wall_s": r.wall_s,
             "peak_bytes": r.peak_bytes, "ct_rows": r.ct_rows,
             "completed": r.completed} for r in recs]


# ------------------------------------------------------- serve dimension --

def _flood_db(n_rels: int, edges: int, seed: int = 0) -> RelationalDB:
    """``n_rels`` identically-shaped relationships: every single-atom
    lattice point compiles to a stack-compatible plan — the ideal
    same-signature flood (symmetric schemas like VisualGenome's predicate
    sets are the realistic analogue)."""
    att = lambda n, c=3: Attribute(n, c)
    ents = (EntityType("fa", 400, (att("a0"), att("a1"))),
            EntityType("fb", 300, (att("b0"),)))
    rels = tuple(Relationship(f"F{i}", "fa", "fb", (att(f"e{i}"),))
                 for i in range(n_rels))
    schema = Schema(ents, rels)
    return synth_db(schema, {f"F{i}": edges for i in range(n_rels)},
                    seed=seed)


def bench_service_flood(n_rels: int = 16, edges: int = 2000,
                        rounds: int = 5,
                        executors: Sequence[str] = ("dense", "sparse"),
                        seed: int = 0) -> List[dict]:
    """Same-signature query flood: per-query executor dispatch vs the
    counting service's signature-bucketed stacked execution.

    Each round answers the same ``n_rels`` distinct positive queries cold
    (the ct-cache is cleared between rounds, so every round re-executes);
    the batched path keeps its jitted vmapped evaluator across rounds the
    way a long-running service would.  Reports queries/s per mode and the
    batched-over-per-query speedup.
    """
    from repro.serve import CountingService

    db = _flood_db(n_rels, edges, seed=seed)
    lattice = build_lattice(db.schema, 1)
    config = f"flood{n_rels}x{edges}r{rounds}"
    out: List[dict] = []
    for ex in executors:
        eng = CountingEngine(db, ex, CostStats())
        plans = [eng.plan(p, None) for p in lattice]
        n_queries = rounds * len(plans)

        # ---- per-query dispatch (warm one round, then timed) -------------
        jax.block_until_ready([eng.executor.positive(db, p).counts
                               for p in plans])
        t0 = time.perf_counter()
        for _ in range(rounds):
            jax.block_until_ready([eng.executor.positive(db, p).counts
                                   for p in plans])
        wall_pq = time.perf_counter() - t0
        qps_pq = n_queries / wall_pq

        # ---- service-batched (same engine; cold cache every round) -------
        svc = CountingService(eng, max_batch_size=max(n_rels, 1))
        queries = [(p, None) for p in lattice]
        eng.cache.evict_all()
        jax.block_until_ready([t.counts for t in svc.count_many(queries)])
        t0 = time.perf_counter()
        for _ in range(rounds):
            eng.cache.evict_all()
            jax.block_until_ready([t.counts
                                   for t in svc.count_many(queries)])
        wall_b = time.perf_counter() - t0
        qps_b = n_queries / wall_b

        speedup = qps_b / qps_pq if qps_pq > 0 else float("inf")
        print(f"[flood] {config} {ex:6s} per_query={qps_pq:8.1f} q/s  "
              f"batched={qps_b:8.1f} q/s  speedup={speedup:5.2f}x",
              flush=True)
        for mode, wall, qps in (("per_query", wall_pq, qps_pq),
                                ("batched", wall_b, qps_b)):
            rec = {"bench": "service_flood", "config": config,
                   "dataset": "synthflood", "strategy": "SERVICE",
                   "executor": ex, "mode": mode, "queries": n_queries,
                   "wall_s": round(wall, 4), "qps": round(qps, 1),
                   "completed": True}
            if mode == "batched":
                rec["speedup_vs_per_query"] = round(speedup, 3)
            out.append(rec)
    return out


def bench_tenant_flood(n_tenants: int = 4, edges: int = 800,
                       rounds: int = 3,
                       executors: Sequence[str] = ("dense", "sparse"),
                       seed: int = 0) -> List[dict]:
    """Multi-tenant fleet flood: per-tenant serial dispatch vs the
    registry's cross-tenant batched dispatch.

    ``n_tenants`` logical databases share one schema (the tiered
    GREEN/YELLOW/RED supply-chain pattern space from
    ``benchmarks/workloads.py``) behind one
    :class:`~repro.serve.tenancy.TenantRegistry`.  Each round every
    tenant answers the full tiered mix cold (the shared cache is evicted
    between rounds).  The per-tenant baseline is STRONG — each tenant's
    ``count_many`` still signature-buckets and stacks within the
    tenant — so the measured speedup is purely the cross-tenant
    stacking win (same-shape plans from different tenants riding one
    jitted dispatch instead of one dispatch per tenant per shape).
    """
    try:
        from benchmarks.workloads import (supply_chain_schema,
                                          tenant_fleet, tiered_points)
    except ImportError:                 # run as a script from benchmarks/
        from workloads import (supply_chain_schema, tenant_fleet,
                               tiered_points)
    from repro.serve import TenantRegistry

    fleet = tenant_fleet(n_tenants, supply_chain_schema(), edges=edges,
                         seed=seed)
    schema = fleet[0][1].schema
    tiers = tiered_points(schema, 3)
    mix = tiers["GREEN"] + tiers["YELLOW"] + tiers["RED"]
    tier_counts = {t: len(v) for t, v in tiers.items()}
    config = f"tenants{n_tenants}x{edges}r{rounds}"
    out: List[dict] = []
    for ex in executors:
        reg = TenantRegistry(executor=ex)
        for tid, db in fleet:
            reg.add_tenant(tid, db)
        tenant_qs = [(p, None) for p in mix]
        all_qs = [(tid, p, None) for tid, _ in fleet for p in mix]
        n_queries = rounds * len(all_qs)

        def serial_round():
            reg.cache.evict_all()
            for tid, _ in fleet:
                jax.block_until_ready(
                    [t.counts for t in
                     reg.tenant(tid).service.count_many(tenant_qs)])

        def cross_round():
            reg.cache.evict_all()
            jax.block_until_ready(
                [t.counts for t in reg.count_many(all_qs)])

        serial_round()                  # warm jits/staging for both modes
        cross_round()
        t0 = time.perf_counter()
        for _ in range(rounds):
            serial_round()
        wall_s = time.perf_counter() - t0
        qps_s = n_queries / wall_s
        t0 = time.perf_counter()
        for _ in range(rounds):
            cross_round()
        wall_c = time.perf_counter() - t0
        qps_c = n_queries / wall_c

        speedup = qps_c / qps_s if qps_s > 0 else float("inf")
        print(f"[tenants] {config} {ex:6s} "
              f"per_tenant={qps_s:8.1f} q/s  "
              f"cross_tenant={qps_c:8.1f} q/s  speedup={speedup:5.2f}x",
              flush=True)
        for mode, wall, qps in (("per_tenant", wall_s, qps_s),
                                ("cross_tenant", wall_c, qps_c)):
            rec = {"bench": "tenant_flood", "config": config,
                   "dataset": "synthfleet", "strategy": "REGISTRY",
                   "executor": ex, "mode": mode, "tenants": n_tenants,
                   "queries": n_queries, "tier_mix": tier_counts,
                   "wall_s": round(wall, 4), "qps": round(qps, 1),
                   "completed": True}
            if mode == "cross_tenant":
                rec["speedup_vs_per_tenant"] = round(speedup, 3)
            out.append(rec)
        reg.shutdown()
    return out


def bench_tiered_schedule(schemas: Sequence[str] = ("social", "fmcg",
                                                    "supply_chain"),
                          edges: int = 400, n_queries: int = 60,
                          rounds: int = 3, executor: str = "sparse",
                          seed: int = 0) -> List[dict]:
    """The paper's pre/post schedule choice, driven per complexity tier
    (the ``tiered`` trajectory dimension).

    Each example schema's tier-weighted query mix (``benchmarks/
    workloads.py``) is answered two ways on identical data:

    * **scheduled** — the counting strategy follows the tier: GREEN
      (single-atom) queries pre-count through ``PRECOUNT`` (complete
      table once, every projection free), RED (long/self-relationship
      chains) post-count through ``ONDEMAND`` (never materialise the
      expensive complete tables), YELLOW takes ``HYBRID``.
    * **hybrid** — the uniform baseline: every tier through one
      ``HYBRID`` strategy, the paper's default.

    Caches are evicted between rounds so every round re-executes both
    phases.  Reports queries/s per mode per schema and the
    scheduled-over-hybrid ratio.  This dimension is recorded, not gated:
    the paper's claim is that HYBRID dominates both pure schedules, so a
    ratio below 1 — per-tier scheduling losing to uniform hybrid — is
    the expected, paper-consistent outcome, and the trajectory keeps the
    measured margin honest across revisions.
    """
    try:
        from benchmarks.workloads import EXAMPLE_SCHEMAS, classify, query_mix
    except ImportError:                 # run as a script from benchmarks/
        from workloads import EXAMPLE_SCHEMAS, classify, query_mix

    schedule = {"GREEN": "PRECOUNT", "YELLOW": "HYBRID", "RED": "ONDEMAND"}
    out: List[dict] = []
    for name in schemas:
        schema = EXAMPLE_SCHEMAS[name]()
        db = synth_db(schema, {r.name: edges for r in schema.relationships},
                      seed=seed)
        lattice = build_lattice(schema, 3)
        mix = query_mix(schema, n_queries, seed=seed)
        # every occurrence projects a DIFFERENT random axis subset (all
        # indicators + some attrs): the realistic discovery read pattern
        # that pre-counting exists for — one complete table serves every
        # projection, while on-demand recounts per distinct keep
        import random as _random
        krng = _random.Random(seed + 1)
        queries = []
        for p in mix:
            axes = [v for v in p.all_ct_vars(schema, include_rind=True)
                    if v.kind != "edge"]
            rinds = [v for v in axes if v.kind == "rind"]
            attrs = [v for v in axes if v.kind == "attr"]
            chosen = (krng.sample(attrs, krng.randint(1, len(attrs)))
                      if attrs else [])
            keep = tuple(v for v in axes if v in rinds or v in chosen)
            queries.append((p, keep))
        tier_of = {p: classify(schema, p) for p in set(mix)}
        tier_counts: Dict[str, int] = {}
        for p in mix:
            tier_counts[tier_of[p]] = tier_counts.get(tier_of[p], 0) + 1
        config = f"tiered-{name}e{edges}n{n_queries}r{rounds}"

        by_tier = {}
        for tier, sname in schedule.items():
            st = make_strategy(sname, executor=executor)
            st.prepare(db, lattice)
            by_tier[tier] = st
        hy = make_strategy("HYBRID", executor=executor)
        hy.prepare(db, lattice)

        def scheduled_round():
            for st in by_tier.values():
                st.engine.cache.evict_all()
            jax.block_until_ready(
                [by_tier[tier_of[p]].family_ct(p, keep).counts
                 for p, keep in queries])

        def hybrid_round():
            hy.engine.cache.evict_all()
            jax.block_until_ready(
                [hy.family_ct(p, keep).counts for p, keep in queries])

        scheduled_round()               # warm jits for both modes
        hybrid_round()
        walls = {}
        for mode, fn in (("scheduled", scheduled_round),
                         ("hybrid", hybrid_round)):
            t0 = time.perf_counter()
            for _ in range(rounds):
                fn()
            walls[mode] = time.perf_counter() - t0
        total = rounds * len(mix)
        ratio = (walls["hybrid"] / walls["scheduled"]
                 if walls["scheduled"] > 0 else float("inf"))
        print(f"[tiered] {config} {executor:6s} "
              f"scheduled={total / walls['scheduled']:8.1f} q/s  "
              f"hybrid={total / walls['hybrid']:8.1f} q/s  "
              f"ratio={ratio:5.2f}x", flush=True)
        for mode in ("scheduled", "hybrid"):
            rec = {"bench": "tiered_schedule", "config": config,
                   "dataset": name, "strategy": ("SCHEDULE" if mode ==
                                                 "scheduled" else "HYBRID"),
                   "executor": executor, "mode": mode, "queries": total,
                   "tier_mix": tier_counts,
                   "wall_s": round(walls[mode], 4),
                   "qps": round(total / walls[mode], 1)
                   if walls[mode] > 0 else 0.0,
                   "completed": True}
            if mode == "scheduled":
                rec["ratio_vs_hybrid"] = round(ratio, 3)
            out.append(rec)
    return out


def bench_negative_flood(n_rels: int = 16, edges: int = 2000,
                         rounds: int = 5,
                         executors: Sequence[str] = ("dense", "sparse"),
                         seed: int = 0) -> List[dict]:
    """Same-signature complete-CT flood: per-family Möbius joins vs the
    service's fully batched complete path.

    Each query asks for the COMPLETE table (attribute + relationship
    indicator axes — the butterfly case the paper says must be
    post-counted).  The per-family baseline answers them one
    :func:`~repro.core.mobius.complete_ct` at a time (per-query positive
    contraction + per-query transform); the batched side routes the same
    flood through :meth:`~repro.serve.service.CountingService
    .complete_many` (stacked positive dispatches + ONE butterfly
    transform per shape group).  The ct-cache is cleared between rounds,
    so every round re-executes both phases.  Reports queries/s per mode
    and the batched-over-per-family speedup.
    """
    from repro.core.engine import OnDemandPositives
    from repro.core.mobius import complete_ct
    from repro.serve import CountingService

    db = _flood_db(n_rels, edges, seed=seed)
    lattice = build_lattice(db.schema, 1)
    # attr + indicator axes: a kept edge-attr axis would force the
    # blockwise join on both sides (complete_ct semantics, not batching)
    keeps = [tuple(v for v in p.all_ct_vars(db.schema, include_rind=True)
                   if v.kind != "edge") for p in lattice]
    queries = list(zip(lattice, keeps))
    n_queries = rounds * len(queries)
    config = f"negflood{n_rels}x{edges}r{rounds}"
    out: List[dict] = []
    for ex in executors:
        # ---- per-family dispatch (warm one round, then timed) ------------
        eng = CountingEngine(db, ex, CostStats())
        policy = OnDemandPositives(eng)

        def per_family_round():
            eng.cache.evict_all()
            jax.block_until_ready([complete_ct(p, k, policy).counts
                                   for p, k in queries])

        per_family_round()
        t0 = time.perf_counter()
        for _ in range(rounds):
            per_family_round()
        wall_pf = time.perf_counter() - t0
        qps_pf = n_queries / wall_pf

        # ---- service-batched complete path (cold cache every round) ------
        eng_b = CountingEngine(db, ex, CostStats())
        svc = CountingService(eng_b, max_batch_size=max(n_rels, 1))

        def batched_round():
            eng_b.cache.evict_all()
            jax.block_until_ready([t.counts
                                   for t in svc.complete_many(queries)])

        batched_round()
        t0 = time.perf_counter()
        for _ in range(rounds):
            batched_round()
        wall_b = time.perf_counter() - t0
        qps_b = n_queries / wall_b

        speedup = qps_b / qps_pf if qps_pf > 0 else float("inf")
        print(f"[negflood] {config} {ex:6s} per_family={qps_pf:8.1f} q/s  "
              f"batched={qps_b:8.1f} q/s  speedup={speedup:5.2f}x",
              flush=True)
        for mode, wall, qps in (("per_family", wall_pf, qps_pf),
                                ("batched", wall_b, qps_b)):
            rec = {"bench": "negative_flood", "config": config,
                   "dataset": "synthflood", "strategy": "SERVICE",
                   "executor": ex, "mode": mode, "queries": n_queries,
                   "wall_s": round(wall, 4), "qps": round(qps, 1),
                   "completed": True}
            if mode == "batched":
                rec["speedup_vs_per_family"] = round(speedup, 3)
            out.append(rec)
    return out


def bench_sharded_flood(n_shards: int = 2, n_rels: int = 16,
                        edges: int = 2000, rounds: int = 5,
                        seed: int = 0, trace: bool = False) -> List[dict]:
    """Sharded-vs-single sparse counting throughput (the ``--shards``
    dimension).

    The same cold-cache query flood is answered two ways: by one
    CountingService over the whole database, and by a CountingRouter over
    a ``n_shards``-way hash-partitioned copy (one service per shard,
    fan-out + count merging at the front-end).  Both sides run the sparse
    executor.  Reports queries/s per mode and the sharded-over-single
    ratio — on one host this measures the routing/merge overhead; across
    real hosts each shard scans 1/``n_shards`` of the edge rows.

    ``trace=True`` (the ``--trace`` flag) runs the sharded side with a
    request tracer (slow threshold 0, so every query is offered) and
    dumps the slow-query log — which queries were the tail, and which
    dispatch path answered them.
    """
    from repro.core.database import shard_database
    from repro.serve import CountingRouter, CountingService

    db = _flood_db(n_rels, edges, seed=seed)
    lattice = build_lattice(db.schema, 1)
    queries = [(p, None) for p in lattice]
    n_queries = rounds * len(queries)
    config = f"shard{n_shards}x{n_rels}x{edges}r{rounds}"
    out: List[dict] = []

    # Each round is timed on its own and the *median* round wall drives the
    # reported q/s: one flood round is only a few ms, so a single scheduler
    # hiccup or GC pause in a summed wall would swing the sharded/single
    # ratio by 2x.  ``wall_s`` in the records stays the summed wall.

    # ---- single-database service (the baseline) ----------------------------
    eng = CountingEngine(db, "sparse", CostStats())
    svc = CountingService(eng, max_batch_size=max(n_rels, 1))
    eng.cache.evict_all()
    jax.block_until_ready([t.counts for t in svc.count_many(queries)])
    walls: List[float] = []
    for _ in range(rounds):
        eng.cache.evict_all()
        t0 = time.perf_counter()
        jax.block_until_ready([t.counts for t in svc.count_many(queries)])
        walls.append(time.perf_counter() - t0)
    wall_single = sum(walls)
    qps_single = len(queries) / statistics.median(walls)

    # ---- sharded router ----------------------------------------------------
    sdb = shard_database(db, n_shards)
    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=1 << 15, slow_threshold_s=0.0)
    router = CountingRouter(sdb, executor="sparse",
                            max_batch_size=max(n_rels, 1),
                            tracer=tracer)
    jax.block_until_ready([t.counts for t in router.count_many(queries)])
    walls = []
    for _ in range(rounds):
        for e in router.engines:
            e.cache.evict_all()
        router.invalidate()      # keep measuring fan-out+merge, not the
        t0 = time.perf_counter()  # router's own result cache
        jax.block_until_ready([
            t.counts for t in router.count_many(queries)])
        walls.append(time.perf_counter() - t0)
    wall_sharded = sum(walls)
    qps_sharded = len(queries) / statistics.median(walls)

    ratio = qps_sharded / qps_single if qps_single > 0 else float("inf")
    rs = router.stats()["router"]
    print(f"[shards] {config} sparse single={qps_single:8.1f} q/s  "
          f"sharded={qps_sharded:8.1f} q/s  ratio={ratio:5.2f}x  "
          f"fanout={rs['fanout_requests']} merged={rs['merged_tables']}",
          flush=True)
    slow_dump: List[dict] = []
    if tracer is not None:
        slow_dump = tracer.slow.as_dicts()[:10]
        print(f"[shards] {config} slow-query log "
              f"(top {len(slow_dump)} of {tracer.slow.offered} offered, "
              f"{tracer.recorded} spans traced):", flush=True)
        for q in slow_dump:
            info = " ".join(f"{k}={v}" for k, v in q["info"].items())
            print(f"[shards]   {q['duration_s'] * 1e3:8.3f}ms "
                  f"{q['name']}  {info}", flush=True)
    for mode, wall, qps in (("single", wall_single, qps_single),
                            ("sharded", wall_sharded, qps_sharded)):
        rec = {"bench": "sharded_flood", "config": config,
               "dataset": "synthflood", "strategy": "ROUTER",
               "executor": "sparse", "mode": mode, "shards": n_shards,
               "queries": n_queries, "wall_s": round(wall, 4),
               "qps": round(qps, 1), "completed": True}
        if mode == "sharded":
            rec["ratio_vs_single"] = round(ratio, 3)
            if slow_dump:
                rec["slow_queries"] = slow_dump
        out.append(rec)
    return out


def _fresh_edge_batches(db: RelationalDB, rels: Sequence[str], rounds: int,
                        delta_edges: int, seed: int) -> List[dict]:
    """Pre-generated insert batches (new (src, dst) pairs + random attrs),
    identical across the modes being compared."""
    import numpy as np
    rng = np.random.default_rng(seed)
    have = {r: db.relations[r].pair_set() for r in rels}
    out: List[dict] = []
    for _ in range(rounds):
        per = {}
        for r in rels:
            tab = db.relations[r]
            ns = db.entities[tab.type.src].size
            nd = db.entities[tab.type.dst].size
            pairs = []
            while len(pairs) < delta_edges:
                s, d = int(rng.integers(ns)), int(rng.integers(nd))
                if (s, d) not in have[r]:
                    have[r].add((s, d))
                    pairs.append((s, d))
            per[r] = (np.array([p[0] for p in pairs], np.int32),
                      np.array([p[1] for p in pairs], np.int32),
                      {a.name: rng.integers(0, a.card, size=delta_edges)
                       .astype(np.int32) for a in tab.type.attrs})
        out.append(per)
    return out


def bench_mutation_flood(n_rels: int = 6, edges: int = 100000,
                         delta_edges: int = 128, rounds: int = 3,
                         executors: Sequence[str] = ("dense", "sparse"),
                         seed: int = 0) -> List[dict]:
    """Insert-heavy mutation flood: delta count maintenance vs
    recount-from-scratch (the ``mutflood`` trajectory dimension).

    The workload interleaves writes with reads against warmed caches:
    every write inserts ``delta_edges`` fresh edges into one
    relationship, then the full single-atom query set is re-read.  Two
    freshness models answer it:

    * **delta** — ``CountingService.insert_facts``: fenced write +
      fine-grained cache reconcile; the affected positive table is
      updated in place by one contraction over just the delta edges, and
      every read is a cache hit.
    * **recount** — the pre-mutations model: each write flushes the
      whole ct-cache (all-or-nothing invalidation was the only safe
      answer when entries carried no dependency metadata), so every
      read after a write re-contracts from the full edge lists.

    Both modes serve identical queries on identical data (same
    pre-generated edge batches).  Reports wall time and writes+reads/s
    per mode, and the delta-over-recount speedup.
    """
    from repro.serve import CountingService

    config = f"mutflood{n_rels}x{edges}d{delta_edges}r{rounds}"
    rels = [f"F{i}" for i in range(n_rels)]
    out: List[dict] = []
    for ex in executors:
        walls = {}
        for mode in ("delta", "recount"):
            db = _flood_db(n_rels, edges, seed=seed)
            batches = _fresh_edge_batches(db, rels, rounds, delta_edges,
                                          seed=seed + 1)
            eng = CountingEngine(db, ex, CostStats())
            svc = CountingService(eng, max_batch_size=max(n_rels, 1))
            lattice = build_lattice(db.schema, 1)
            queries = [(p, None) for p in lattice]
            jax.block_until_ready([t.counts                    # warm
                                   for t in svc.count_many(queries)])
            t0 = time.perf_counter()
            for rnd in batches:
                for r in rels:
                    src, dst, attrs = rnd[r]
                    if mode == "delta":
                        svc.insert_facts(r, src, dst, attrs)
                    else:
                        with svc.fence():
                            eng.db.insert_facts(r, src, dst, attrs)
                            eng.cache.invalidate()   # all-or-nothing flush
                    jax.block_until_ready(
                        [t.counts for t in svc.count_many(queries)])
            walls[mode] = time.perf_counter() - t0
        n_ops = rounds * n_rels * (1 + len(rels))    # writes + reads
        speedup = (walls["recount"] / walls["delta"]
                   if walls["delta"] > 0 else float("inf"))
        print(f"[mutflood] {config} {ex:6s} "
              f"delta={walls['delta']:7.3f}s  "
              f"recount={walls['recount']:7.3f}s  "
              f"speedup={speedup:5.2f}x", flush=True)
        for mode in ("delta", "recount"):
            rec = {"bench": "mutation_flood", "config": config,
                   "dataset": "synthflood", "strategy": "SERVICE",
                   "executor": ex, "mode": mode,
                   "queries": n_ops, "wall_s": round(walls[mode], 4),
                   "qps": round(n_ops / walls[mode], 1)
                   if walls[mode] > 0 else 0.0,
                   "completed": True}
            if mode == "delta":
                rec["speedup_vs_recount"] = round(speedup, 3)
            out.append(rec)
    return out


def bench_mutation_negative_flood(n_rels: int = 6, edges: int = 100000,
                                  delta_edges: int = 128, rounds: int = 3,
                                  executors: Sequence[str] = ("dense",
                                                              "sparse"),
                                  seed: int = 0) -> List[dict]:
    """Write-heavy flood over COMPLETE-CT reads: fused butterfly delta
    propagation vs flush-and-recount (the ``mutnegflood`` trajectory
    dimension).

    Same interleaving as :func:`bench_mutation_flood`, but every read
    asks for the complete table (attribute + indicator axes — the
    negative phase), served through :meth:`~repro.serve.service
    .CountingService.complete_many` into the ``"fam"`` cache namespace:

    * **delta** — ``CountingService.insert_facts``: the resident family
      tables are updated IN PLACE by pushing per-corner block deltas
      (contractions over just the delta edges) through ONE fused
      butterfly dispatch per (shape, perm) group; reads after a write
      are cache hits.
    * **recount** — the pre-delta model: each write flushes the whole
      ct-cache, so every read round re-runs the full Möbius join
      (positive contractions over the full edge lists + transform).

    Both modes serve identical queries on identical data.  Reports wall
    time and writes+reads/s per mode, and the delta-over-recount
    speedup — the headline number for "writes stop flushing the
    negative phase".
    """
    from repro.serve import CountingService

    config = f"mutnegflood{n_rels}x{edges}d{delta_edges}r{rounds}"
    rels = [f"F{i}" for i in range(n_rels)]
    out: List[dict] = []
    for ex in executors:
        walls = {}
        for mode in ("delta", "recount"):
            db = _flood_db(n_rels, edges, seed=seed)
            batches = _fresh_edge_batches(db, rels, rounds, delta_edges,
                                          seed=seed + 1)
            eng = CountingEngine(db, ex, CostStats())
            svc = CountingService(eng, max_batch_size=max(n_rels, 1))
            lattice = build_lattice(db.schema, 1)
            # attr + indicator axes: the butterfly-eligible complete CT
            queries = [(p, tuple(v for v in p.all_ct_vars(db.schema,
                                                          include_rind=True)
                                 if v.kind != "edge")) for p in lattice]
            jax.block_until_ready([t.counts                    # warm
                                   for t in svc.complete_many(queries)])
            t0 = time.perf_counter()
            for rnd in batches:
                for r in rels:
                    src, dst, attrs = rnd[r]
                    if mode == "delta":
                        svc.insert_facts(r, src, dst, attrs)
                    else:
                        with svc.fence():
                            eng.db.insert_facts(r, src, dst, attrs)
                            eng.cache.invalidate()   # all-or-nothing flush
                    jax.block_until_ready(
                        [t.counts for t in svc.complete_many(queries)])
            walls[mode] = time.perf_counter() - t0
        n_ops = rounds * n_rels * (1 + len(rels))    # writes + reads
        speedup = (walls["recount"] / walls["delta"]
                   if walls["delta"] > 0 else float("inf"))
        print(f"[mutnegflood] {config} {ex:6s} "
              f"delta={walls['delta']:7.3f}s  "
              f"recount={walls['recount']:7.3f}s  "
              f"speedup={speedup:5.2f}x", flush=True)
        for mode in ("delta", "recount"):
            rec = {"bench": "mutation_negative_flood", "config": config,
                   "dataset": "synthflood", "strategy": "SERVICE",
                   "executor": ex, "mode": mode,
                   "queries": n_ops, "wall_s": round(walls[mode], 4),
                   "qps": round(n_ops / walls[mode], 1)
                   if walls[mode] > 0 else 0.0,
                   "completed": True}
            if mode == "delta":
                rec["speedup_vs_recount"] = round(speedup, 3)
            out.append(rec)
    return out


def bench_discovery(dataset: str = "IMDb", scale: float = 0.05,
                    rounds: int = 3, seed: int = 0,
                    max_chain_length: int = 1, max_parents: int = 2,
                    strategy: str = "HYBRID") -> List[dict]:
    """Served vs local model-discovery throughput (the ``--discovery``
    dimension).

    The same hill-climbing discovery runs two ways on the IMDb-style
    schema: through a bare in-process strategy (the local oracle) and
    through a :class:`CountingService` (batched, coalesced, cached —
    the served path).  Each timed round drops the score memo but keeps
    the CT caches warm, so both modes redo identical BDeu scoring work
    over identical counts and the ratio isolates the serve layer's
    round-trip overhead on search traffic.  The two modes' timed rounds
    are interleaved and the reported ratio is the *median of per-pair*
    served/local families/s (each pair ran back-to-back, so ambient
    load cancels within a pair and the median drops pairs a scheduler
    blip hit one-sided — same reasoning as the tracing-overhead gate);
    per-mode rounds/s and families/s are best-of-``rounds``.  The
    perf-smoke gate requires ratio >= 0.9x.
    """
    from repro.discover import DiscoveryService
    from repro.serve import CountingService

    config = f"disc{dataset}s{scale}r{rounds}"
    out: List[dict] = []
    modes = ("local", "served")
    sigs: Dict[str, dict] = {}
    dsvcs: Dict[str, DiscoveryService] = {}
    for mode in modes:
        db = paper_benchmark_db(dataset, seed=seed, scale=scale)
        if mode == "local":
            dsvc = DiscoveryService(make_strategy(strategy), db=db,
                                    max_chain_length=max_chain_length,
                                    max_parents=max_parents)
        else:
            svc = CountingService(CountingEngine(db, "sparse", CostStats()))
            dsvc = DiscoveryService(svc,
                                    max_chain_length=max_chain_length,
                                    max_parents=max_parents)
        sigs[mode] = dsvc.discover().signature()   # warm CTs + jit caches
        dsvcs[mode] = dsvc
    walls: Dict[str, List[float]] = {m: [] for m in modes}
    round_counts: Dict[str, List[int]] = {m: [] for m in modes}
    fam_counts: Dict[str, List[int]] = {m: [] for m in modes}
    for _ in range(rounds):       # interleaved: drift hits both modes
        for mode in modes:
            dsvc = dsvcs[mode]
            dsvc.reset_memo()    # re-score everything over warm counts
            before = dsvc.metrics.snapshot()["rounds"]
            t0 = time.perf_counter()
            res = dsvc.discover()
            walls[mode].append(time.perf_counter() - t0)
            round_counts[mode].append(
                dsvc.metrics.snapshot()["rounds"] - before)
            fam_counts[mode].append(res.families_scored)
    perf: Dict[str, Tuple[float, float, float]] = {}
    for mode in modes:
        rounds_per_s = max(
            (r / w for r, w in zip(round_counts[mode], walls[mode])
             if w > 0), default=0.0)
        fams_per_s = max(
            (f / w for f, w in zip(fam_counts[mode], walls[mode])
             if w > 0), default=0.0)
        perf[mode] = (sum(walls[mode]), rounds_per_s, fams_per_s)
    assert sigs["served"] == sigs["local"], \
        "served discovery diverged from the local oracle"
    # Ratio = median of per-pair ratios: round i of each mode ran
    # back-to-back, so ambient load cancels within a pair, and the
    # median drops pairs where a scheduler blip hit only one side.
    pair_ratios = [
        (fam_counts["served"][i] / walls["served"][i])
        / (fam_counts["local"][i] / walls["local"][i])
        for i in range(len(walls["local"]))
        if walls["local"][i] > 0 and walls["served"][i] > 0
        and fam_counts["local"][i] > 0]
    ratio = statistics.median(pair_ratios) if pair_ratios else float("inf")
    print(f"[discovery] {config} local={perf['local'][2]:8.1f} fam/s "
          f"({perf['local'][1]:6.1f} rounds/s)  "
          f"served={perf['served'][2]:8.1f} fam/s "
          f"({perf['served'][1]:6.1f} rounds/s)  ratio={ratio:5.2f}x",
          flush=True)
    for mode in ("local", "served"):
        wall, rps, fps = perf[mode]
        rec = {"bench": "discovery", "config": config, "dataset": dataset,
               "strategy": strategy if mode == "local" else "SERVICE",
               "executor": "sparse", "mode": mode,
               "queries": rounds, "wall_s": round(wall, 4),
               "qps": round(fps, 1), "rounds_per_s": round(rps, 1),
               "families_per_s": round(fps, 1), "completed": True}
        if mode == "served":
            rec["ratio_vs_local"] = round(ratio, 3)
        out.append(rec)
    return out


def write_outputs(art: dict, out_dir: str = "results/bench",
                  bench_json: Optional[str] = "BENCH_counting.json") -> None:
    """One canonical artifact; the root trajectory file is derived.

    ``results/bench/counting.json`` holds the whole artifact (this run's
    source of truth).  ``BENCH_counting.json`` is its ``trajectory``
    section *appended* to whatever earlier PRs recorded — the
    accumulating cross-PR perf trajectory the CI perf-smoke gate reads.
    """
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "counting.json").write_text(json.dumps(art, indent=1))
    print(f"[counting] wrote {out / 'counting.json'} (canonical)")
    if bench_json:
        path = Path(bench_json)
        history: List[dict] = []
        if path.exists():
            try:
                history = json.loads(path.read_text())
            except json.JSONDecodeError:
                history = []
        history.extend(art["trajectory"])
        path.write_text(json.dumps(history, indent=1))
        print(f"[counting] wrote {path} (derived from trajectory, "
              f"{len(history)} rows)")


def main(out_dir: str = "results/bench", scale: Optional[float] = None,
         datasets: Sequence[str] = PAPER_DATASETS,
         budget_s: float = TIME_BUDGET_S, spotlight: bool = True,
         executors: Sequence[str] = ("dense", "sparse"),
         flood: bool = True,
         flood_kw: Optional[dict] = None,
         neg_flood: bool = True,
         neg_flood_kw: Optional[dict] = None,
         shards: Sequence[int] = (),
         shard_kw: Optional[dict] = None,
         mut_flood: bool = True,
         mut_flood_kw: Optional[dict] = None,
         mut_neg_flood: bool = True,
         mut_neg_flood_kw: Optional[dict] = None,
         tenant_flood: bool = False,
         tenant_flood_kw: Optional[dict] = None,
         tiered: bool = True,
         tiered_kw: Optional[dict] = None,
         discovery: bool = False,
         discovery_kw: Optional[dict] = None,
         trace: bool = False,
         bench_json: Optional[str] = "BENCH_counting.json") -> dict:
    recs = run_all(datasets=datasets, scale=scale, budget_s=budget_s,
                   executors=executors)
    art = {
        "runs": [r.as_dict() for r in recs],
        "fig3_runtime": fig3_runtime(recs),
        "fig4_memory": fig4_memory(recs),
        "table5_sizes": table5_sizes(recs),
    }
    if spotlight:
        # the paper's headline: hybrid counting scales to millions of facts.
        # Full-scale VisualGenome (15.8M rows) / IMDb (1.06M rows), HYBRID on
        # the sparse backend (positive phase scales in nnz, not entities×D).
        spot = []
        for name, sc in (("IMDb", 1.0), ("VisualGenome", 1.0)):
            r = run_one(name, "HYBRID", scale=sc, budget_s=1200.0,
                        executor="sparse")
            print(f"[spotlight] {name} rows={r.rows} HYBRID/sparse "
                  f"wall={r.wall_s}s pos={r.time_positive} "
                  f"neg={r.time_negative} completed={r.completed}",
                  flush=True)
            spot.append(r.as_dict())
            recs.append(r)
        art["spotlight_full_scale"] = spot
    flood_recs: List[dict] = []
    if flood:
        flood_recs = bench_service_flood(executors=tuple(executors),
                                         **(flood_kw or {}))
        art["service_flood"] = flood_recs
    neg_recs: List[dict] = []
    if neg_flood:
        neg_recs = bench_negative_flood(executors=tuple(executors),
                                        **(neg_flood_kw or {}))
        art["negative_flood"] = neg_recs
    shard_recs: List[dict] = []
    for n in shards:
        shard_recs.extend(bench_sharded_flood(n_shards=int(n), trace=trace,
                                              **(shard_kw or {})))
    if shard_recs:
        art["sharded_flood"] = shard_recs
    mut_recs: List[dict] = []
    if mut_flood:
        mut_recs = bench_mutation_flood(executors=tuple(executors),
                                        **(mut_flood_kw or {}))
        art["mutation_flood"] = mut_recs
    mutneg_recs: List[dict] = []
    if mut_neg_flood:
        mutneg_recs = bench_mutation_negative_flood(
            executors=tuple(executors), **(mut_neg_flood_kw or {}))
        art["mutation_negative_flood"] = mutneg_recs
    tenant_recs: List[dict] = []
    if tenant_flood:
        tenant_recs = bench_tenant_flood(executors=tuple(executors),
                                         **(tenant_flood_kw or {}))
        art["tenant_flood"] = tenant_recs
    tiered_recs: List[dict] = []
    if tiered:
        tiered_recs = bench_tiered_schedule(**(tiered_kw or {}))
        art["tiered_schedule"] = tiered_recs
    disc_recs: List[dict] = []
    if discovery:
        disc_recs = bench_discovery(**(discovery_kw or {}))
        art["discovery"] = disc_recs
    art["trajectory"] = (bench_trajectory(recs) + flood_recs + neg_recs
                         + shard_recs + mut_recs + mutneg_recs
                         + tenant_recs + tiered_recs + disc_recs)
    write_outputs(art, out_dir=out_dir, bench_json=bench_json)
    return art


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=None,
                    help="multiply the per-dataset DEFAULT_SCALES")
    ap.add_argument("--datasets", nargs="*", default=list(PAPER_DATASETS))
    ap.add_argument("--budget-s", type=float, default=TIME_BUDGET_S)
    ap.add_argument("--no-spotlight", action="store_true")
    ap.add_argument("--no-flood", action="store_true")
    ap.add_argument("--no-neg-flood", action="store_true")
    ap.add_argument("--no-mut-flood", action="store_true")
    ap.add_argument("--no-mut-neg-flood", action="store_true")
    ap.add_argument("--no-tiered", action="store_true")
    ap.add_argument("--shards", type=int, nargs="*", default=[],
                    metavar="N",
                    help="also run the sharded-vs-single sparse flood for "
                         "each shard count given (e.g. --shards 2 4)")
    ap.add_argument("--trace", action="store_true",
                    help="run the sharded flood with request tracing on "
                         "and dump its slow-query log")
    ap.add_argument("--discovery", action="store_true",
                    help="also run the served-vs-local model-discovery "
                         "throughput bench (rounds/s + families/s)")
    ap.add_argument("--tenant-flood", action="store_true",
                    help="also run the multi-tenant fleet flood "
                         "(cross-tenant batched vs per-tenant serial)")
    args = ap.parse_args()
    main(scale=args.scale, datasets=tuple(args.datasets),
         budget_s=args.budget_s, spotlight=not args.no_spotlight,
         flood=not args.no_flood, neg_flood=not args.no_neg_flood,
         shards=tuple(args.shards), mut_flood=not args.no_mut_flood,
         mut_neg_flood=not args.no_mut_neg_flood,
         tiered=not args.no_tiered,
         tenant_flood=args.tenant_flood,
         discovery=args.discovery, trace=args.trace)
