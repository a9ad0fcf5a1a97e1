"""Distributed counting == single-device counting.

Two layers are covered:

* **mesh sharding** (``core/distributed.py``): the dense and sparse
  executors with their hops sharded over a device mesh produce counts
  numerically identical to the single-device path and the brute-force
  oracle — including every strategy over ``ShardedSparseExecutor`` on a
  >= 2-shard mesh.  These run in a subprocess with 8 fake host devices
  (XLA_FLAGS must be set before jax initialises, so the main test process
  — which needs 1 device — can't do it in-process).
* **database sharding** (``ShardedDatabase`` + ``serve/router.py``): a
  horizontally hash-partitioned database behind one CountingService per
  shard merges, at the router, to the exact single-database answer —
  including under a concurrent mixed-signature flood.  These need no
  extra devices and run in-process.
"""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core import (CostStats, CountingEngine, LatticePoint,
                        NotRoutableError, build_lattice, shard_database)
from repro.core.variables import Atom, Var
from repro.serve import CountingRouter, RouterMetrics
from tests.test_serve import mixed_db

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import positive_ct, point_from_rels
    from repro.core.distributed import (ShardedSparseExecutor,
                                        sharded_positive_ct,
                                        sharded_sparse_positive_ct)
    from tests.test_counting_core import tiny_db

    db = tiny_db(4)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    for rels in (["Reg"], ["Reg", "RA"]):
        point = point_from_rels(db.schema, rels)
        keep = point.all_ct_vars(db.schema, include_rind=False)
        a = positive_ct(db, point, keep)
        b = sharded_positive_ct(db, point, keep, mesh=mesh)
        np.testing.assert_allclose(np.asarray(a.counts), np.asarray(b.counts),
                                   atol=1e-3)
        c = sharded_sparse_positive_ct(db, point, keep, mesh=mesh)
        np.testing.assert_allclose(np.asarray(a.counts), np.asarray(c.counts),
                                   atol=1e-3)
    print("DISTRIBUTED-OK")
""")

# Sharded sparse == unsharded sparse == brute-force oracle, for all four
# strategies, on an 8-shard data mesh (the ISSUE's >= 2-shard property).
STRATEGY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import build_lattice, make_strategy
    from repro.core.distributed import ShardedSparseExecutor
    from repro.core.oracle import oracle_ct
    from repro.core.strategies import STRATEGIES
    from tests.test_engine_equivalence import random_db, random_keeps

    mesh = jax.make_mesh((8,), ("data",))
    for seed in (0, 1):
        db = random_db(seed)
        rng = np.random.default_rng(seed + 50)
        lattice = build_lattice(db.schema, 2)
        point = lattice[-1]
        keeps = random_keeps(rng, point, db.schema)
        oracles = [oracle_ct(db, point, keep) for keep in keeps]
        plain = make_strategy("ONDEMAND", executor="sparse")
        plain.prepare(db, lattice)
        for sname in sorted(STRATEGIES):
            ex = ShardedSparseExecutor(mesh=mesh, axis="data")
            assert ex.n_ranks == 8
            st = make_strategy(sname, executor=ex)
            st.prepare(db, lattice)
            for keep, want in zip(keeps, oracles):
                got = st.family_ct(point, keep)
                np.testing.assert_allclose(
                    np.asarray(got.counts), want, atol=1e-3,
                    err_msg=f"seed={seed} {sname} "
                            f"keep={[str(v) for v in keep]}")
                ref = plain.family_ct(point, keep)
                np.testing.assert_allclose(
                    np.asarray(got.counts), np.asarray(ref.counts),
                    atol=1e-3)

    # mutations on the 8-rank mesh: delta maintenance stays oracle-exact,
    # and the delta path's local_mode contractions never build new
    # shard_map closures (a handful of delta edges must not pay padding
    # + psum per hop)
    from tests.test_mutations import random_delete, random_insert
    db = random_db(0)
    lattice = build_lattice(db.schema, 2)
    ex = ShardedSparseExecutor(mesh=mesh, axis="data")
    st = make_strategy("HYBRID", executor=ex)
    st.prepare(db, lattice)
    point = lattice[-1]
    keep = point.all_ct_vars(db.schema, include_rind=True)
    st.family_ct(point, keep)
    rng = np.random.default_rng(5)
    rel = sorted(point.rels)[0]
    n_closures = len(ex._shard_fn_cache)
    rep = st.apply_delta(random_insert(db, rel, 2, rng))
    assert rep.updated + rep.invalidated > 0, rep
    assert len(ex._shard_fn_cache) == n_closures     # local_mode: no
    for delta_round in range(2):                     # sharded delta hops
        got = st.family_ct(point, keep)
        np.testing.assert_allclose(np.asarray(got.counts),
                                   oracle_ct(db, point, keep), atol=1e-3)
        d = random_delete(db, rel, 1, rng)
        if d is not None:
            st.apply_delta(d)
    got = st.family_ct(point, keep)
    np.testing.assert_allclose(np.asarray(got.counts),
                               oracle_ct(db, point, keep), atol=1e-3)
    print("SHARDED-SPARSE-OK")
""")


# The sharded executor's shard_map closures are cached per device-step
# shape: a flood of same-shape hops must trace each step ONCE (PR-3
# follow-up: no per-hop retracing).
TRACE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import CostStats, CountingEngine, build_lattice
    from repro.core.distributed import ShardedSparseExecutor
    from tests.test_serve import mixed_db

    mesh = jax.make_mesh((8,), ("data",))
    db = mixed_db()
    ex = ShardedSparseExecutor(mesh=mesh, axis="data")
    eng = CountingEngine(db, ex, CostStats())
    ref = CountingEngine(db, "sparse", CostStats())
    plans = [eng.plan(p, None) for p in build_lattice(db.schema, 2)]
    for plan in plans:                       # first pass: traces happen here
        got = ex.positive(db, plan)
        want = ref.executor.positive(db, plan)
        np.testing.assert_allclose(np.asarray(got.counts),
                                   np.asarray(want.counts), atol=1e-3)
    first = dict(ex.trace_counts)
    assert first and all(v == 1 for v in first.values()), first
    for _ in range(3):                       # the flood: same-shape re-runs
        for plan in plans:
            ex.positive(db, plan)
    assert ex.trace_counts == first, (ex.trace_counts, first)
    assert len(ex._shard_fn_cache) == len(first)
    print("TRACE-FLAT-OK")
""")


def _run_subprocess(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath("src"), os.path.abspath("."),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_counting_matches(tmp_path):
    assert "DISTRIBUTED-OK" in _run_subprocess(SCRIPT)


def test_sharded_sparse_strategies_match_oracle():
    assert "SHARDED-SPARSE-OK" in _run_subprocess(STRATEGY_SCRIPT)


def test_sharded_sparse_trace_counts_stay_flat():
    assert "TRACE-FLAT-OK" in _run_subprocess(TRACE_SCRIPT)


# ---------------------------------------------------------------------------
# ShardedDatabase: partition invariants + routing decisions (in-process)
# ---------------------------------------------------------------------------

def test_shard_database_partition_invariants():
    db = mixed_db()
    sdb = shard_database(db, 3)
    assert sdb.n_shards == 3
    assert sdb.root_etype == "A"            # most-incident entity type
    assert sdb.partitioned == {"R0", "R2"}  # A-incident rels; R1 replicated
    for name, tab in db.relations.items():
        if name in sdb.partitioned:
            # every edge on exactly one shard, attribute columns aligned
            parts = [s.relations[name] for s in sdb.shards]
            assert sum(p.num_edges for p in parts) == tab.num_edges
            got = sorted(
                (int(a), int(b)) for p in parts
                for a, b in zip(p.src, p.dst))
            assert got == sorted(
                (int(a), int(b)) for a, b in zip(tab.src, tab.dst))
        else:
            for s in sdb.shards:
                assert s.relations[name] is tab      # replicated, shared
    for s in sdb.shards:
        s.validate()
        for ename, etab in s.entities.items():       # entities replicated
            assert etab is db.entities[ename]


def test_shard_database_rejects_bad_args():
    db = mixed_db()
    with pytest.raises(ValueError):
        shard_database(db, 0)
    with pytest.raises(ValueError):
        shard_database(db, 2, root_etype="nope")


def test_route_decisions():
    db = mixed_db()
    sdb = shard_database(db, 2, root_etype="A")
    lattice = build_lattice(db.schema, 2)
    modes = {str(p): sdb.route(p) for p in lattice}
    assert modes["R1(B0,C0)"][0] == "single"        # only replicated tables
    assert modes["R0(A0,B0)"] == ("fanout", None)   # one partitioned atom
    assert modes["R0(A0,B0)&R2(A0,C0)"] == ("fanout", None)  # shared A0
    # single-shard picks a shard deterministically and in range
    mode, shard = modes["R1(B0,C0)"]
    assert 0 <= shard < 2


def test_route_rejects_incoherent_partition_vars():
    """Two partitioned atoms meeting the root type at DIFFERENT variables:
    their edges hash by different grounding values, so per-shard counts
    are not additive and route() must refuse."""
    db = mixed_db()
    sdb = shard_database(db, 2, root_etype="A")
    bad = LatticePoint((Atom("R0", Var("A", 1), Var("B", 0)),
                        Atom("R2", Var("A", 0), Var("C", 0))))
    with pytest.raises(NotRoutableError):
        sdb.route(bad)


# ---------------------------------------------------------------------------
# CountingRouter: merged answers == single-database answers
# ---------------------------------------------------------------------------

def _routable_points(sdb, lattice):
    out = []
    for p in lattice:
        try:
            sdb.route(p)
            out.append(p)
        except NotRoutableError:
            pass
    return out


@pytest.mark.parametrize("n_shards", [2, 3])
def test_router_merges_to_single_db_answer(n_shards):
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, n_shards)
    router = CountingRouter(sdb, executor="sparse")
    eng = CountingEngine(db, "sparse", CostStats())
    points = _routable_points(sdb, lattice)
    assert points                                   # workload is non-empty
    for point in points:
        want = eng.contract(point, None)
        got = router.count(point)
        assert got.vars == want.vars
        np.testing.assert_allclose(np.asarray(got.counts),
                                   np.asarray(want.counts), atol=1e-3,
                                   err_msg=str(point))
    snap = router.stats()
    assert snap["router"]["requests"] == len(points)
    assert snap["router"]["fanout_requests"] >= 1
    assert snap["router"]["single_shard_requests"] >= 1
    assert snap["aggregate"]["requests"] >= len(points)


def test_router_count_many_batches_per_shard():
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="dense", max_batch_size=32)
    eng = CountingEngine(db, "dense", CostStats())
    points = _routable_points(sdb, lattice)
    queries = [(p, None) for p in points] * 3       # repeats coalesce/hit
    tabs = router.count_many(queries)
    for (p, _), tab in zip(queries, tabs):
        want = eng.contract(p, None)
        np.testing.assert_allclose(np.asarray(tab.counts),
                                   np.asarray(want.counts), atol=1e-3)
    agg = router.stats()["aggregate"]
    rt = router.stats()["router"]
    assert agg["batched_queries"] >= 1              # shard services batched
    # repeats were cheap: absorbed by the router's own cache/in-flight
    # table (or, failing that, by the shard services)
    assert (rt["cache_hits"] + rt["coalesced"]
            + agg["cache"]["hits"] + agg["coalesced"]) >= 1


def test_router_mixed_flood_concurrent_clients():
    """Acceptance: a mixed flood over 2 database shards merges to the
    single-DB answer under concurrent client threads."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse", max_batch_size=4,
                            metrics=RouterMetrics())
    points = _routable_points(sdb, lattice)
    eng = CountingEngine(db, "sparse", CostStats())
    ref = {p: np.asarray(eng.contract(p, None).counts) for p in points}
    errors = []

    def client(seed):
        rng = np.random.default_rng(seed)
        for _ in range(6):
            p = points[int(rng.integers(len(points)))]
            try:
                tab = router.count(p)
                np.testing.assert_allclose(np.asarray(tab.counts), ref[p],
                                           atol=1e-3)
            except Exception as e:          # surface in the main thread
                errors.append(e)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = router.stats()
    assert snap["router"]["requests"] == 24
    assert snap["router"]["merged_tables"] >= 1
    assert len(snap["shards"]) == 2


def test_router_count_many_prevalidates_mixed_list():
    """A non-routable query anywhere in a count_many list must fail the
    whole call BEFORE any shard work is enqueued."""
    db = mixed_db()
    sdb = shard_database(db, 2, root_etype="A")
    router = CountingRouter(sdb, executor="sparse")
    good = build_lattice(db.schema, 1)[0]
    bad = LatticePoint((Atom("R0", Var("A", 1), Var("B", 0)),
                        Atom("R2", Var("A", 0), Var("C", 0))))
    with pytest.raises(NotRoutableError):
        router.count_many([(good, None), (bad, None)])
    assert router.pending() == 0
    assert router.stats()["aggregate"]["enqueued"] == 0


def test_router_result_cache_and_coalescing():
    """A repeated query is served from the router's merged-result cache
    without touching any shard; identical concurrent fan-out queries
    coalesce onto ONE in-flight ticket (one execute + one merge)."""
    db = mixed_db()
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse")
    lattice = build_lattice(db.schema, 2)
    fanout = next(p for p in _routable_points(sdb, lattice)
                  if sdb.route(p)[0] == "fanout")

    # coalescing: two submits before any result -> the SAME ticket
    t1 = router.submit(fanout)
    t2 = router.submit(fanout)
    assert t2 is t1
    router.flush()
    tab1 = t1.result()
    np.testing.assert_array_equal(np.asarray(t2.result().counts),
                                  np.asarray(tab1.counts))
    rt = router.stats()["router"]
    assert rt["coalesced"] == 1
    assert rt["merged_tables"] == 2                 # merged exactly once

    # result cache: a later identical submit never reaches the shards
    shard_requests_before = router.stats()["aggregate"]["requests"]
    t3 = router.submit(fanout)
    assert t3.done
    np.testing.assert_array_equal(np.asarray(t3.result().counts),
                                  np.asarray(tab1.counts))
    snap = router.stats()
    assert snap["router"]["cache_hits"] == 1
    assert snap["aggregate"]["requests"] == shard_requests_before
    assert snap["router"]["merged_tables"] == 2     # still exactly once


def test_router_cache_disabled_and_lru_trim():
    db = mixed_db()
    sdb = shard_database(db, 2)
    points = _routable_points(sdb, build_lattice(db.schema, 2))
    off = CountingRouter(sdb, executor="sparse", cache_entries=0)
    off.count(points[0])
    off.count(points[0])
    assert off.stats()["router"]["cache_hits"] == 0
    tiny = CountingRouter(sdb, executor="sparse", cache_entries=1)
    tiny.count(points[0])
    tiny.count(points[1])                           # evicts points[0]
    assert len(tiny._results) == 1
    tiny.count(points[0])                           # miss -> recompute
    assert tiny.stats()["router"]["cache_hits"] == 0


def test_router_invalidate_keeps_stale_results_out():
    """invalidate() mid-flight: the ticket settles its waiters, but its
    pre-invalidate table must NOT be re-published into the cache."""
    db = mixed_db()
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse")
    p = _routable_points(sdb, build_lattice(db.schema, 2))[0]
    t = router.submit(p)
    router.invalidate()                   # data "refreshed" mid-flight
    assert t.result() is not None         # waiters settle fine …
    assert len(router._results) == 0      # … but stale data is not cached
    router.count(p)
    assert len(router._results) == 1      # the fresh epoch caches again


def test_router_metrics_rollup_counts_not_routable():
    db = mixed_db()
    sdb = shard_database(db, 2, root_etype="A")
    router = CountingRouter(sdb, executor="sparse")
    bad = LatticePoint((Atom("R0", Var("A", 1), Var("B", 0)),
                        Atom("R2", Var("A", 0), Var("C", 0))))
    with pytest.raises(NotRoutableError):
        router.submit(bad)
    snap = router.stats()["router"]
    assert snap["not_routable"] == 1 and snap["requests"] == 1


# ---------------------------------------------------------------------------
# Device-side merging: the fan-out reassembly fast path, the fused drain
# flush, and their host-path fallback all agree with the single-DB answer
# ---------------------------------------------------------------------------

def _force_host_merge(router):
    """Disable both fused device-merge paths on THIS router instance —
    count_many falls back to per-shard service submits and flush() to one
    concurrent svc.flush() per shard, so answers come through the
    original per-ticket merge."""
    router._count_many_fanout = lambda *a, **k: None
    router._fused_groups = lambda *a, **k: None


def _completable_points(sdb, lattice):
    """Routable points whose every butterfly positive sub-query is also
    routable (what complete-CT needs)."""
    from repro.core.mobius import positive_queries
    out = []
    for p in _routable_points(sdb, lattice):
        keep = tuple(p.all_ct_vars(sdb.schema, include_rind=True))
        try:
            for sp, _ in positive_queries(p, keep, use_butterfly=True):
                sdb.route(sp)
        except NotRoutableError:
            continue
        out.append(p)
    return out


@pytest.mark.parametrize("sname", ["HYBRID", "ONDEMAND", "PRECOUNT",
                                   "TUPLEID"])
def test_merge_parity_device_host_single_db_per_strategy(sname):
    """Device merge == host merge == single-DB strategy answer, for every
    counting strategy: the strategy computes the complete family CT on
    the unsharded database; a default router (fused device merging) and a
    host-fallback router answer the same workload over 2 shards."""
    from repro.core import make_strategy

    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    st = make_strategy(sname, executor="sparse")
    st.prepare(db, lattice)
    points = _completable_points(sdb, lattice)
    assert points
    queries = [(p, tuple(p.all_ct_vars(db.schema, include_rind=True)))
               for p in points]
    want = [np.asarray(st.family_ct(p, k).counts) for p, k in queries]

    dev = CountingRouter(sdb, executor="sparse")
    host = CountingRouter(sdb, executor="sparse")
    _force_host_merge(host)
    for router in (dev, host):
        tabs = router.complete_many(queries)
        for (p, _), tab, ref in zip(queries, tabs, want):
            np.testing.assert_allclose(
                np.asarray(tab.counts), ref, atol=1e-3,
                err_msg=f"{sname} {p} via "
                        f"{'device' if router is dev else 'host'} merge")
    # both routers merged on device (complete workloads mix fan-out and
    # single-shard sub-queries, so the FUSED dispatch may not engage —
    # but the host-forced router must never have fused)
    assert dev.stats()["router"]["device_merges"] >= 1
    assert host.stats()["router"]["fused_dispatches"] == 0
    assert host.stats()["router"]["merged_tables"] >= 1


def test_count_many_fanout_fast_path_bypasses_services():
    """An all-fan-out count_many reassembles shard inputs and answers at
    single-DB cost: no shard service sees a request, answers equal the
    single-DB engine, repeats hit the router cache, and invalidate()
    forces a fresh evaluation."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse")
    eng = CountingEngine(db, "sparse", CostStats())
    points = [p for p in _routable_points(sdb, lattice)
              if sdb.route(p)[0] == "fanout"]
    assert len(points) >= 2
    queries = [(p, None) for p in points]

    tabs = router.count_many(queries)
    for (p, _), tab in zip(queries, tabs):
        want = eng.contract(p, None)
        assert tab.vars == want.vars
        np.testing.assert_allclose(np.asarray(tab.counts),
                                   np.asarray(want.counts), atol=1e-3,
                                   err_msg=str(p))
    rt = router.stats()["router"]
    agg = router.stats()["aggregate"]
    assert rt["fused_dispatches"] >= 1
    assert rt["device_merges"] >= 1
    assert rt["fanout_requests"] == len(points)
    assert rt["merged_tables"] == len(points) * 2
    assert agg["enqueued"] == 0                     # services bypassed

    # duplicates inside ONE list: first occurrence evaluates, repeats
    # are absorbed (in-flight coalesce) without extra dispatches
    router.invalidate()
    before = router.stats()["router"]["fused_dispatches"]
    dup = router.count_many(queries + queries)
    np.testing.assert_array_equal(np.asarray(dup[0].counts),
                                  np.asarray(dup[len(points)].counts))
    rt = router.stats()["router"]
    assert rt["coalesced"] >= len(points)
    assert rt["fused_dispatches"] >= before + 1

    # repeats across calls: served from the router's merged-result cache
    before = rt["fused_dispatches"]
    router.count_many(queries)
    rt = router.stats()["router"]
    assert rt["cache_hits"] >= len(points)
    assert rt["fused_dispatches"] == before         # nothing re-evaluated

    # a later submit() of the same key is already resolved
    t = router.submit(points[0])
    assert t.done


def test_fused_flush_serves_submitted_tickets():
    """submit() + flush(): the drain-based fused dispatch computes every
    shard's table AND the merged table in one evaluation — tickets get
    the merged answer, shard services get their per-shard deliveries
    (metrics + caches), and the answers equal the single-DB engine."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse", max_batch_size=64)
    eng = CountingEngine(db, "sparse", CostStats())
    points = [p for p in _routable_points(sdb, lattice)
              if sdb.route(p)[0] == "fanout"]
    tickets = [router.submit(p) for p in points]
    router.flush()
    for p, t in zip(points, tickets):
        want = eng.contract(p, None)
        np.testing.assert_allclose(np.asarray(t.result().counts),
                                   np.asarray(want.counts), atol=1e-3,
                                   err_msg=str(p))
    snap = router.stats()
    assert snap["router"]["fused_dispatches"] >= 1
    # per-shard deliveries reached the services: batches observed and
    # results cached shard-side
    assert snap["aggregate"]["batches"] >= 2
    assert snap["aggregate"]["batched_queries"] >= 2 * len(points)
    assert snap["aggregate"]["cache"]["entries"] >= 1


def test_fused_flush_falls_back_on_misaligned_queues():
    """Unequal shard queues (a direct shard-service client alongside the
    router) cannot fuse: the drained work must still execute per shard
    and every waiter must settle with the right answer."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse", max_batch_size=64)
    eng = CountingEngine(db, "sparse", CostStats())
    points = [p for p in _routable_points(sdb, lattice)
              if sdb.route(p)[0] == "fanout"]
    services = router._snapshot()[1]
    t_router = router.submit(points[0])
    extra = points[1]
    t_direct = services[0].submit(extra)     # shard 0 queue is now longer
    router.flush()
    np.testing.assert_allclose(
        np.asarray(t_router.result().counts),
        np.asarray(eng.contract(points[0], None).counts), atol=1e-3)
    # the direct ticket holds shard 0's PARTIAL count (its slice of the
    # partitioned edges), not the merged answer — it must settle too
    assert t_direct.result() is not None
    assert router.stats()["router"]["fused_dispatches"] == 0


def test_partial_overlapped_merge_under_staggered_shards():
    """Host-path merging with 3 shards: when two shards settle before the
    third, their tables fold into a running partial while the last shard
    executes — partial_merges counts the overlapped fold, and the final
    table still equals the single-DB answer."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 3)
    router = CountingRouter(sdb, executor="sparse", max_batch_size=64)
    _force_host_merge(router)
    eng = CountingEngine(db, "sparse", CostStats())
    p = next(q for q in _routable_points(sdb, lattice)
             if sdb.route(q)[0] == "fanout")
    t = router.submit(p)
    services = router._snapshot()[1]
    services[0].flush()                      # two shards settle early …
    services[1].flush()
    tab = t.result()                         # … third flushes inside wait
    np.testing.assert_allclose(np.asarray(tab.counts),
                               np.asarray(eng.contract(p, None).counts),
                               atol=1e-3)
    rt = router.stats()["router"]
    assert rt["partial_merges"] >= 1
    assert rt["merged_tables"] == 3

    # and the merged table landed in the router cache zero-copy: the
    # cached entry IS the ticket's table object
    key = (p.atoms, router.engines[0].plan(p, None).keep)
    assert router._results[key] is tab


def test_fanout_fast_path_concurrent_with_deltas():
    """The fan-out fast path linearizes against apply_delta: concurrent
    floods and inserts interleave without torn reads — every flood answer
    matches the single-DB engine at SOME insert prefix (never a mix)."""
    db = mixed_db()
    lattice = build_lattice(db.schema, 2)
    sdb = shard_database(db, 2)
    router = CountingRouter(sdb, executor="sparse")
    points = [p for p in _routable_points(sdb, lattice)
              if sdb.route(p)[0] == "fanout"
              and any(a.rel == "R1" for a in p.atoms)][:3]
    assert points

    # two fresh ("R1" has no attrs) edges not present in the base store
    present = {(int(s), int(d)) for s, d in zip(db.relations["R1"].src,
                                                db.relations["R1"].dst)}
    inserts = [(s, d) for s in range(7) for d in range(6)
               if (s, d) not in present][:2]

    # reference tables at every insert prefix, from fresh single engines
    prefixes = []
    for i in range(len(inserts) + 1):
        ref_db = mixed_db()
        for s, d in inserts[:i]:
            ref_db.insert_facts("R1", [s], [d], None)
        eng = CountingEngine(ref_db, "sparse", CostStats())
        prefixes.append({p: np.asarray(eng.contract(p, None).counts)
                         for p in points})
    errors = []

    def flood():
        try:
            for _ in range(4):
                router.invalidate()        # measure the store, not cache
                tabs = router.count_many([(p, None) for p in points])
                got = {p: np.asarray(t.counts)
                       for p, t in zip(points, tabs)}
                ok = any(all(np.array_equal(got[p], pref[p])
                             for p in points) for pref in prefixes)
                assert ok, "flood observed a torn (mixed-delta) answer"
        except Exception as e:                   # pragma: no cover
            errors.append(e)

    def writer():
        try:
            for s, d in inserts:
                router.apply_delta("R1", [s], [d], None)
        except Exception as e:                   # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=flood), threading.Thread(target=writer)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
