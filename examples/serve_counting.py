"""Serve counting queries to concurrent clients: a CountingService flood demo.

Several client threads flood one :class:`~repro.serve.service
.CountingService` with positive-count queries over a schema whose
relationships share one shape — the service coalesces duplicate
in-flight queries, short-circuits cache residents, buckets the rest by
plan signature, and executes each bucket as a single stacked/vmapped
contraction against the shared byte-budgeted ct-cache.  For comparison
the same query stream is first answered per-query through the bare
executor.

Run:  PYTHONPATH=src python examples/serve_counting.py [n_clients] [n_rels]
      default: 4 clients x 24 queries each, 8 relationships, sparse backend.
"""

import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import (Attribute, EntityType, Relationship, Schema,
                        CostStats, CountingEngine, build_lattice, synth_db)
from repro.serve import CountingService


def flood_schema(n_rels: int) -> Schema:
    att = lambda n: Attribute(n, 3)
    ents = (EntityType("item", 500, (att("a0"), att("a1"))),
            EntityType("tag", 300, (att("b0"),)))
    rels = tuple(Relationship(f"Rel{i}", "item", "tag", (att(f"e{i}"),))
                 for i in range(n_rels))
    return Schema(ents, rels)


def main():
    n_clients = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    n_rels = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    per_client = 60

    schema = flood_schema(n_rels)
    db = synth_db(schema, {f"Rel{i}": 3000 for i in range(n_rels)}, seed=0)
    points = build_lattice(schema, 1)
    print(f"database: {db.total_rows} rows, {n_rels} same-shape "
          f"relationships -> {len(points)} distinct count queries")

    # ---- baseline: per-query dispatch, no batching ----------------------
    eng = CountingEngine(db, "sparse", CostStats())
    rng = np.random.default_rng(0)
    stream = [points[i] for i in
              rng.integers(len(points), size=n_clients * per_client)]
    t0 = time.perf_counter()
    for p in stream:
        eng.executor.positive(db, eng.plan(p, None))
    t_pq = time.perf_counter() - t0
    print(f"per-query : {len(stream)} queries in {t_pq*1e3:7.0f} ms "
          f"({len(stream)/t_pq:7.0f} q/s)")

    # ---- service: concurrent clients, micro-batched ---------------------
    eng = CountingEngine(db, "sparse", CostStats(),
                         cache_budget_bytes=64 << 20)
    svc = CountingService(eng, max_batch_size=n_rels)
    # warm the stacked evaluator (a long-running service compiles once,
    # then serves); drop the warmed tables so clients do real work
    for burst in (points, points[:4], points[:2], points[:1]):
        svc.count_many([(p, None) for p in burst])
        eng.cache.evict_all()
    svc.metrics = type(svc.metrics)()

    def client(cid: int):
        crng = np.random.default_rng(cid)
        for _ in range(per_client // 6):
            # submit a burst of tickets, then resolve them — bursts from
            # concurrent clients land in one signature bucket
            tickets = [svc.submit(points[int(crng.integers(len(points)))])
                       for _ in range(6)]
            for t in tickets:
                t.result()

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_svc = time.perf_counter() - t0
    n = n_clients * per_client
    print(f"service   : {n} queries in {t_svc*1e3:7.0f} ms "
          f"({n/t_svc:7.0f} q/s) from {n_clients} client threads")

    snap = svc.stats()
    print("\nservice health:")
    print(f"  requests / cache hits / coalesced : "
          f"{snap['requests']} / {snap['cache_hits']} / {snap['coalesced']}")
    print(f"  batches x mean size               : {snap['batches']} x "
          f"{snap['batched_queries'] / max(snap['batches'], 1):.1f}")
    print(f"  bucket exec throughput            : {snap['qps']:.0f} q/s")
    print(f"  ct-cache                          : {snap['cache']}")
    print("OK — counting service flood works end-to-end.")


if __name__ == "__main__":
    main()
