"""TUPLEID — the paper's future-work pre-count variant (tuple-ID
propagation) — must produce identical counts to the other strategies and
never touch edge tables during search."""

import numpy as np

from repro.core.database import paper_benchmark_db
from repro.core.strategies import make_strategy
from repro.core.variables import build_lattice
from tests._workloads import family_workload


def test_tupleid_matches_hybrid():
    db = paper_benchmark_db("UW", scale=0.25, seed=3)
    lattice = build_lattice(db.schema, 2)
    h = make_strategy("HYBRID")
    t = make_strategy("TUPLEID")
    h.prepare(db, lattice)
    t.prepare(db, lattice)
    for point, keep in family_workload(db, lattice, per_point=24):
        th = h.family_ct(point, keep)
        tt = t.family_ct(point, keep).transpose_to(th.vars)
        np.testing.assert_allclose(np.asarray(tt.counts),
                                   np.asarray(th.counts),
                                   atol=1e-3, rtol=1e-5)


def test_tupleid_zero_joins_at_search_time():
    db = paper_benchmark_db("MovieLens", scale=0.05, seed=1)
    lattice = build_lattice(db.schema, 2)
    t = make_strategy("TUPLEID")
    t.prepare(db, lattice)
    joins_after_prepare = t.stats.joins
    for point, keep in family_workload(db, lattice, per_point=16):
        t.family_ct(point, keep)
    # tuple-ID propagation: the JOIN count must not grow during search
    assert t.stats.joins == joins_after_prepare
