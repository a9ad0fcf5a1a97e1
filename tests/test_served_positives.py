"""The served complete-table path reads positives through the positive
policy of the strategy that built the service.

* served discovery over ``strategy.service()`` learns the same models from
  the same family tables as the strategy in process, for all four
  strategies;
* HYBRID's served job contracts nothing from data after its pre-count,
  while ONDEMAND's still does;
* HYBRID under a budget that evicts its pre-counted tables, and after a
  write, answers exactly what an unbounded or a fresh strategy answers;
* a service over a bare engine keeps contracting on demand.
"""

import itertools

import numpy as np
import pytest

from repro.core import build_lattice, make_strategy
from repro.core.engine import CountingEngine, OnDemandPositives
from repro.core.executors import SparseExecutor
from repro.discover import DiscoveryService
from repro.discover.providers import LocalCounts, ServiceCounts
from repro.obs import Tracer
from repro.serve.service import CountingService
from tests.test_counting_core import tiny_db
from tests.test_mutations import fresh_pairs

STRATEGIES = ["PRECOUNT", "ONDEMAND", "HYBRID", "TUPLEID"]


class _Recording:
    """A count provider passing every call through and keeping the family
    tables it returned, by ``(atoms, keep)``."""

    def __init__(self, inner):
        self.inner = inner
        self.tracer = inner.tracer
        self.tables = {}

    @property
    def schema(self):
        return self.inner.schema

    def prepare(self, lattice):
        self.inner.prepare(lattice)

    def version(self):
        return self.inner.version()

    def _keep(self, point, keep, tab):
        self.tables[(point.atoms, tuple(keep))] = (
            tab.vars, np.asarray(tab.counts))
        return tab

    def family_ct(self, point, keep):
        return self._keep(point, keep, self.inner.family_ct(point, keep))

    def family_ct_many(self, point, keeps):
        tabs = self.inner.family_ct_many(point, keeps)
        return [self._keep(point, k, t) for k, t in zip(keeps, tabs)]


def _served(name, chain, tracer=None, **kw):
    """One served discovery job as the benchmark runs it: the strategy's
    pre-count, then discovery over the strategy's counting service."""
    db = tiny_db(0)
    ex = SparseExecutor()
    if tracer is not None:
        ex.tracer = tracer
    strat = make_strategy(name, executor=ex, **kw)
    strat.prepare(db, build_lattice(db.schema, chain))
    svc = strat.service()
    if tracer is not None:
        svc.set_tracer(tracer)
    rec = _Recording(ServiceCounts(svc))
    return strat, DiscoveryService(rec, max_chain_length=chain).discover(), rec


def _local(name, chain):
    db = tiny_db(0)
    rec = _Recording(LocalCounts(make_strategy(name, executor="sparse"), db))
    return DiscoveryService(rec, max_chain_length=chain).discover(), rec


def _assert_same_tables(got, want):
    assert got.keys() == want.keys()
    for key, (vars_, counts) in want.items():
        assert got[key][0] == vars_
        np.testing.assert_array_equal(got[key][1], counts, err_msg=str(key))


def _outside_prepare(tracer, name):
    recs = tracer.records()
    by_id = {r.span_id: r for r in recs}

    def in_prepare(r):
        while r.parent_id in by_id:
            r = by_id[r.parent_id]
            if r.name == "strategy.prepare":
                return True
        return False

    return [r for r in recs if r.name == name and not in_prepare(r)]


@pytest.mark.parametrize("name", STRATEGIES)
def test_served_discovery_matches_local_per_strategy(name):
    strat, served, srec = _served(name, 2)
    local, lrec = _local(name, 2)
    assert strat.service()._policy is strat.provider
    assert served.signature() == local.signature()
    assert served.score == local.score
    assert served.families_scored == local.families_scored
    _assert_same_tables(srec.tables, lrec.tables)


def test_hybrid_served_job_contracts_nothing_after_the_precount():
    tracer = Tracer()
    strat, _, _ = _served("HYBRID", 1, tracer)
    assert _outside_prepare(tracer, "count.positive") == []
    complete = _outside_prepare(tracer, "count.complete")
    assert complete
    assert sum(r.attrs["subqueries"] for r in complete) > 0
    assert all(r.attrs["from_data"] == 0 for r in complete)


def test_ondemand_served_job_still_contracts_from_data():
    tracer = Tracer()
    _served("ONDEMAND", 1, tracer)
    post = _outside_prepare(tracer, "count.positive")
    assert sum(r.attrs["tables"] for r in post) > 0
    complete = _outside_prepare(tracer, "count.complete")
    assert sum(r.attrs["from_data"] for r in complete) > 0
    assert all(r.attrs["from_data"] <= r.attrs["subqueries"]
               for r in complete)


def test_bare_engine_service_contracts_on_demand():
    db = tiny_db(0)
    eng = CountingEngine(db, "sparse")
    assert isinstance(CountingService(eng)._policy, OnDemandPositives)
    strat = make_strategy("HYBRID", executor="sparse")
    strat.prepare(db, build_lattice(db.schema, 1))
    with pytest.raises(ValueError):
        CountingService(eng, positives=strat.provider)


def _queries(schema, lattice):
    """Every lattice point with each one- and two-axis keep and its full
    keep (attribute and indicator axes)."""
    out = []
    for point in lattice:
        pool = tuple(point.all_ct_vars(schema, include_rind=True))
        for k in (1, 2):
            out += [(point, keep) for keep in itertools.combinations(pool, k)]
        out.append((point, pool))
    return out


def test_hybrid_served_under_eviction_matches_unbounded():
    tracer = Tracer()
    tables = {}
    for budget in (None, 200):
        db = tiny_db(0)
        ex = SparseExecutor()
        ex.tracer = tracer
        strat = make_strategy("HYBRID", executor=ex,
                              cache_budget_bytes=budget)
        lattice = build_lattice(db.schema, 2)
        strat.prepare(db, lattice)
        svc = strat.service().set_tracer(tracer)
        qs = _queries(db.schema, lattice)
        tables[budget] = svc.complete_many(qs)
    cache = strat.engine.cache
    assert cache.evictions + cache.dropped > 0
    # the evicted full tables came back through the batched miss path
    assert sum(r.attrs["from_data"]
               for r in _outside_prepare(tracer, "count.complete")) > 0
    for got, want in zip(tables[200], tables[None]):
        assert got.vars == want.vars
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(want.counts))


def test_hybrid_served_after_write_matches_fresh_strategy():
    db = tiny_db(0)
    lattice = build_lattice(db.schema, 2)
    strat = make_strategy("HYBRID", executor="sparse")
    strat.prepare(db, lattice)
    svc = strat.service()
    qs = _queries(db.schema, lattice)
    before = svc.complete_many(qs)
    rng = np.random.default_rng(11)
    src, dst = fresh_pairs(db, "Reg", 3, rng)
    svc.insert_facts("Reg", src, dst,
                     {"grade": rng.integers(0, 2, size=3).astype(np.int32)})
    got = svc.complete_many(qs)
    fresh = make_strategy("HYBRID", executor="sparse")
    fresh.prepare(db, lattice)
    changed = 0
    for (point, keep), old, tab in zip(qs, before, got):
        want = fresh.family_ct(point, keep)
        assert tab.vars == want.vars
        np.testing.assert_array_equal(np.asarray(tab.counts),
                                      np.asarray(want.counts))
        changed += not np.array_equal(np.asarray(old.counts),
                                      np.asarray(tab.counts))
    assert changed > 0
