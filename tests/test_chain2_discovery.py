"""Served HYBRID discovery at chain length 2 against the plain reference.

The data is the benchmark's tiny ring (``bench/tests/benchtiny.TINY_RING``:
8 relationships over 4 entity types of 40) under the ``vg-full-c2``
configuration, so the lattice holds the same 16 two-relationship chains as
the VisualGenome cell.  One job runs the normal path
(``Strategy.prepare`` -> ``Strategy.service()`` -> ``ServiceCounts`` ->
``DiscoveryService``) and is compared with ``bench/reference``:

* every family table of every chain-2 point: positive cells exact, the
  complete table within the configuration's limit;
* every point's model score against the reference's float64 BDeu, and the
  chosen models against their neighbours;
* the k=2 butterfly, through the block memo, against the blockwise join;
* a chain with more groundings than float32 holds integers (2**24): its
  positive cells equal the reference's, and the cells the join subtracts
  in float32 are within the configuration's limit.
"""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench.data.generate import generate
from bench.harness import checks, system
from bench.harness.jobs import DiscoverLoop
from bench.reference.counts import complete_table
from bench.tests.benchtiny import TINY_RING
from repro.core.mobius import complete_ct, complete_ct_many
from repro.core.variables import rind_var

ROOT = Path(__file__).resolve().parents[1]
CONFIG = dict(json.loads((ROOT / "bench" / "configs" /
                          "vg-full-c2.json").read_text()), schema=TINY_RING)
N_CHAIN2 = 16
# ~140 k^2 / 1 k = 19.6 M groundings per two-relationship chain
WIDE = dict(TINY_RING, entities_per_type=1000, edges=[140_000] * 8)


@pytest.fixture(scope="module")
def run():
    data = generate(TINY_RING, 2 ** 33 + 16)
    loop = DiscoverLoop(CONFIG, system.build_db(data))
    job = loop.job()
    job.calls = [(p, k, system.table_array(t)) for p, k, t in job.calls]
    chain2 = [p for p in loop.lattice if len(p.atoms) == 2]
    return SimpleNamespace(data=data, loop=loop, job=job, chain2=chain2)


def _judged(values):
    return {v["name"]: v for v in checks.judge(values, CONFIG["limits"])}


def test_the_lattice_holds_the_chain2_points(run):
    assert len(run.loop.lattice) == 8 + N_CHAIN2
    assert len(run.chain2) == N_CHAIN2
    assert set(run.job.result.models) == set(run.loop.lattice)
    fetched = {p for p, _, _ in run.job.calls}
    assert set(run.chain2) <= fetched


@pytest.mark.parametrize("i", range(N_CHAIN2))
def test_chain2_family_tables_match_the_reference(run, i):
    point = run.chain2[i]
    calls = [c for c in run.job.calls if c[0] == point]
    assert calls
    # no models: only the tables of this point are compared
    sub = SimpleNamespace(calls=calls, result=SimpleNamespace(models={}))
    got = _judged(checks.discover_checks(sub, run.data, CONFIG["ess"],
                                         CONFIG["max_parents"]))
    assert got["positive_max_abs_diff"]["value"] == 0.0
    assert got["complete_max_rel_diff"]["ok"], got


def test_scores_and_search_match_the_reference_bdeu(run):
    got = _judged(checks.discover_checks(run.job, run.data, CONFIG["ess"],
                                         CONFIG["max_parents"]))
    assert all(v["ok"] for v in got.values()), got
    assert got["positive_max_abs_diff"]["value"] == 0.0


@pytest.mark.parametrize("i", range(0, N_CHAIN2, 3))
def test_k2_butterfly_equals_the_blockwise_join(run, i):
    strat = system.make_strategy(CONFIG, run.loop.executor)
    strat.prepare(run.loop.db, run.loop.lattice)
    point = run.chain2[i]
    nodes = point.all_ct_vars(strat.db.schema, include_rind=True)
    rinds = tuple(v for v in nodes if v.kind == "rind")
    attrs = [v for v in nodes if v.kind == "attr"]
    assert len(rinds) == 2
    keeps = [rinds, (attrs[0],) + rinds, rinds[::-1] + (attrs[1],),
             (attrs[0], attrs[2]) + rinds]
    butterfly = complete_ct_many([(point, k) for k in keeps],
                                 strat.provider)
    for keep, tab in zip(keeps, butterfly):
        block = complete_ct(point, keep, strat.provider, use_butterfly=False)
        assert tab.vars == block.vars == keep
        np.testing.assert_array_equal(np.asarray(tab.counts),
                                      np.asarray(block.counts))


def test_counts_past_float32_integers_are_exact():
    data = generate(WIDE, 2 ** 33 + 17)
    db = system.build_db(data)
    lattice = system.lattice(db, 2)
    strat = system.make_strategy(CONFIG, system.make_executor(CONFIG))
    strat.prepare(db, lattice)
    point = next(p for p in lattice if len(p.atoms) == 2)
    rinds = tuple(rind_var(a.rel) for a in point.atoms)
    attr = next(v for v in point.all_ct_vars(db.schema) if v.kind == "attr")
    keeps = [rinds, (attr,) + rinds]
    tabs = strat.service().complete_many([(point, k) for k in keeps])
    ref = complete_table(data, system.ref_atoms(point))
    for keep, tab in zip(keeps, tabs):
        want = ref.project([system.ref_axis(v) for v in keep])
        got = system.table_array(tab)
        np.testing.assert_array_equal(got[..., 1, 1], want[..., 1, 1])
        assert np.max(np.abs(got - want)) / np.max(want) \
            <= CONFIG["limits"]["complete_max_rel_diff"]
    assert ref.project([system.ref_axis(v) for v in rinds])[1, 1] > 2 ** 24
