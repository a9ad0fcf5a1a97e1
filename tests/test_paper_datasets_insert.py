"""After a write through the counting service, HYBRID serves the complete
tables a fresh HYBRID counts on the written database, on the paper's eight
Table 4 databases at test size."""

import numpy as np
import pytest

from repro.core import make_strategy
from repro.core.database import PAPER_DATASETS
from tests._workloads import paper_case, relational_families
from tests.test_mutations import fresh_pairs

BATCH = 8


@pytest.mark.parametrize("dataset", PAPER_DATASETS)
def test_hybrid_tables_after_insert_match_a_recount(dataset):
    db, lattice = paper_case(dataset)
    fams = relational_families(db, lattice)
    strat = make_strategy("HYBRID", executor="sparse")
    strat.prepare(db, lattice)
    svc = strat.service()
    before = [svc.count_complete(point, keep) for point, keep in fams]

    rel = db.schema.relationships[0]
    rng = np.random.default_rng(0)
    src, dst = fresh_pairs(db, rel.name, BATCH, rng)
    svc.insert_facts(rel.name, src, dst,
                     {a.name: rng.integers(0, a.card, size=BATCH)
                      .astype(np.int32) for a in rel.attrs})

    fresh = make_strategy("HYBRID", executor="sparse")
    fresh.prepare(db, lattice)
    changed = 0
    for (point, keep), old in zip(fams, before):
        got = svc.count_complete(point, keep)
        want = fresh.family_ct(point, keep)
        assert got.vars == want.vars
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(want.counts),
                                      err_msg=f"{point} {keep}")
        changed += not np.array_equal(np.asarray(old.counts),
                                      np.asarray(got.counts))
    assert changed > 0
