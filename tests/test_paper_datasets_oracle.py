"""Every counting strategy on the sparse executor against the grounding
oracle, on the paper's eight Table 4 databases at test size."""

import numpy as np
import pytest

from repro.core import make_strategy
from repro.core.database import PAPER_DATASETS
from repro.core.oracle import oracle_ct
from tests._workloads import paper_case, relational_families

STRATEGIES = ("PRECOUNT", "ONDEMAND", "HYBRID", "TUPLEID")


def _positive_axes(keep):
    return tuple(v for v in keep if v.kind != "rind")


@pytest.fixture(scope="module")
def case(request):
    """One database, its lattice, its families and their oracle tables
    (positive, complete), built once for all four strategies."""
    db, lattice = paper_case(request.param)
    fams = relational_families(db, lattice)
    want = [(oracle_ct(db, point, _positive_axes(keep),
                       require_positive=True),
             oracle_ct(db, point, keep)) for point, keep in fams]
    return db, lattice, fams, want


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("case", PAPER_DATASETS, indirect=True)
def test_strategy_matches_the_oracle(case, strategy):
    """Positive tables match exactly, complete family tables within
    float32 rounding."""
    db, lattice, fams, want = case
    strat = make_strategy(strategy, executor="sparse")
    strat.prepare(db, lattice)
    for (point, keep), (pos, complete) in zip(fams, want):
        msg = f"{point} keep={[str(v) for v in keep]}"
        axes = _positive_axes(keep)
        got = strat.provider.positive(point, axes)
        assert got.vars == axes, msg
        np.testing.assert_array_equal(np.asarray(got.counts), pos,
                                      err_msg=msg)
        tab = strat.family_ct(point, keep)
        assert tab.vars == tuple(keep), msg
        np.testing.assert_allclose(np.asarray(tab.counts), complete,
                                   rtol=np.finfo(np.float32).eps, atol=0,
                                   err_msg=msg)
