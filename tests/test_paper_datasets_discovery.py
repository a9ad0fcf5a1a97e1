"""Served discovery over HYBRID's counting service learns what discovery
over the bare strategy learns, on the paper's eight Table 4 databases at
test size."""

import pytest

from repro.core import make_strategy
from repro.core.database import PAPER_DATASETS
from repro.discover import DiscoveryService
from repro.discover.providers import LocalCounts
from tests._workloads import paper_case


@pytest.mark.parametrize("dataset", PAPER_DATASETS)
def test_served_discovery_matches_local(dataset):
    db, lattice = paper_case(dataset)
    chain = max(point.length for point in lattice)
    strat = make_strategy("HYBRID", executor="sparse")
    strat.prepare(db, lattice)
    served = DiscoveryService(strat.service(),
                              max_chain_length=chain).discover()
    local = DiscoveryService(
        LocalCounts(make_strategy("HYBRID", executor="sparse"), db),
        max_chain_length=chain).discover()
    assert served.signature() == local.signature()
    assert served.score == local.score
    assert served.families_scored == local.families_scored
