"""The dense and the sparse executor count the same positive tables on the
paper's eight Table 4 databases at test size."""

import numpy as np
import pytest

from repro.core.database import PAPER_DATASETS
from repro.core.engine import CountingEngine
from tests._workloads import paper_case


@pytest.mark.parametrize("dataset", PAPER_DATASETS)
def test_dense_and_sparse_positives_agree(dataset):
    db, lattice = paper_case(dataset)
    dense, sparse = CountingEngine(db, "dense"), CountingEngine(db, "sparse")
    for point in lattice:
        want, got = dense.contract(point), sparse.contract(point)
        assert got.vars == want.vars
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(want.counts),
                                      err_msg=str(point))
