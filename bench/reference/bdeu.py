"""Plain reference BDeu family score (float64)."""

from __future__ import annotations

import numpy as np
from scipy.special import gammaln


def bdeu(counts: np.ndarray, child_axis: int, ess: float = 1.0) -> float:
    """Log BDeu marginal likelihood of a family from its complete table:
    rows are parent configurations, columns the child's values."""
    t = np.moveaxis(np.asarray(counts, dtype=np.float64), child_axis, -1)
    r = t.shape[-1]
    nijk = t.reshape(-1, r)
    q = nijk.shape[0]
    a_j, a_jk = ess / q, ess / (q * r)
    nij = nijk.sum(axis=1)
    return float(np.sum(gammaln(a_j) - gammaln(nij + a_j))
                 + np.sum(gammaln(nijk + a_jk) - gammaln(a_jk)))
