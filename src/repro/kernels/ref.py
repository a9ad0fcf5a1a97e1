"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln


def segment_hist_ref(codes: jnp.ndarray, values: jnp.ndarray,
                     num_segments: int) -> jnp.ndarray:
    """Weighted histogram / segment-sum: out[p, d] = sum_{n: codes[n]=p} values[n, d]."""
    return jax.ops.segment_sum(values, codes, num_segments=num_segments)


def edge_segment_sum_ref(seg: jnp.ndarray, rows: jnp.ndarray,
                         num_segments: int) -> jnp.ndarray:
    """Sparse hop scatter-add: out[p, d] = sum_{e: seg[e]=p} rows[e, d];
    out-of-range segment ids (edge-bucket padding) are dropped."""
    return jax.ops.segment_sum(rows, seg, num_segments=num_segments)


def ones_segment_sum_ref(seg: jnp.ndarray, weights: jnp.ndarray,
                         num_segments: int) -> jnp.ndarray:
    """Weighted histogram: out[p] = sum_{e: seg[e]=p} weights[e]."""
    return jax.ops.segment_sum(weights, seg, num_segments=num_segments)


def bdeu_ref(nijk: jnp.ndarray, ess: float, q: int, r: int) -> jnp.ndarray:
    """BDeu log marginal likelihood over N_ijk [Q, R] (Q may be padded with
    zero rows and R with zero columns — both contribute exactly 0)."""
    a_j = ess / q
    a_jk = ess / (q * r)
    nij = jnp.sum(nijk, axis=1)
    per_j = (gammaln(a_j) - gammaln(nij + a_j)
             + jnp.sum(gammaln(nijk + a_jk) - gammaln(a_jk), axis=1))
    return jnp.sum(per_j)
