"""BDeu scoring of families from contingency tables (paper Eq. 1).

The ct-table for (parents, child) is reshaped to ``N_ijk`` with ``j`` ranging
over parent configurations and ``k`` over child values; the score is the usual
Dirichlet-multinomial marginal likelihood with equivalent sample size ``N'``.
The lgamma-heavy reduction is the scoring hot spot — mirrored by the Pallas
kernel in ``kernels/bdeu_kernel.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

from ..obs.trace import NULL_TRACER, NullTracer
from .ct import CtTable, to_host
from .variables import CtVar


def _bdeu_2d(nijk: jnp.ndarray, ess: float) -> jnp.ndarray:
    nijk = nijk.astype(jnp.float32)
    q, r = nijk.shape
    a_j = ess / q
    a_jk = ess / (q * r)
    nij = jnp.sum(nijk, axis=1)
    per_j = (gammaln(a_j) - gammaln(nij + a_j)
             + jnp.sum(gammaln(nijk + a_jk) - gammaln(a_jk), axis=1))
    return jnp.sum(per_j)


@partial(jax.jit, static_argnames=("ess",))
def bdeu_score_2d(nijk: jnp.ndarray, ess: float = 1.0) -> jnp.ndarray:
    """BDeu log marginal likelihood for N_ijk of shape (q, r)."""
    return _bdeu_2d(nijk, ess)


@partial(jax.jit, static_argnames=("ess",))
def bdeu_score_batch(nijk: jnp.ndarray, ess: float = 1.0) -> jnp.ndarray:
    """Batched BDeu: ``(B, q, r) -> (B,)`` in one vmapped call.

    Structure search groups same-shape families per hill-climbing round and
    scores each group here instead of one Python round-trip per family —
    one XLA dispatch amortises the lgamma-heavy reduction across the whole
    candidate set."""
    return jax.vmap(lambda t: _bdeu_2d(t, ess))(nijk)


def family_nijk(tab: CtTable, child: CtVar) -> jnp.ndarray:
    """Reshape a family's complete ct-table to ``N_ijk`` of shape (q, r):
    parent configurations × child values, child axis last."""
    order = tuple(v for v in tab.vars if v != child) + (child,)
    t = tab.transpose_to(order)
    return t.counts.reshape((-1, child.card))


def family_score(tab: CtTable, child: CtVar, ess: float = 1.0,
                 score_fn=None, tracer: NullTracer = NULL_TRACER) -> float:
    """Score a family from its complete ct-table.  ``tab`` must contain the
    child axis and any number of parent axes.  The score's read back to
    the host is a ``host.read`` span on ``tracer``."""
    nijk = family_nijk(tab, child)
    fn = score_fn or bdeu_score_2d
    return float(to_host(fn(nijk, ess=ess), tracer, "family_score"))
