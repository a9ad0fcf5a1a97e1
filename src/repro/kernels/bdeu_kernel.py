"""Pallas TPU kernel: BDeu family-score reduction.

The scoring hot loop is an lgamma-heavy reduction over N_ijk [Q, R] with Q =
parent configurations (large for big families) and R = child arity (small).
Zero-padded rows/columns contribute exactly 0 to the score (lgamma terms
cancel), so padding needs no masks.

Grid tiles Q; each tile folds its partial score into its own (8, 128) tile
of the partials array, summed by the wrapper.  All transcendentals run on
the VPU from VMEM-resident tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Lanczos approximation, g = 7 with nine terms (Numerical Recipes' choice)
_LANCZOS_G = 7.0
_LANCZOS = (0.99999999999980993, 676.5203681218851, -1259.1392167224028,
            771.32342877765313, -176.61502916214059, 12.507343278686905,
            -0.13857109526572012, 9.9843695780195716e-6,
            1.5056327351493116e-7)
_HALF_LOG_2PI = 0.9189385332046727


def _lgamma(x):
    """log Gamma(x) for x > 0 from ops Mosaic lowers (``lax.lgamma`` has no
    Pallas TPU lowering): the Lanczos series for Gamma(x + 1), brought down
    by the recurrence Gamma(x) = Gamma(x + 1) / x so that the tiny
    Dirichlet pseudo-counts stay accurate."""
    s = jnp.full_like(x, _LANCZOS[0])
    for i, c in enumerate(_LANCZOS[1:], start=1):
        s = s + c / (x + float(i))
    t = x + (_LANCZOS_G + 0.5)
    return (_HALF_LOG_2PI + (x + 0.5) * jnp.log(t) - t
            + jnp.log(s) - jnp.log(x))


def _bdeu_kernel(nijk_ref, o_ref, *, a_j: float, a_jk: float, r_true: int):
    nijk = nijk_ref[...]                                     # (Qb, Rp)
    nij = jnp.sum(nijk, axis=1, keepdims=True)               # (Qb, 1)
    # mask padded child-value columns to an exact 0 contribution (the lgamma
    # approximation is not bitwise-stable enough for cancellation to be exact)
    col = jax.lax.broadcasted_iota(jnp.int32, nijk.shape, 1)
    cells = jnp.where(col < r_true,
                      _lgamma(nijk + a_jk) - _lgamma(jnp.full_like(nijk, a_jk)),
                      0.0)
    per_j = _lgamma(jnp.full_like(nij, a_j)) - _lgamma(nij + a_j)   # (Qb, 1)
    contrib = cells + jnp.where(col == 0, per_j, 0.0)
    qb, rp = contrib.shape
    # fold the rows into one (8, 128)-tiled partial per Q-block
    o_ref[...] = jnp.sum(contrib.reshape(qb // 8, 8, rp), axis=0)


def bdeu_pallas(nijk: jnp.ndarray, ess: float = 1.0, *,
                block_q: int = 512, interpret: bool = True) -> jnp.ndarray:
    """BDeu score of N_ijk [Q, R]; returns a scalar f32."""
    q, r = nijk.shape
    a_j = float(ess / q)
    a_jk = float(ess / (q * r))
    qpad = ((q + block_q - 1) // block_q) * block_q
    rpad = ((r + 127) // 128) * 128
    x = jnp.pad(nijk.astype(jnp.float32), ((0, qpad - q), (0, rpad - r)))
    nblk = qpad // block_q

    partials = pl.pallas_call(
        functools.partial(_bdeu_kernel, a_j=a_j, a_jk=a_jk, r_true=r),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((block_q, rpad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, rpad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * 8, rpad), jnp.float32),
        interpret=interpret,
    )(x)
    # padded rows contribute lgamma(a_j)-lgamma(a_j)+R*0 = 0; padded columns
    # are masked to 0 -> the partial sums are exact.
    return jnp.sum(partials)
