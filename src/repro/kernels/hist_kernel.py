"""Pallas TPU kernel: weighted segment histogram (the JOIN hop inner loop).

``out[p, d] = sum_{n : codes[n] == p} values[n, d]``

TPU adaptation: scatter-add is hostile to the TPU memory system, so the hop
is recast as a one-hot matmul — ``out = OneHot(codes)^T @ values`` — which
runs on the MXU.  The one-hot tile is materialised *inside* the kernel from a
``broadcasted_iota`` comparison (never in HBM).  Grid: (segments x D x N)
tiles with accumulation over the N (sequential, innermost) dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(codes_ref, vals_ref, o_ref, *, block_p: int):
    n_idx = pl.program_id(2)
    p_idx = pl.program_id(0)

    @pl.when(n_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = codes_ref[...]                                  # (Nc,)
    vals = vals_ref[...]                                    # (Nc, Db)
    base = p_idx * block_p
    seg = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], block_p), 1)
    onehot = (codes[:, None] - base == seg).astype(jnp.float32)  # (Nc, Pb)
    # HIGHEST: full f32 passes; bf16 operands would round values
    o_ref[...] += jnp.dot(onehot.T, vals,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def segment_hist_pallas(codes: jnp.ndarray, values: jnp.ndarray,
                        num_segments: int, *, block_n: int = 1024,
                        block_p: int = 256, block_d: int = 256,
                        interpret: bool = True) -> jnp.ndarray:
    """Weighted histogram of ``values`` [N, D] into ``num_segments`` rows.

    Out-of-range codes (e.g. -1 padding) are dropped — they match no one-hot
    column."""
    n, d = values.shape
    npad = ((n + block_n - 1) // block_n) * block_n
    dpad = ((d + block_d - 1) // block_d) * block_d
    ppad = ((num_segments + block_p - 1) // block_p) * block_p
    codes_p = jnp.pad(codes.astype(jnp.int32), (0, npad - n),
                      constant_values=-1)
    vals_p = jnp.pad(values.astype(jnp.float32),
                     ((0, npad - n), (0, dpad - d)))

    out = pl.pallas_call(
        functools.partial(_hist_kernel, block_p=block_p),
        grid=(ppad // block_p, dpad // block_d, npad // block_n),
        in_specs=[
            pl.BlockSpec((block_n,), lambda p, dd, nn: (nn,)),
            pl.BlockSpec((block_n, block_d), lambda p, dd, nn: (nn, dd)),
        ],
        out_specs=pl.BlockSpec((block_p, block_d), lambda p, dd, nn: (p, dd)),
        out_shape=jax.ShapeDtypeStruct((ppad, dpad), jnp.float32),
        interpret=interpret,
    )(codes_p, vals_p)
    return out[:num_segments, :d]
