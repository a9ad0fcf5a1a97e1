"""Pallas TPU kernel: superset Möbius transform (the Möbius Join core).

TPU adaptation: instead of k strided butterfly passes (pointer-chasing,
VPU-bound on sublanes), the whole transform over the 2^k relationship
configurations is a single small matmul by the precomputed transform matrix

    T[A, S] = (-1)^{|S \\ A|}  if S >= A  else 0      (bitmask order)

so the kernel is ``out = T @ X`` with X = [2^k, D] resident per D-tile — an
MXU op with perfect reuse of T.  For k <= 8 T is at most 256x256 (256 KiB
f32), far under VMEM.  The attribute axis D is tiled across the grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def mobius_matrix(k: int, dtype=np.float32) -> np.ndarray:
    """Dense superset-Möbius transform matrix over bitmasks of length k."""
    r = 1 << k
    t = np.zeros((r, r), dtype=dtype)
    for a in range(r):
        for s in range(r):
            if (a & s) == a:  # S superset of A
                t[a, s] = (-1.0) ** bin(s & ~a).count("1")
    return t


def _mobius_kernel(t_ref, x_ref, o_ref):
    t = t_ref[...]
    x = x_ref[...]
    # HIGHEST: full f32 passes; bf16 operands would round counts above 256
    o_ref[...] = jnp.dot(t, x, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def mobius_pallas(stack: jnp.ndarray, *, block_d: int = 512,
                  interpret: bool = True) -> jnp.ndarray:
    """Apply the superset Möbius transform to a [R=2^k, D] stack."""
    r, d = stack.shape
    k = r.bit_length() - 1
    assert 1 << k == r, "leading dim must be 2^k"
    rp = max(8, r)                       # sublane-align tiny stacks
    t = np.eye(rp, dtype=np.float32)
    t[:r, :r] = mobius_matrix(k)
    dp = ((d + block_d - 1) // block_d) * block_d
    x = stack.astype(jnp.float32)
    if rp != r or dp != d:
        x = jnp.pad(x, ((0, rp - r), (0, dp - d)))

    out = pl.pallas_call(
        _mobius_kernel,
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((rp, rp), lambda i: (0, 0)),        # T resident
            pl.BlockSpec((rp, block_d), lambda i: (0, i)),   # X tile
        ],
        out_specs=pl.BlockSpec((rp, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rp, dp), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(t), x)
    return out[:r, :d]
