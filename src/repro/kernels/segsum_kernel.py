"""Pallas TPU kernel: edge scatter-add — the sparse executor's hop primitive.

``SparseExecutor`` reduces every positive-count hop to one scatter-add over
the raw edge list,

    out[p, d] = sum_{e : seg[e] == p} rows[e, d]        (dense-message hop)
    out[p]    = sum_{e : seg[e] == p} w[e]              (leaf hop / histogram)

where ``seg`` flattens ``(parent entity, mixed-radix attr code)`` into one
int32 segment id.  Scatter-add is hostile to the TPU memory system, so —
like :mod:`.hist_kernel` — the reduction is recast as a one-hot contraction
that runs on the MXU/VPU: the one-hot tile is built *inside* the kernel
from a ``broadcasted_iota`` comparison and never touches HBM.

What distinguishes this kernel from ``segment_hist`` is its consumer: the
flattened ``(parent, code)`` space means ``num_segments`` is routinely in
the 1e3–1e5 range while the edge axis is the long streamed dimension, and
the executor pads edge buckets with ``seg == num_segments`` (one past the
last real segment).  Out-of-range ids match no one-hot column of any tile
— padding is dropped exactly as ``jax.ops.segment_sum`` drops it, and any
spill into the padded tail rows is sliced away on return.

Grid layout: segments on the outer (parallel) grid dimension, edges on the
innermost (sequential) dimension with ``+=`` accumulation, so each output
tile stays resident in VMEM while the edge stream passes through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# full f32 passes on the MXU: the default rounds operands to bf16, which
# is exact for the one-hot side but not for counts above 256
_EXACT = jax.lax.Precision.HIGHEST


def _rows_kernel(seg_ref, rows_ref, o_ref, *, block_p: int):
    p_idx = pl.program_id(0)
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[...]                                   # (Nb,)
    rows = rows_ref[...]                                 # (Nb, Db)
    base = p_idx * block_p
    col = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], block_p), 1)
    onehot = (seg[:, None] - base == col).astype(jnp.float32)   # (Nb, Pb)
    o_ref[...] += jnp.dot(onehot.T, rows, precision=_EXACT,
                          preferred_element_type=jnp.float32)


def _ones_kernel(seg_ref, w_ref, o_ref, *, block_p: int):
    p_idx = pl.program_id(0)
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[...]                                   # (Nb,)
    w = w_ref[...]                                       # (Nb,)
    base = p_idx * block_p
    col = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], block_p), 1)
    onehot = (seg[:, None] - base == col).astype(jnp.float32)   # (Nb, Pb)
    o_ref[...] += jnp.dot(w[None, :], onehot, precision=_EXACT,
                          preferred_element_type=jnp.float32)   # (1, Pb)


def segment_sum_rows_pallas(seg: jnp.ndarray, rows: jnp.ndarray,
                            num_segments: int, *, block_n: int = 1024,
                            block_p: int = 256, block_d: int = 256,
                            interpret: bool = True) -> jnp.ndarray:
    """``out[p, d] = sum_{e: seg[e]==p} rows[e, d]`` for ``rows`` [N, D].

    Out-of-range segment ids (the executor's ``seg == num_segments`` edge
    padding, or the -1 this wrapper pads with) contribute nothing."""
    n, d = rows.shape
    npad = ((n + block_n - 1) // block_n) * block_n if n else block_n
    dpad = ((d + block_d - 1) // block_d) * block_d
    ppad = ((num_segments + block_p - 1) // block_p) * block_p
    seg_p = jnp.pad(seg.astype(jnp.int32), (0, npad - n),
                    constant_values=-1)
    rows_p = jnp.pad(rows.astype(jnp.float32),
                     ((0, npad - n), (0, dpad - d)))

    out = pl.pallas_call(
        functools.partial(_rows_kernel, block_p=block_p),
        grid=(ppad // block_p, dpad // block_d, npad // block_n),
        in_specs=[
            pl.BlockSpec((block_n,), lambda p, dd, nn: (nn,)),
            pl.BlockSpec((block_n, block_d), lambda p, dd, nn: (nn, dd)),
        ],
        out_specs=pl.BlockSpec((block_p, block_d),
                               lambda p, dd, nn: (p, dd)),
        out_shape=jax.ShapeDtypeStruct((ppad, dpad), jnp.float32),
        interpret=interpret,
    )(seg_p, rows_p)
    return out[:num_segments, :d]


def segment_sum_ones_pallas(seg: jnp.ndarray, weights: jnp.ndarray,
                            num_segments: int, *, block_n: int = 1024,
                            block_p: int = 256,
                            interpret: bool = True) -> jnp.ndarray:
    """``out[p] = sum_{e: seg[e]==p} weights[e]`` — the weighted histogram
    (leaf hops pass all-ones weights; the sharded executor passes its 0/1
    mesh-padding mask).  Output kept 2-D ``(1, P)`` inside the kernel for
    lane alignment, squeezed on return."""
    n = int(seg.shape[0])
    npad = ((n + block_n - 1) // block_n) * block_n if n else block_n
    ppad = ((num_segments + block_p - 1) // block_p) * block_p
    seg_p = jnp.pad(seg.astype(jnp.int32), (0, npad - n),
                    constant_values=-1)
    w_p = jnp.pad(weights.astype(jnp.float32), (0, npad - n))

    out = pl.pallas_call(
        functools.partial(_ones_kernel, block_p=block_p),
        grid=(ppad // block_p, npad // block_n),
        in_specs=[
            pl.BlockSpec((block_n,), lambda p, nn: (nn,)),
            pl.BlockSpec((block_n,), lambda p, nn: (nn,)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda p, nn: (0, p)),
        out_shape=jax.ShapeDtypeStruct((1, ppad), jnp.float32),
        interpret=interpret,
    )(seg_p, w_p)
    return out[0, :num_segments]
