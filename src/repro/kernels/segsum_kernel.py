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

That sweep costs O(edges x segments), so it is capped to small segment
spaces.  A leaf hop over a large parent axis instead takes
:func:`blocked_segment_sum_pallas`: its edges come sorted by parent into
tiles of ``TILE_PARENTS`` parents, each tile a fixed slab of slots, so a
grid step contracts one slab against one tile of parents and the cost is
O(slots x (codes + TILE_PARENTS)).
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


# parents per tile of the blocked layout: the lane width of a one-hot
# tile and of each output block
TILE_PARENTS = 256
# parent tiles per grid step: an input block's rows, one sublane tile
TILE_ROWS = 8


def _blocked_kernel(parent_ref, code_ref, w_ref, o_ref, *, num_codes: int):
    rows, slots = parent_ref.shape
    codes = jax.lax.broadcasted_iota(jnp.int32, (num_codes, slots), 0)
    parents = jax.lax.broadcasted_iota(jnp.int32, (TILE_PARENTS, slots), 0)
    for r in range(rows):
        # both one-hots are 0/1, exact in bfloat16; the MXU sums in f32
        by_code = jnp.where(code_ref[r:r + 1, :] == codes,
                            w_ref[r:r + 1, :], 0.0).astype(jnp.bfloat16)
        by_parent = (parent_ref[r:r + 1, :] == parents).astype(jnp.bfloat16)
        o_ref[:, r * TILE_PARENTS:(r + 1) * TILE_PARENTS] = \
            jax.lax.dot_general(by_code, by_parent,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)


def blocked_segment_sum_pallas(parent: jnp.ndarray, code: jnp.ndarray,
                               weights: jnp.ndarray, num_codes: int, *,
                               interpret: bool = True) -> jnp.ndarray:
    """``out[t * TILE_PARENTS + parent[t, s], code[t, s]] += weights[t, s]``
    over a parent-sorted slot layout: row ``t`` of the ``(T, S)`` inputs
    holds the edges of parents ``[t * TILE_PARENTS, (t + 1) *
    TILE_PARENTS)``, ``parent`` their parent's offset in that tile and
    ``code`` their code, padding slots with weight 0.  ``T`` is below or a
    multiple of ``TILE_ROWS``, the tiles of one grid step, and ``S`` a
    multiple of 128.  Returns ``(T * TILE_PARENTS, num_codes)``.

    Each tile is ``onehot(code)^T @ onehot(parent)`` on the MXU, in
    bfloat16 with float32 sums: exact while no cell passes 2**24."""
    tiles, slots = parent.shape
    rows = min(tiles, TILE_ROWS)
    if tiles % rows:
        raise ValueError(f"{tiles} tiles is no multiple of {TILE_ROWS}")
    block = pl.BlockSpec((rows, slots), lambda t: (t, 0))
    out = pl.pallas_call(
        functools.partial(_blocked_kernel, num_codes=num_codes),
        grid=(tiles // rows,),
        in_specs=[block, block, block],
        out_specs=pl.BlockSpec((num_codes, rows * TILE_PARENTS),
                               lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((num_codes, tiles * TILE_PARENTS),
                                       jnp.float32),
        interpret=interpret,
    )(parent.astype(jnp.int32), code.astype(jnp.int32),
      weights.astype(jnp.float32))
    return out.T
