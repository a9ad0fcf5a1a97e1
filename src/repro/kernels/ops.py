"""Jit'd public wrappers for the Pallas kernels, plus the single backend
probe that decides how they lower.

Every wrapper takes ``interpret=None`` and resolves it through
:func:`default_interpret`: one probe of ``jax.default_backend()`` —
CPU → ``True`` (the Pallas interpreter; Mosaic/Triton lowering needs a
real accelerator), TPU/GPU → ``False`` (native lowering).  If the
backend cannot be started the probe raises: it never falls back to the
interpreter, so a broken accelerator fails loudly instead of running
kernels a thousand times slower.  Resolution happens *outside* the
jitted inner functions, so flipping an override between calls takes
effect immediately (the bool is a static jit argument either way).

:func:`segsum_kernel_enabled` is the matching routing predicate for the
sparse executors' scatter-add hop (:mod:`.segsum_kernel`): on by default
only on accelerators (the interpreted kernel body is Python — orders of
magnitude slower than XLA's native scatter on CPU), and always capped at
``SEGSUM_KERNEL_MAX_SEGMENTS`` because the one-hot sweep costs
O(edges x segments) — huge flattened ``(parent, code)`` spaces stay on
``jax.ops.segment_sum``.

Two environment variables override the probe, for debugging only:
``REPRO_PALLAS_INTERPRET`` (``1``/``0``) forces interpret mode or native
lowering, and ``REPRO_SEGSUM_PALLAS`` (``1``/``0``) forces the segment-sum
route on or off (the CPU tests use it for kernel-parity coverage).  A
measured run must set neither; ``chip_smoke.py`` refuses both.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from .hist_kernel import segment_hist_pallas
from .bdeu_kernel import bdeu_pallas
from .segsum_kernel import segment_sum_ones_pallas, segment_sum_rows_pallas
from .ref import segment_hist_ref, bdeu_ref

# beyond this the O(edges x segments) one-hot sweep loses to XLA scatter
SEGSUM_KERNEL_MAX_SEGMENTS = 1 << 15


def _env_flag(name: str) -> Optional[bool]:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return None
    return v.strip().lower() in ("1", "true", "yes", "on")


@functools.lru_cache(maxsize=None)
def _on_accelerator() -> bool:
    # a backend that fails to start raises here: there is no fallback
    return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")


def default_interpret() -> bool:
    """The one backend probe behind every kernel entry point: ``True``
    (interpreter) on CPU, ``False`` (Mosaic on TPU / Triton on GPU) on an
    accelerator; ``REPRO_PALLAS_INTERPRET`` overrides."""
    env = _env_flag("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env
    return not _on_accelerator()


def _resolve(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def segsum_kernel_enabled(num_segments: int) -> bool:
    """Should a sparse scatter-add hop with this segment space route
    through the Pallas kernel (vs ``jax.ops.segment_sum``)?"""
    if num_segments > SEGSUM_KERNEL_MAX_SEGMENTS:
        return False
    forced = _env_flag("REPRO_SEGSUM_PALLAS")
    if forced is not None:
        return forced
    return _on_accelerator()


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segment_hist(codes: jnp.ndarray, values: jnp.ndarray,
                  num_segments: int, interpret: bool) -> jnp.ndarray:
    return segment_hist_pallas(codes, values, num_segments,
                               interpret=interpret)


def segment_hist(codes: jnp.ndarray, values: jnp.ndarray, num_segments: int,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    return _segment_hist(codes, values, num_segments,
                         interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _edge_segment_sum(seg: jnp.ndarray, rows: jnp.ndarray,
                      num_segments: int, interpret: bool) -> jnp.ndarray:
    return segment_sum_rows_pallas(seg, rows, num_segments,
                                   interpret=interpret)


def edge_segment_sum(seg: jnp.ndarray, rows: jnp.ndarray, num_segments: int,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel-backed ``out[p, :] = sum_{e: seg[e]==p} rows[e, :]`` — the
    sparse executor's dense-message hop."""
    return _edge_segment_sum(seg, rows, num_segments,
                             interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _ones_segment_sum(seg: jnp.ndarray, weights: jnp.ndarray,
                      num_segments: int, interpret: bool) -> jnp.ndarray:
    return segment_sum_ones_pallas(seg, weights, num_segments,
                                   interpret=interpret)


def ones_segment_sum(seg: jnp.ndarray, weights: jnp.ndarray,
                     num_segments: int,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel-backed weighted histogram ``out[p] = sum_{e: seg[e]==p}
    w[e]`` — the sparse executor's leaf hop and code histogram."""
    return _ones_segment_sum(seg, weights, num_segments,
                             interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("ess", "interpret"))
def _bdeu(nijk: jnp.ndarray, ess: float, interpret: bool) -> jnp.ndarray:
    return bdeu_pallas(nijk, ess=ess, interpret=interpret)


def bdeu(nijk: jnp.ndarray, ess: float = 1.0,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    return _bdeu(nijk, ess=ess, interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_attention(q, k, v, causal: bool, block_q: int, block_k: int,
                     interpret: bool):
    from .attention_kernel import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: Optional[bool] = None):
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=_resolve(interpret))
