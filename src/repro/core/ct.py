"""Contingency tables (ct-tables).

The paper stores ct-tables as sparse SQL rows; on TPU we store them as dense
count tensors over the attribute value space, one axis per :class:`CtVar`.
Dense tensors keep projection (the PRECOUNT/HYBRID family-extraction
primitive) a pure ``sum`` over axes — a VPU-friendly reduction — and keep the
Möbius transform a strided butterfly.  (Sparsity is exploited upstream:
the sparse *executor* contracts raw edge lists in O(nnz) and only the
final table is dense — see :mod:`repro.core.executors`.)  Tables are the
unit of account in the byte-budgeted :class:`~repro.core.cache.CtCache`.

``nnz_rows`` reports the sparse-equivalent row count so benchmarks can be
compared against the paper's Table 5 numbers.  Reads that block on the
device go through :func:`to_host`, which spans them as ``host.read``.

Counts are integers.  The executors contract in their dtype on the
device (float32 holds every integer up to 2**24).  The small tables the
Möbius join consumes are brought to the host in float64 (:func:`on_host`,
exact to 2**53), where a table of NumPy counts keeps its algebra.  A
projection of a pre-counted full table (HYBRID, PRECOUNT) is summed there
and stays exact past 2**24: a VisualGenome chain of two relationships
has ~18 M groundings in one cell.  A positive that sums its dropped axes
on the device (ONDEMAND, TUPLEID, a service over a bare engine) carries
the dtype's rounding past 2**24, and so does every complete cell the
join subtracts (:mod:`repro.core.mobius`).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Iterable, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..obs.trace import NULL_TRACER, NullTracer
from .variables import CtVar


def _xp(counts):
    """The array module of ``counts``: NumPy for host tables, jax.numpy
    for device ones."""
    return np if isinstance(counts, np.ndarray) else jnp


def to_host(value, tracer: NullTracer = NULL_TRACER,
            site: str = "") -> np.ndarray:
    """Block on a device array and copy it to the host, inside a
    ``host.read`` span carrying the bytes read and the calling ``site``.

    Usage::

        n = int(to_host(jnp.count_nonzero(x), tracer, "nnz_rows"))
    """
    with tracer.span("host.read") as sp:
        out = np.asarray(value)
        if tracer.enabled:
            sp.set(nbytes=int(out.nbytes), site=site)
    return out


def on_host(tab: "CtTable", tracer: NullTracer = NULL_TRACER) -> "CtTable":
    """``tab`` with its counts on the host in float64 (a device table is
    read through :func:`to_host`; a float64 host table is returned as
    is).

    Usage::

        exact = on_host(policy.positive(point, keep), engine.tracer)
    """
    if isinstance(tab.counts, np.ndarray) and tab.counts.dtype == np.float64:
        return tab
    return CtTable(tab.vars, to_host(tab.counts, tracer,
                                     "on_host").astype(np.float64))


@dataclass
class CtTable:
    vars: Tuple[CtVar, ...]
    counts: jnp.ndarray               # shape == tuple(v.card for v in vars)

    def __post_init__(self) -> None:
        if isinstance(self.counts, np.generic):    # NumPy's 0-d results
            self.counts = np.asarray(self.counts)
        expect = tuple(v.card for v in self.vars)
        if tuple(self.counts.shape) != expect:
            raise ValueError(f"ct shape {self.counts.shape} != vars {expect}")

    # -- bookkeeping --------------------------------------------------------
    @property
    def size(self) -> int:
        """Dense cell count (memory proxy)."""
        return int(np.prod([v.card for v in self.vars], dtype=np.int64)) if self.vars else 1

    @property
    def nbytes(self) -> int:
        return int(self.counts.nbytes)

    def nnz_rows(self, tracer: NullTracer = NULL_TRACER) -> int:
        """Sparse-equivalent number of ct-table rows (paper Table 5)."""
        if isinstance(self.counts, np.ndarray):
            return int(np.count_nonzero(self.counts))
        return int(to_host(jnp.count_nonzero(self.counts), tracer,
                           "nnz_rows"))

    def total(self, tracer: NullTracer = NULL_TRACER) -> float:
        if isinstance(self.counts, np.ndarray):
            return float(np.sum(self.counts))
        return float(to_host(jnp.sum(self.counts), tracer, "total"))

    # -- algebra ------------------------------------------------------------
    def axis_of(self, var: CtVar) -> int:
        return self.vars.index(var)

    def project(self, keep: Sequence[CtVar]) -> "CtTable":
        """Marginalise onto ``keep`` (paper: *projection*), preserving the
        order given in ``keep``."""
        keep = tuple(keep)
        missing = [v for v in keep if v not in self.vars]
        if missing:
            raise KeyError(f"project: vars not in table: {missing}")
        xp = _xp(self.counts)
        drop = tuple(i for i, v in enumerate(self.vars) if v not in keep)
        counts = xp.sum(self.counts, axis=drop) if drop else self.counts
        cur = tuple(v for v in self.vars if v in keep)
        # permute to requested order
        perm = tuple(cur.index(v) for v in keep)
        counts = xp.transpose(counts, perm) if perm != tuple(range(len(perm))) else counts
        return CtTable(keep, counts)

    def transpose_to(self, order: Sequence[CtVar]) -> "CtTable":
        order = tuple(order)
        if set(order) != set(self.vars):
            raise ValueError("transpose_to needs the same var set")
        perm = tuple(self.vars.index(v) for v in order)
        return CtTable(order, _xp(self.counts).transpose(self.counts, perm))

    def _same_side(self, other: "CtTable") -> "CtTable":
        """``other`` where ``self`` lives: a host table pulls a device
        operand to the host, so host counts stay exact."""
        return on_host(other) if isinstance(self.counts, np.ndarray) \
            else other

    def outer(self, other: "CtTable") -> "CtTable":
        """Tensor (Cartesian) product — used to extend a component ct over
        unconstrained variables."""
        other = self._same_side(other)
        a = self.counts.reshape(self.counts.shape + (1,) * other.counts.ndim)
        return CtTable(self.vars + other.vars, a * other.counts)

    def scale(self, c) -> "CtTable":
        return CtTable(self.vars, self.counts * c)

    def __sub__(self, other: "CtTable") -> "CtTable":
        other = self._same_side(other).transpose_to(self.vars)
        return CtTable(self.vars, self.counts - other.counts)

    def __add__(self, other: "CtTable") -> "CtTable":
        other = self._same_side(other).transpose_to(self.vars)
        return CtTable(self.vars, self.counts + other.counts)


def scalar_table(value: float, dtype=jnp.float32) -> CtTable:
    return CtTable((), jnp.asarray(value, dtype=dtype))
