"""The Möbius Join: extend positive ct-tables to complete ct-tables.

Inclusion–exclusion over relationship indicators (Qian, Schulte & Sun 2014):
for a final configuration with relation set ``A`` true and ``B`` false,

    N[A=T, B=F, attrs] = sum_{S subseteq B} (-1)^|S| ct_+[A u S true, attrs]

No access to the original data is needed: every term is a positive ct-table of
a *sub-pattern*, served by a :class:`PositiveProvider` — one of the policies
in :mod:`repro.core.engine` (cached-full for PRECOUNT/HYBRID, on-demand for
ONDEMAND, message recombination for TUPLEID), all backed by the shared
planner/executor/cache machinery — with disconnected sub-patterns
factorising into outer products of component tables and per-variable
histograms.

The join runs on the host (:func:`~repro.core.ct.on_host`).  Its tables
are small — a family's cells, 2^k blocks of them — while its counts are
not: a complete cell counts groundings of every variable of the point
(200 k^3 for a VisualGenome chain of two relationships), and a positive
cell of such a chain ~18 M, past the 2**24 up to which float32 holds
integers.  The blocks arrive in float64, exact where the provider
projects a pre-counted table (HYBRID, PRECOUNT); the inclusion–exclusion
subtracts in ``dtype``, the precision the engine contracts in, so a
complete cell carries that precision's rounding.  The all-true cells,
which no subtraction writes, are the positive counts as the provider
gave them.

Two equivalent evaluation orders are implemented:

* ``blockwise`` — explicit 3^k-term sum, handles kept edge attributes (whose
  axes only exist while their relation is true; when false they collapse to
  the N/A slot).
* ``butterfly`` — the superset Möbius transform as k in-place passes
  ``F-slice = *-slice − T-slice`` over a [2^k, D] stack
  (:func:`superset_mobius`).  Used when no edge-attr axes are kept.

:func:`complete_ct_many` serves many queries with one block memo: the
families of one point share sub-pattern blocks (most notably the
all-unconstrained block, a product of histograms), and each distinct block
is projected and aligned once.

The transform output is integral and non-negative (counts); property tests
assert both.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Protocol, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from ..obs.trace import NULL_TRACER, NullTracer
from .contract import CostStats
from .ct import CtTable, on_host
from .variables import (Atom, CtVar, LatticePoint, Var, connected_components,
                        rind_var)


class PositiveProvider(Protocol):
    """Source of positive ct-tables and variable histograms."""

    def positive(self, point: LatticePoint, keep: Tuple[CtVar, ...]) -> CtTable: ...

    def hist(self, var: Var, keep: Tuple[CtVar, ...]) -> CtTable: ...


# --------------------------------------------------------------------------
# superset Möbius transform
# --------------------------------------------------------------------------

def _in_dtype(op, a: np.ndarray, b: np.ndarray, dtype) -> np.ndarray:
    """``op(a, b)`` computed in ``dtype`` and held in ``a``'s dtype."""
    dt = jnp.dtype(dtype)
    return op(a.astype(dt), b.astype(dt)).astype(a.dtype)


def superset_mobius(stack: np.ndarray, k: int,
                    dtype=jnp.float32) -> np.ndarray:
    """In the leading ``k`` axes (each of size 2, index 1 = "relation true",
    index 0 = "unconstrained"), replace index 0 with "relation false" by
    applying ``x0 <- x0 - x1`` per axis.  Equivalent to
    ``N[A] = sum_{S >= A} (-1)^{|S|-|A|} Y[S]``.  The subtractions run in
    ``dtype``; the all-true corner, which none writes, keeps ``stack``'s
    values."""
    x = np.asarray(stack)
    for i in range(k):
        x1 = np.take(x, 1, axis=i)
        x0 = _in_dtype(np.subtract, np.take(x, 0, axis=i), x1, dtype)
        x = np.stack([x0, x1], axis=i)
    return x


# --------------------------------------------------------------------------
# butterfly plumbing shared by the per-query and batched complete-CT paths
# --------------------------------------------------------------------------

class _ButterflyPlan:
    """Static description of one butterfly-eligible complete-CT query:
    the kept axes split into attrs vs indicator relations, plus the final
    transpose from transform layout to request layout."""

    __slots__ = ("keep", "kept_attrs", "effective", "k", "perm")

    def __init__(self, keep, kept_attrs, effective, k, perm):
        self.keep, self.kept_attrs = keep, kept_attrs
        self.effective, self.k, self.perm = effective, k, perm


def _butterfly_plan(point: LatticePoint,
                    keep: Tuple[CtVar, ...]) -> Optional[_ButterflyPlan]:
    """The butterfly evaluation plan for ``(point, keep)``, or ``None``
    when the query is not butterfly-eligible (kept edge-attr axes need the
    blockwise N/A-slot handling; ``k == 0`` has no indicator axes to
    transform)."""
    kept_attrs = tuple(v for v in keep if v.kind == "attr")
    kept_edges = [v for v in keep if v.kind == "edge"]
    kept_rinds = {v.owner[0] for v in keep if v.kind == "rind"}
    effective = tuple(sorted(kept_rinds))
    k = len(effective)
    if kept_edges or k == 0:
        return None
    # rind axis i = effective[i] ({0:F, 1:T} matches the rind_var
    # convention), attr axis k+j = kept_attrs[j]; one transpose replaces
    # 2^k scatter dispatches (§Perf H3 it.1).
    src_axis = ({rind_var(r).owner: i for i, r in enumerate(effective)}
                | {v.owner: k + j for j, v in enumerate(kept_attrs)})
    perm = tuple(src_axis[v.owner] for v in keep)
    return _ButterflyPlan(keep, kept_attrs, effective, k, perm)


def _join_blocks(keep: Tuple[CtVar, ...]) -> int:
    """The blocks one complete table's Möbius join assembles: 2^k, one per
    truth assignment of the k relationships with a kept indicator or edge
    axis (the ``blocks`` counter of ``count.negative`` spans)."""
    return 1 << len({v.owner[0] for v in keep if v.kind in ("rind", "edge")})


def _butterfly_blocks(point: LatticePoint, bp: _ButterflyPlan,
                      provider: PositiveProvider,
                      memo: Optional[Dict] = None) -> List[np.ndarray]:
    """The aligned transform-input blocks, one per ``{*,T}^k`` corner in
    ``itertools.product`` order: Y[c] = ct_+(T-set of c) over the kept
    attrs (positive phase of the Möbius join).

    ``memo`` (used by :func:`complete_ct_many`) caches the aligned block
    arrays across a batch of queries: a same-signature flood shares its
    sub-pattern tables — most notably the all-unconstrained block, a pure
    product of histograms identical for every family over the same
    variables — so the per-query assembly glue runs once per DISTINCT
    block, not once per family."""
    blocks = []
    for bits in itertools.product((0, 1), repeat=bp.k):
        X = {r for r, b in zip(bp.effective, bits) if b == 1}
        blk = None
        mkey = None
        if memo is not None:
            # everything the block depends on: the sub-pattern's atoms,
            # the point's var set (histogram factors), the kept axes
            mkey = (tuple(a for a in point.atoms if a.rel in X),
                    tuple(point.vars), bp.kept_attrs)
            blk = memo.get(mkey)
        if blk is None:
            t = _pattern_table(point, X, bp.kept_attrs, provider)
            blk = t.transpose_to(bp.kept_attrs).counts
            if memo is not None:
                memo[mkey] = blk
        blocks.append(blk)
    return blocks


def _butterfly_transform(bp: _ButterflyPlan,
                         blocks: Sequence[np.ndarray], dtype) -> CtTable:
    """Stack the blocks to ``(2,)*k + attr_shape``, transform in
    ``dtype``, and transpose to the request's axis order: the complete
    ct-table."""
    attr_shape = tuple(v.card for v in bp.kept_attrs)
    stack = np.stack(blocks).reshape((2,) * bp.k + attr_shape)
    return CtTable(bp.keep, np.transpose(
        superset_mobius(stack, bp.k, dtype), bp.perm))


# --------------------------------------------------------------------------
# pattern tables: positive count of a relation subset over the point's vars
# --------------------------------------------------------------------------

def _pattern_table(point: LatticePoint, rels: Set[str],
                   keep_axes: Tuple[CtVar, ...],
                   provider: PositiveProvider) -> CtTable:
    """ct_+ of the sub-pattern with ``rels`` true, over all vars of ``point``,
    projected onto ``keep_axes`` (entity attrs + edge attrs of rels), on
    the host in float64: products of exact counts, exact to 2**53."""
    atoms = tuple(a for a in point.atoms if a.rel in rels)
    out: Optional[CtTable] = None
    covered: Set[Var] = set()
    for comp in connected_components(atoms):
        cp = LatticePoint(comp)
        comp_rels = {a.rel for a in comp}
        ckeep = tuple(v for v in keep_axes
                      if (v.kind == "attr" and v.owner[0] in cp.vars)
                      or (v.kind == "edge" and v.owner[0] in comp_rels))
        t = on_host(provider.positive(cp, ckeep))
        out = t if out is None else out.outer(t)
        covered.update(cp.vars)
    for var in point.vars:
        if var in covered:
            continue
        vkeep = tuple(v for v in keep_axes
                      if v.kind == "attr" and v.owner[0] == var)
        h = on_host(provider.hist(var, vkeep))
        out = h if out is None else out.outer(h)
    assert out is not None
    return out.transpose_to(tuple(v for v in keep_axes if v in out.vars)) \
        if set(out.vars) == set(keep_axes) else out.project(keep_axes)


def positive_queries(point: LatticePoint, keep: Sequence[CtVar],
                     use_butterfly: bool = True
                     ) -> List[Tuple[LatticePoint, Tuple[CtVar, ...]]]:
    """The positive sub-queries :func:`complete_ct` will request from its
    provider for ``(point, keep)``, in request order.

    This mirrors the Möbius join's own enumeration (butterfly vs blockwise
    branch, relation dropping, connected-component factorisation) without
    touching any data — it is what lets a serving layer batch a whole
    round of family queries into signature buckets *before* any Möbius
    join runs (see :meth:`repro.serve.service.CountingService.prefetch`).
    Per-variable histogram queries are omitted: they are cheap, shared,
    and cached on first use.  Duplicates across terms are preserved
    (callers dedupe); every entry is a connected sub-pattern.
    """
    keep = tuple(keep)
    kept_attrs = tuple(v for v in keep if v.kind == "attr")
    kept_edges: Dict[str, List[CtVar]] = {}
    for v in keep:
        if v.kind == "edge":
            kept_edges.setdefault(v.owner[0], []).append(v)
    kept_rinds = {v.owner[0] for v in keep if v.kind == "rind"}
    effective = sorted(set(kept_edges) | kept_rinds)
    k = len(effective)

    out: List[Tuple[LatticePoint, Tuple[CtVar, ...]]] = []

    def pattern(rels: Set[str], keep_axes: Tuple[CtVar, ...]) -> None:
        atoms = tuple(a for a in point.atoms if a.rel in rels)
        for comp in connected_components(atoms):
            cp = LatticePoint(comp)
            comp_rels = {a.rel for a in comp}
            ckeep = tuple(v for v in keep_axes
                          if (v.kind == "attr" and v.owner[0] in cp.vars)
                          or (v.kind == "edge" and v.owner[0] in comp_rels))
            out.append((cp, ckeep))

    if use_butterfly and not kept_edges and k > 0:
        for bits in itertools.product((0, 1), repeat=k):
            pattern({r for r, b in zip(effective, bits) if b == 1},
                    kept_attrs)
    else:
        for r_bits in itertools.product((0, 1), repeat=k):
            A = {r for r, b in zip(effective, r_bits) if b == 1}
            B = [r for r in effective if r not in A]
            axes_A = kept_attrs + tuple(
                v for r in sorted(A) for v in kept_edges.get(r, ()))
            for j in range(len(B) + 1):
                for S in itertools.combinations(B, j):
                    pattern(A | set(S), axes_A)
    return out


# --------------------------------------------------------------------------
# complete ct-table
# --------------------------------------------------------------------------

def complete_ct(point: LatticePoint, keep: Sequence[CtVar],
                provider: PositiveProvider,
                stats: Optional[CostStats] = None,
                use_butterfly: bool = True,
                tracer: NullTracer = NULL_TRACER,
                dtype=jnp.float32) -> CtTable:
    """Complete ct-table over ``keep`` — the Möbius Join, on the host,
    subtracting in ``dtype`` (the engine's), inside a ``count.negative``
    span of one table on ``tracer`` (``blocks`` and ``blocks_built`` both
    its 2^k blocks: one table shares none).

    ``keep`` may contain entity-attr axes, edge-attr axes, and relationship
    indicator axes of the point.  Relations with neither a kept indicator nor
    a kept edge attribute impose no constraint once their indicator is summed
    out, so they are dropped from the pattern up front (this is what makes
    HYBRID's per-family tables small).
    """
    with tracer.span("count.negative") as sp:
        if tracer.enabled:
            n = _join_blocks(tuple(keep))
            sp.set(tables=1, blocks=n, blocks_built=n)
        return _complete_ct(point, keep, provider, stats, use_butterfly,
                            dtype)


def _complete_ct(point: LatticePoint, keep: Sequence[CtVar],
                 provider: PositiveProvider,
                 stats: Optional[CostStats], use_butterfly: bool,
                 dtype, memo: Optional[Dict] = None) -> CtTable:
    keep = tuple(keep)
    bp = _butterfly_plan(point, keep) if use_butterfly else None
    if bp is not None:
        # stack Y[c in {*,T}^k] = ct_+(T-set of c), butterfly to {F,T}^k;
        # with no edge axes the complete table IS the transform output, up
        # to axis order.
        tab = _butterfly_transform(
            bp, _butterfly_blocks(point, bp, provider, memo), dtype)
    else:
        tab = CtTable(keep, _blockwise(point, keep, provider, dtype))
    if stats is not None:
        stats.ct_cells += tab.size
    return tab


def _blockwise(point: LatticePoint, keep: Tuple[CtVar, ...],
               provider: PositiveProvider, dtype) -> np.ndarray:
    """The complete table by the explicit inclusion–exclusion sum, block
    by block (the order that handles kept edge-attr axes), summed in
    ``dtype``."""
    kept_attrs = tuple(v for v in keep if v.kind == "attr")
    kept_edges: Dict[str, List[CtVar]] = {}
    for v in keep:
        if v.kind == "edge":
            kept_edges.setdefault(v.owner[0], []).append(v)
    kept_rinds = {v.owner[0] for v in keep if v.kind == "rind"}
    effective = sorted(set(kept_edges) | kept_rinds)
    final = np.zeros(tuple(v.card for v in keep))
    for r_bits in itertools.product((0, 1), repeat=len(effective)):
        A = {r for r, b in zip(effective, r_bits) if b == 1}
        B = [r for r in effective if r not in A]
        axes_A = kept_attrs + tuple(
            v for r in sorted(A) for v in kept_edges.get(r, ()))
        acc: Optional[np.ndarray] = None
        for j in range(len(B) + 1):
            for S in itertools.combinations(B, j):
                t = _pattern_table(point, A | set(S), axes_A, provider)
                contrib = t.transpose_to(axes_A).counts
                acc = contrib if acc is None else _in_dtype(
                    np.subtract if j % 2 else np.add, acc, contrib, dtype)
        assert acc is not None
        _embed(final, keep, A, CtTable(axes_A, acc))
    return final


def _embed(final: np.ndarray, keep: Tuple[CtVar, ...], A: Set[str],
           table: CtTable) -> None:
    """Add block-A of a complete table into ``final``: kept indicators
    pinned to ``A``'s truth values, the edge axes of relations outside
    ``A`` to their N/A slot.  A kept edge axis without its indicator
    spans the N/A slot that the A-less block writes too, so blocks add."""
    idx: List[object] = []
    block_axes: List[CtVar] = []
    for v in keep:
        if v.kind == "rind":
            idx.append(1 if v.owner[0] in A else 0)
        elif v.kind == "edge" and v.owner[0] not in A:
            idx.append(v.card - 1)              # N/A slot
        else:
            idx.append(slice(None))
            block_axes.append(v)
    final[tuple(idx)] += table.transpose_to(tuple(block_axes)).counts


def complete_ct_many(queries: Sequence[Tuple[LatticePoint,
                                             Sequence[CtVar]]],
                     provider: PositiveProvider,
                     stats: Optional[CostStats] = None,
                     use_butterfly: bool = True,
                     tracer: NullTracer = NULL_TRACER,
                     dtype=jnp.float32) -> List[CtTable]:
    """Complete ct-tables for many ``(point, keep)`` queries, with one
    block memo across them, all inside one ``count.negative`` span on
    ``tracer``.  The span counts ``blocks``, the 2^k blocks of every
    table's join, and ``blocks_built``, those projected and aligned on the
    host: the difference is what the memo saved.

    Butterfly-eligible queries (no kept edge-attr axes, ``k > 0``) read
    their corner blocks through the memo — families of one point share
    sub-patterns, so each distinct block is assembled once.  Everything
    else (blockwise queries, ``k == 0``) is joined per query, as by
    :func:`complete_ct`.

    Args:
        queries: ``(point, keep)`` pairs; ``keep`` may contain attr, edge
            and rind axes of the point.
        provider: positive-table source (a policy from
            :mod:`repro.core.engine`).
        stats: optional :class:`~repro.core.contract.CostStats`;
            ``ct_cells`` accounting matches the per-query path.
        use_butterfly: as for :func:`complete_ct`.
        tracer: the request tracer of the engine behind ``provider``.
        dtype: the precision the join subtracts in, the engine's.

    Returns:
        One :class:`~repro.core.ct.CtTable` per query, positionally
        aligned with ``queries`` and identical to per-query
        :func:`complete_ct`.

    Usage::

        tabs = complete_ct_many([(point, keep) for keep in keeps], policy)
    """
    queries = [(point, tuple(keep)) for point, keep in queries]
    with tracer.span("count.negative") as sp:
        memo: Dict = {}      # cross-query block reuse within this batch
        tabs = [_complete_ct(point, keep, provider, stats, use_butterfly,
                             dtype, memo) for point, keep in queries]
        if tracer.enabled:
            # blockwise joins (and butterflies off) build every block; a
            # butterfly builds only its memo misses, one memo entry each
            unshared = sum(_join_blocks(keep) for point, keep in queries
                           if not use_butterfly
                           or _butterfly_plan(point, keep) is None)
            sp.set(tables=len(queries),
                   blocks=sum(_join_blocks(keep) for _, keep in queries),
                   blocks_built=unshared + len(memo))
        return tabs


# --------------------------------------------------------------------------
# delta propagation THROUGH the butterfly: writes stop flushing the
# negative phase
# --------------------------------------------------------------------------

def _butterfly_delta_blocks(point: LatticePoint, bp: _ButterflyPlan,
                            rel: str, provider: PositiveProvider,
                            memo: Dict) -> List[np.ndarray]:
    """Transform-input blocks of the COMPLETE-table *delta* for a write to
    ``rel``, in the same ``{*,T}^k`` corner order as
    :func:`_butterfly_blocks`.

    Each corner's block is the positive table of the sub-pattern with
    corner set ``X`` true, so it depends on ``rel``'s edge table iff
    ``rel in X`` (atoms of other relations never enter the sub-pattern —
    see :func:`_pattern_table`).  Corners without ``rel`` therefore have an
    exactly-zero delta and are materialised as zero blocks; corners with
    ``rel`` evaluate the SAME pattern assembly against a *delta provider*
    (positives contracted over the
    :meth:`~repro.core.database.FactDelta.as_db` view), which by
    multilinearity yields the exact per-block delta as long as the point
    uses ``rel`` in exactly one atom (callers guard this).

    ``memo`` is shared across a batch of queries: delta blocks dedupe by
    sub-pattern exactly like the full path's blocks.
    """
    zero = np.zeros(tuple(v.card for v in bp.kept_attrs))
    blocks = []
    for bits in itertools.product((0, 1), repeat=bp.k):
        X = {r for r, b in zip(bp.effective, bits) if b == 1}
        if rel not in X:
            blocks.append(zero)
            continue
        mkey = (tuple(a for a in point.atoms if a.rel in X),
                tuple(point.vars), bp.kept_attrs)
        blk = memo.get(mkey)
        if blk is None:
            t = _pattern_table(point, X, bp.kept_attrs, provider)
            blk = memo[mkey] = t.transpose_to(bp.kept_attrs).counts
        blocks.append(blk)
    return blocks


def _blockwise_ct_delta(point: LatticePoint, keep: Tuple[CtVar, ...],
                        rel: str, provider: PositiveProvider,
                        memo: Dict, dtype) -> CtTable:
    """Blockwise complete-table delta for queries the butterfly cannot
    serve (kept edge-attr axes need the N/A-slot block assembly).

    Mirrors :func:`complete_ct`'s blockwise branch, but keeps only the
    inclusion–exclusion terms whose pattern contains ``rel`` — every other
    term is independent of ``rel``'s edge multiset, so its delta is
    exactly zero.  ``provider`` serves delta positives (contractions over
    the :meth:`~repro.core.database.FactDelta.as_db` view), so the
    assembled tensor is the exact signed-magnitude delta of the resident
    table; callers guard that ``rel`` appears in exactly one atom.
    ``memo`` dedupes pattern tables across a batch of queries, with the
    same keying as :func:`_butterfly_delta_blocks`.
    """
    kept_attrs = tuple(v for v in keep if v.kind == "attr")
    kept_edges: Dict[str, List[CtVar]] = {}
    for v in keep:
        if v.kind == "edge":
            kept_edges.setdefault(v.owner[0], []).append(v)
    kept_rinds = {v.owner[0] for v in keep if v.kind == "rind"}
    effective = sorted(set(kept_edges) | kept_rinds)
    final = np.zeros(tuple(v.card for v in keep))
    for r_bits in itertools.product((0, 1), repeat=len(effective)):
        A = {r for r, b in zip(effective, r_bits) if b == 1}
        B = [r for r in effective if r not in A]
        axes_A = kept_attrs + tuple(
            v for r in sorted(A) for v in kept_edges.get(r, ()))
        acc: Optional[np.ndarray] = None
        for j in range(len(B) + 1):
            for S in itertools.combinations(B, j):
                X = A | set(S)
                if rel not in X:
                    continue                  # term independent of rel
                mkey = (tuple(a for a in point.atoms if a.rel in X),
                        tuple(point.vars), axes_A)
                blk = memo.get(mkey)
                if blk is None:
                    t = _pattern_table(point, X, axes_A, provider)
                    blk = memo[mkey] = t.transpose_to(axes_A).counts
                acc = (blk if j % 2 == 0 else -blk) if acc is None else (
                    _in_dtype(np.subtract if j % 2 else np.add, acc, blk,
                              dtype))
        if acc is not None:                   # else independent of rel
            _embed(final, keep, A, CtTable(axes_A, acc))
    return CtTable(keep, final)


def complete_ct_delta_many(queries: Sequence[Tuple[LatticePoint,
                                                   Sequence[CtVar]]],
                           rel: str,
                           provider: PositiveProvider,
                           stats: Optional[CostStats] = None,
                           dtype=jnp.float32
                           ) -> List[Tuple[str, Optional[CtTable]]]:
    """Delta tables for many resident complete-CT queries after a write to
    ``rel``, with one block memo across them, as :func:`complete_ct_many`.

    The Möbius transform is linear in its input blocks, so the delta of a
    complete table is the transform of the per-block deltas — no resident
    data is re-read and no full butterfly recompute happens.  ``provider``
    must serve *delta* positives: contractions over the
    :meth:`~repro.core.database.FactDelta.as_db` view, so that (by
    multilinearity of positive counts in each relation's edge multiset)
    each affected block's delta is exact; the engine adds
    ``delta.sign * result`` onto the resident table.

    Args:
        queries: ``(point, keep)`` pairs for the RESIDENT entries being
            maintained.
        rel: the relationship the delta wrote.
        provider: delta-positive source (full-valued ``hist``; the engine
            wraps its policy in a view-backed provider).
        stats, dtype: as for :func:`complete_ct_many`.

    Returns:
        One ``(status, table)`` per query, positionally aligned:

        * ``("delta", ct)`` — ``ct`` is the exact signed-magnitude delta in
          request axis order; add ``sign * ct`` to the resident table;
        * ``("zero", None)`` — the entry provably does not depend on
          ``rel``'s edges (indicator summed out): retain unchanged;
        * ``("fallback", None)`` — not delta-propagatable: ``rel``
          appears in more than one atom, where the delta view
          under-counts cross terms; the caller invalidates or recounts.
          (Kept edge-attr axes take the blockwise N/A-slot assembly
          instead of the transform — :func:`_blockwise_ct_delta` — but
          still yield ``"delta"``.)

    Usage::

        for (key, point, keep), (st, d) in zip(resident,
                complete_ct_delta_many(q, delta.rel, delta_provider)):
            ...
    """
    results: List[Tuple[str, Optional[CtTable]]] = []
    memo: Dict = {}
    for point, keep in queries:
        keep = tuple(keep)
        bp = _butterfly_plan(point, keep)
        effective = bp.effective if bp is not None else tuple(
            {v.owner[0] for v in keep if v.kind in ("edge", "rind")})
        if rel not in effective:
            # rel's indicator is summed out (or rel is not in the pattern
            # at all): every transform block is independent of rel's edge
            # table, so the resident value is already exact.
            results.append(("zero", None))
            continue
        if sum(1 for a in point.atoms if a.rel == rel) != 1:
            results.append(("fallback", None))   # cross terms
            continue
        if bp is None:
            # kept edge-attr axes: same linearity, blockwise assembly
            tab = _blockwise_ct_delta(point, keep, rel, provider, memo,
                                      dtype)
        else:
            tab = _butterfly_transform(bp, _butterfly_delta_blocks(
                point, bp, rel, provider, memo), dtype)
        if stats is not None:
            stats.ct_cells += tab.size
        results.append(("delta", tab))
    return results


def butterfly_delta(point: LatticePoint, keep: Sequence[CtVar], rel: str,
                    provider: PositiveProvider,
                    stats: Optional[CostStats] = None,
                    dtype=jnp.float32) -> Tuple[str, Optional[CtTable]]:
    """Single-query convenience over :func:`complete_ct_delta_many` — the
    ``(status, delta table)`` for one resident complete-CT entry after a
    write to ``rel``."""
    return complete_ct_delta_many([(point, keep)], rel, provider, stats,
                                  dtype)[0]
