"""Distributed relational counting over a device mesh.

Counting is linear in edge rows, so the JOIN sweep data-parallelises
perfectly: shard every relationship's edge list over the ``data`` mesh axis,
run the gather -> one-hot multiply -> segment-sum hop on local rows, and
``psum`` the per-entity partials.  Entity-indexed messages stay replicated
(they are small: n_entities x value-space); the Möbius join runs on the
host over the small tables the contraction returns.

Two mesh-sharded paths live here, mirroring the two executors:

* :func:`sharded_positive_ct` — the dense one-hot path, written directly
  against the database (predates the planner);
* :class:`ShardedSparseExecutor` — the O(nnz) path: a drop-in
  :class:`~repro.core.executors.SparseExecutor` whose mixed-radix
  segment-sum hops run under ``shard_map`` over the ``data`` axis.  It
  walks :class:`~repro.core.plan.ContractionPlan` unchanged — only the two
  device primitives (edge scatter-add, root combine) are replaced, so it
  inherits every strategy/Möbius/cache behaviour and is property-tested
  against the oracle like any registered executor
  (``EXECUTORS["sparse_sharded"]``).

This is the scale-out path for the paper's technique: the 15.8M-row Visual
Genome sweep becomes 15.8M / (pods x data) rows per chip with one all-reduce
per hop.  For scaling beyond one mesh — horizontally partitioned
*databases*, one service per shard — see :mod:`repro.core.database`
(``ShardedDatabase``) and :mod:`repro.serve.router`.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from .contract import CostStats, entity_onehot, _onehot, _expand
from .ct import CtTable
from .database import RelationalDB
from .executors import EXECUTORS, SparseExecutor, _kr_segment_sum
from .variables import Atom, CtVar, LatticePoint, Var, edge_var


def _pad_to(arr: np.ndarray, mult: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad axis 0 to a multiple of ``mult``; returns (padded, weight_mask)."""
    n = arr.shape[0]
    target = ((n + mult - 1) // mult) * mult
    pad = target - n
    w = np.ones(target, dtype=np.float32)
    if pad:
        arr = np.concatenate([arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])
        w[n:] = 0.0
    return arr, w


def _pad_rows(arr: jax.Array, mult: int) -> Tuple[jax.Array, jax.Array]:
    """:func:`_pad_to` for a device array, padded on the device: axis 0
    to a multiple of ``mult`` with zeros; returns (padded, weight_mask)."""
    n = int(arr.shape[0])
    pad = -n % mult
    w = jnp.pad(jnp.ones((n,), dtype=jnp.float32), (0, pad))
    return jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1)), w


def _unsharded(x: jax.Array) -> jax.Array:
    """``x`` as a plain array on the first device of its mesh, with no mesh
    in its type: the form in which every table leaves a sharded hop.

    The engine mixes these tables with its single-device arrays (reshapes,
    ``complete_ct``'s ``dynamic_update_slice``).  On a mesh with
    ``Explicit`` axes, what ``jax.make_mesh`` builds by default, a
    mesh-typed operand makes each such mix a sharding type error.  A
    replicated ``x`` is handed over without a copy; a sharded one is
    gathered."""
    return jax.device_put(
        x, SingleDeviceSharding(x.addressable_shards[0].device))


def _segsum_shard_kernel(total: int):
    """Per-rank Pallas scatter-add bodies for a ``shard_map`` closure, or
    ``None`` to stay on ``jax.ops.segment_sum``.  Resolved once when the
    closure is built — the cached jitted ``shard_map`` bakes the backend
    choice in, so the env override must be set before the first hop."""
    from ..kernels import ops as kernel_ops
    if not kernel_ops.segsum_kernel_enabled(total):
        return None
    from types import SimpleNamespace
    from ..kernels.segsum_kernel import (segment_sum_ones_pallas,
                                         segment_sum_rows_pallas)
    interp = kernel_ops.default_interpret()
    return SimpleNamespace(
        ones=functools.partial(segment_sum_ones_pallas, interpret=interp),
        rows=functools.partial(segment_sum_rows_pallas, interpret=interp))


def _sharded_hop(mesh: Mesh, axis: str, n_parent: int, n_hot: int, dtype,
                 value_axis: Optional[str] = None):
    """Build the shard_map'd join hop for a given arity.

    ``value_axis``: mesh axis to shard the child value-space (column) axis
    over.  The flattened output value axis is child-D-major, so a contiguous
    child-D shard stays a contiguous output shard — each ``value_axis`` rank
    computes its slice of columns for all rows, and the psum runs over
    ``axis`` only.  This puts the otherwise-idle TP ranks to work on the
    JOIN sweep (memory + collective terms drop by the TP degree — §Perf H3)."""

    def hop(child_msg, gidx, sidx, w, *hots):
        m = child_msg[gidx] * w[:, None].astype(dtype)       # (rows_l, D_l)
        for hot in hots:
            rl, d = m.shape
            m = (m[:, :, None] * hot[:, None, :]).reshape(rl, d * hot.shape[1])
        out = jax.ops.segment_sum(m, sidx, num_segments=n_parent)
        return jax.lax.psum(out, axis)

    vspec = value_axis
    in_specs = (P(None, vspec), P(axis), P(axis), P(axis)) + (P(axis),) * n_hot
    return shard_map(hop, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, vspec), check_vma=False)


def sharded_positive_ct(db: RelationalDB, point: LatticePoint,
                        keep: Optional[Sequence[CtVar]] = None,
                        *, mesh: Mesh, axis: str = "data",
                        dtype=jnp.float32,
                        stats: Optional[CostStats] = None) -> CtTable:
    """Positive ct-table (dense one-hot path) with edge tables sharded
    over ``axis`` of ``mesh``.

    Semantically identical to :func:`repro.core.contract.positive_ct`
    (tested against it); each tree hop performs local partial counts
    followed by one ``psum``.  When the mesh also has a ``model`` axis
    that divides a hop's value-space width, that hop's columns are
    sharded over it too (the otherwise-idle TP ranks join the sweep).

    Args:
        db: the database to count over.
        point: lattice point (>= 1 relationship atom).
        keep: ct-table axes to keep; defaults to every entity/edge
            attribute of the point (no indicator axes — positives only).
        mesh: the device mesh (keyword-only).
        axis: mesh axis to shard edge rows over.
        dtype: accumulation dtype of the counts.
        stats: optional :class:`~repro.core.contract.CostStats` to record
            join/row accounting into.

    Returns:
        The positive :class:`~repro.core.ct.CtTable` over ``keep``.

    Usage::

        tab = sharded_positive_ct(db, point, mesh=mesh, axis="data")
    """
    schema = db.schema
    if keep is None:
        keep = [v for v in point.all_ct_vars(schema, include_rind=False)]
    keep = list(keep)
    nsh = int(mesh.shape[axis])

    adj: Dict[Var, List[Tuple[Atom, Var]]] = {}
    for a in point.atoms:
        adj.setdefault(a.src, []).append((a, a.dst))
        adj.setdefault(a.dst, []).append((a, a.src))
    root = point.vars[0]

    def visit(v: Var, parent_atom: Optional[Atom]):
        msg, mvars = entity_onehot(db, v, keep, dtype)
        for atom, u in adj.get(v, ()):
            if atom is parent_atom:
                continue
            child_msg, child_vars = visit(u, atom)
            rt = db.relations[atom.rel]
            if u == atom.src:
                gidx_np, sidx_np = rt.src, rt.dst
                n_parent = db.entities[atom.dst.etype].size
            else:
                gidx_np, sidx_np = rt.dst, rt.src
                n_parent = db.entities[atom.src.etype].size
            gidx, w = _pad_to(gidx_np, nsh)
            sidx, _ = _pad_to(sidx_np, nsh)
            hots, hvars = [], list(child_vars)
            for a_ in rt.type.attrs:
                cv = edge_var(rt.type.name, a_.name, a_.card)
                if cv in keep:
                    col, _ = _pad_to(rt.attrs[a_.name], nsh)
                    hots.append(_onehot(jnp.asarray(col), cv.card, dtype))
                    hvars.append(cv)
            d_child = int(child_msg.shape[1])
            v_axis = ("model" if "model" in mesh.axis_names
                      and d_child % mesh.shape["model"] == 0
                      and mesh.shape["model"] > 1 else None)
            fn = _sharded_hop(mesh, axis, n_parent, len(hots), dtype,
                              value_axis=v_axis)
            child_msg = jax.device_put(child_msg, NamedSharding(mesh, P()))
            hop_out = _unsharded(fn(child_msg, jnp.asarray(gidx),
                                    jnp.asarray(sidx), jnp.asarray(w), *hots))
            if stats is not None:
                stats.joins += 1
                stats.rows_scanned += int(gidx.shape[0])
            n, d1 = msg.shape
            msg = (msg[:, :, None] * hop_out[:, None, :]).reshape(
                n, d1 * hop_out.shape[1])
            mvars = mvars + hvars
        return msg, mvars

    msg, mvars = visit(root, None)
    flat = jnp.sum(msg, axis=0)
    counts = flat.reshape(tuple(v.card for v in mvars)) if mvars else flat.reshape(())
    tab = CtTable(tuple(mvars), counts)
    order = tuple(v for v in keep if v in tab.vars)
    return tab.transpose_to(order) if order != tab.vars else tab


# ---------------------------------------------------------------------------
# sharded sparse executor: the O(nnz) path over a device mesh
# ---------------------------------------------------------------------------

class ShardedSparseExecutor(SparseExecutor):
    """:class:`~repro.core.executors.SparseExecutor` with its segment-sum
    device steps sharded over one mesh axis.

    The plan walk, the mixed-radix code arithmetic and the caching semantics
    are inherited unchanged; only the two device primitives change:

    * **edge scatter-add** (:meth:`_edge_segment_sum`) — the per-hop edge
      list (padded to a multiple of the shard count) is split over
      ``axis``; each rank ``segment_sum``-s its local rows into the full
      ``(parent, code)`` segment space and the partials merge with a single
      ``psum``.  This is the Möbius-join parallelisation of Qian & Schulte:
      sufficient statistics are sums over data partitions.
    * **root combine** (:meth:`_reduce_by_code`) — entity rows (root codes
      + factor matrices) are split over ``axis`` the same way; one
      ``psum`` of the ``(root_card, D)`` partial tables merges them.

    Both primitives keep their jitted ``shard_map`` closures in a keyed
    cache (``_shard_fn_cache``, one entry per distinct device-step shape —
    analogous to the executor's ``_batch_cache``), so a flood of
    same-shape hops traces each step ONCE instead of rebuilding and
    retracing the closure on every hop; ``trace_counts`` records actual
    trace events per key and is asserted flat across a flood in
    ``tests/test_distributed_counting.py``.

    Counts are integer-valued, so the per-rank reordering is exact: sharded
    results are numerically identical to :class:`SparseExecutor`
    (property-tested in ``tests/test_distributed_counting.py``).

    Stacked/vmapped batch dispatch is intentionally NOT sharded
    (``positive_batch`` falls back to per-plan sharded execution):
    scaling out a *flood* of queries is the database-sharding router's job
    (:mod:`repro.serve.router`), while this class scales out one large
    contraction.

    Args:
        dtype: as for :class:`~repro.core.executors.Executor`.
        mesh: the device mesh; defaults to a 1-D mesh over every visible
            device named ``(axis,)``.
        axis: mesh axis name to shard edge/entity rows over.

    Raises:
        ValueError: ``axis`` is not an axis of ``mesh``.

    Usage::

        ex = ShardedSparseExecutor(mesh=jax.make_mesh((8,), ("data",)))
        tab = CountingEngine(db, ex).contract(point, keep)
    """

    name = "sparse_sharded"

    def __init__(self, dtype=jnp.float32,
                 mesh: Optional[Mesh] = None, axis: str = "data"):
        super().__init__(dtype=dtype)
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()), (axis,))
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.n_ranks = int(mesh.shape[axis])
        self._row_sharding = NamedSharding(mesh, P(axis))
        # (kind, segment space, padded rows, widths...) -> jitted shard_map
        # closure; one trace per key, flat across a flood
        self._shard_fn_cache: Dict[Tuple, object] = {}
        self.trace_counts: Dict[Tuple, int] = {}
        self._force_local = False      # see local_mode()

    @contextmanager
    def local_mode(self):
        """Run device primitives UNSHARDED inside this context.  The
        engine's delta count maintenance contracts a handful of delta
        edges per cached entry — padding those to the mesh and paying a
        ``psum`` per hop costs more than the count itself, so the delta
        path drops to the inherited single-device segment-sums (exact
        either way; counts are integers).  Not re-entrant across threads:
        callers hold the service's execution fence."""
        prev, self._force_local = self._force_local, True
        try:
            yield self
        finally:
            self._force_local = prev

    def shard_rows(self, arr) -> jax.Array:
        """Place a row array (edge or entity rows, padded to a multiple of
        the rank count) split over ``axis``: the layout every sharded
        primitive takes its inputs in.  A host array's upload is a
        ``host.stage`` span."""
        if not isinstance(arr, np.ndarray):
            return jax.device_put(arr, self._row_sharding)
        tr = self.tracer
        with tr.span("host.stage") as sp:
            if tr.enabled:
                sp.set(nbytes=int(arr.nbytes))
            return jax.device_put(arr, self._row_sharding)

    # -- shard_map closure cache --------------------------------------------
    def _shard_fn(self, key: Tuple, build):
        """Keyed cache of jitted ``shard_map`` closures.  ``build(key)``
        constructs the closure once per distinct device-step shape; the
        jitted result is reused for every later hop with the same key, so
        a flood of same-shape queries never retraces."""
        fn = self._shard_fn_cache.get(key)
        if fn is None:
            self.trace_counts.setdefault(key, 0)
            fn = self._shard_fn_cache[key] = build(key)
        return fn

    def _count_trace(self, key: Tuple) -> None:
        # runs at TRACE time only (inside the shard_map body): the flood
        # test pins these counters flat after the first execution
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    def _build_edge_ones(self, key: Tuple):
        _, total, _ = key
        ax = self.axis
        # backend routing is resolved at BUILD time (the closure is cached
        # per key): each rank's local scatter-add runs the Pallas kernel
        # when enabled — the mesh-padding 0/1 mask rides along as the
        # kernel's weight vector — and the psum merges ranks either way
        kernel = _segsum_shard_kernel(total)

        def ones_hop(seg_l, w_l):
            self._count_trace(key)
            if kernel is not None:
                out = kernel.ones(seg_l, w_l.astype(jnp.float32),
                                  total).astype(self.dtype)
            else:
                out = jax.ops.segment_sum(w_l.astype(self.dtype), seg_l,
                                          num_segments=total)
            return jax.lax.psum(out, ax)

        return jax.jit(shard_map(ones_hop, mesh=self.mesh,
                                 in_specs=(P(ax), P(ax)), out_specs=P(None),
                                 check_vma=False))

    def _build_edge_dense(self, key: Tuple):
        _, total, _, _ = key
        ax = self.axis
        kernel = _segsum_shard_kernel(total)

        def dense_hop(seg_l, rows_l):
            self._count_trace(key)
            if kernel is not None:
                out = kernel.rows(seg_l, rows_l, total).astype(self.dtype)
            else:
                out = jax.ops.segment_sum(rows_l, seg_l, num_segments=total)
            return jax.lax.psum(out, ax)

        return jax.jit(shard_map(dense_hop, mesh=self.mesh,
                                 in_specs=(P(ax), P(ax, None)),
                                 out_specs=P(None, None), check_vma=False))

    def _build_reduce_ones(self, key: Tuple):
        _, ds, _ = key
        ax = self.axis

        def ones_reduce(c_l, w_l):
            self._count_trace(key)
            out = jax.ops.segment_sum(w_l.astype(self.dtype), c_l,
                                      num_segments=ds)
            return jax.lax.psum(out, ax)

        return jax.jit(shard_map(ones_reduce, mesh=self.mesh,
                                 in_specs=(P(ax), P(ax)), out_specs=P(None),
                                 check_vma=False))

    def _build_reduce_kr(self, key: Tuple):
        _, ds, _, widths = key
        ax = self.axis

        def kr_reduce(c_l, *ms):
            self._count_trace(key)
            return jax.lax.psum(
                _kr_segment_sum(c_l, list(ms), ds, self.dtype), ax)

        in_specs = (P(ax),) + (P(ax, None),) * len(widths)
        return jax.jit(shard_map(kr_reduce, mesh=self.mesh,
                                 in_specs=in_specs,
                                 out_specs=P(None, None), check_vma=False))

    # -- device primitives, sharded -----------------------------------------
    def _blocked_layout(self, rt, gather_idx, scatter_idx, n_parent: int,
                        ds: int):
        # a blocked layout is one device's; a sharded hop scatters its
        # flat segment ids over the mesh
        if self.n_ranks == 1 or self._force_local:
            return super()._blocked_layout(rt, gather_idx, scatter_idx,
                                           n_parent, ds)
        return None, False

    def _edge_segment_sum(self, seg: jax.Array,
                          rows: Optional[jnp.ndarray],
                          total: int) -> jnp.ndarray:
        if self.n_ranks == 1 or self._force_local:
            return super()._edge_segment_sum(seg, rows, total)
        seg, w = _pad_rows(seg, self.n_ranks)
        if rows is None:
            fn = self._shard_fn(("edge_ones", total, int(seg.shape[0])),
                                self._build_edge_ones)
            return _unsharded(fn(self.shard_rows(seg), self.shard_rows(w)))

        rows_p = jnp.pad(rows, ((0, seg.shape[0] - rows.shape[0]), (0, 0)))
        fn = self._shard_fn(("edge_dense", total, int(seg.shape[0]),
                             int(rows_p.shape[1])), self._build_edge_dense)
        return _unsharded(fn(self.shard_rows(seg), self.shard_rows(rows_p)))

    def _reduce_by_code(self, code, ds: int, n: int,
                        factors: Sequence[jnp.ndarray]) -> jnp.ndarray:
        if self.n_ranks == 1 or self._force_local:
            return super()._reduce_by_code(code, ds, n, factors)
        if code is None:
            code = jnp.zeros((n,), dtype=jnp.int32)
        code_p, w = _pad_rows(code, self.n_ranks)
        if not factors:
            fn = self._shard_fn(("reduce_ones", ds, int(code_p.shape[0])),
                                self._build_reduce_ones)
            return _unsharded(fn(self.shard_rows(code_p),
                                 self.shard_rows(w)))

        n_pad = int(code_p.shape[0])
        # no weight mask here: the factor rows are zero-padded, so padding
        # contributes nothing to segment 0
        mats = [self.shard_rows(jnp.pad(f, ((0, n_pad - n), (0, 0))))
                for f in factors]
        widths = tuple(int(m.shape[1]) for m in mats)
        fn = self._shard_fn(("reduce_kr", ds, n_pad, widths),
                            self._build_reduce_kr)
        return _unsharded(fn(self.shard_rows(code_p), *mats)).reshape(-1)

    # -- batching -----------------------------------------------------------
    def _positive_stacked(self, db, plans, stats):
        # vmap over shard_map is deliberately avoided: per-plan execution is
        # already mesh-parallel, and query-level fan-out belongs to the
        # serve router.  positive_batch's loop fallback handles this.  On a
        # 1-rank mesh nothing is sharded, so the inherited stacked path
        # (bit-identical there) keeps flood dispatch fast.
        if self.n_ranks == 1:
            return super()._positive_stacked(db, plans, stats)
        raise NotImplementedError("sharded sparse plans run one at a time")


EXECUTORS["sparse_sharded"] = ShardedSparseExecutor


def sharded_sparse_positive_ct(db: RelationalDB, point: LatticePoint,
                               keep: Optional[Sequence[CtVar]] = None,
                               *, mesh: Optional[Mesh] = None,
                               axis: str = "data", dtype=jnp.float32,
                               stats: Optional[CostStats] = None) -> CtTable:
    """Positive ct-table via the sparse O(nnz) path, edge lists sharded
    over ``axis`` of ``mesh``.

    Convenience wrapper: compiles the :class:`~repro.core.plan
    .ContractionPlan` for ``(point, keep)`` and evaluates it with a
    :class:`ShardedSparseExecutor`.  Numerically identical to the
    single-device sparse executor (and to :func:`sharded_positive_ct`,
    the dense path).

    Args:
        db: the database to count over.
        point: lattice point (>= 1 relationship atom).
        keep: ct-table axes to keep; defaults to every entity/edge
            attribute of the point (no indicator axes — positives only).
        mesh / axis: device mesh and the axis to shard rows over;
            ``mesh=None`` builds a 1-D mesh over all visible devices.
        dtype: accumulation dtype of the counts.
        stats: optional :class:`~repro.core.contract.CostStats` to record
            join/row accounting into.

    Returns:
        The positive :class:`~repro.core.ct.CtTable` over ``keep``.

    Usage::

        tab = sharded_sparse_positive_ct(db, point, mesh=mesh)
    """
    from .plan import compile_plan_cached
    if keep is None:
        keep = point.all_ct_vars(db.schema, include_rind=False)
    ex = ShardedSparseExecutor(dtype=dtype, mesh=mesh, axis=axis)
    plan = compile_plan_cached(db.schema, point, tuple(keep))
    return ex.positive(db, plan, stats)


def merge_stacked(stacked: jnp.ndarray, axis_name: str = "data"
                  ) -> jnp.ndarray:
    """Device reduction of a ``(n_partials, ...)`` stack of same-shape
    count tables — the router's merge step, meant to be traced inside one
    jitted dispatch (see :class:`~repro.serve.batching.TableMerger`).

    With at least one device per partial the stack is laid over a fresh
    ``data`` mesh and tree-merged with a ``psum`` — each shard's partial
    is reduced where it lives, one collective instead of ``n - 1``
    sequential adds.  On fewer devices (the one-host case) it is a single
    stacked ``jnp.sum``.  Exact either way: counts are integers and
    addition is associative, so no reassociation error exists to care
    about.

    Usage::

        merged = merge_stacked(jnp.stack([tab_a, tab_b]))
    """
    n = int(stacked.shape[0])
    if n == 1:
        return stacked[0]
    devs = jax.devices()
    if len(devs) >= n:
        mesh = Mesh(np.asarray(devs[:n]), (axis_name,))
        red = shard_map(
            lambda x: jax.lax.psum(jnp.sum(x, axis=0), axis_name),
            mesh=mesh, in_specs=P(axis_name), out_specs=P(),
            check_vma=False)
        return red(stacked)
    return jnp.sum(stacked, axis=0)
