"""Executors: pluggable backends that evaluate contraction plans.

The planner (:mod:`repro.core.plan`) fixes the traversal; an executor picks
the message representation:

* :class:`DenseExecutor` — the one-hot path: per-variable one-hot attribute
  encodings, per-relationship ``gather → (outer) multiply → segment_sum``
  hops, chunked Khatri-Rao reduction at the root.  Every hop costs
  O(edges × D) multiply-accumulates and materialises (n, D) messages — MXU
  friendly, but the Eq. (3) blowup is paid in *entities × D*.

* :class:`SparseExecutor` — the code path: attribute combinations are
  mixed-radix ``int32`` codes, never one-hot.  A leaf hop is a single
  ``jax.ops.segment_sum`` of ones over flattened ``(parent, code)`` keys —
  O(nnz) scatter-adds over the raw edge list with no per-entity one-hot
  materialisation — and the root combine segment-sums child messages by the
  root's own code.  Positive ct-tables therefore scale in ``nnz`` rather
  than ``entities × D``, which is what makes the paper's
  VisualGenome-scale configuration reachable.

Both executors expose the same interface (``positive`` / ``hist`` /
``leaf_hop`` / ``root_reduce``) so strategies, the Möbius join and the
tuple-ID variant are executor-agnostic.  The negative phase is not an
executor's: the Möbius join runs on the host, subtracting in the
executor's dtype (:mod:`repro.core.mobius`).
"""

from __future__ import annotations

import functools
import threading
import weakref
from contextlib import nullcontext
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import NULL_TRACER
from .contract import CostStats, _khatri_rao_reduce, _onehot
from .ct import CtTable
from .database import RelationalDB
from .plan import ContractionPlan, FactorSpec, HopSpec, NodeSpec
from .variables import Atom, CtVar, Var

_MAX_CHUNK_CELLS = 32_000_000
_INT32_LIMIT = 2 ** 31 - 1


def project_columns(m: jnp.ndarray, mvars: Tuple[CtVar, ...],
                    keep: Sequence[CtVar]
                    ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
    """Marginalise the column axes of an entity-indexed message matrix
    ``(n, prod cards(mvars))`` onto the vars present in ``keep``."""
    want = tuple(v for v in mvars if v in keep)
    if want == tuple(mvars):
        return m, tuple(mvars)
    wide = m.reshape((m.shape[0],) + tuple(v.card for v in mvars))
    dropped = tuple(i + 1 for i, v in enumerate(mvars) if v not in keep)
    if dropped:
        wide = jnp.sum(wide, axis=dropped)
    return wide.reshape(m.shape[0], -1), want


def _finalise_layout(plan: "ContractionPlan", fvars: Sequence[CtVar]
                     ) -> Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """The static ``(table shape, transpose perm)`` that :func:`_finalise`
    would apply to a plan's flat result — precomputable, so stacked
    execution can fuse reshape+transpose into the jitted dispatch.
    ``None`` when the request order is not a permutation of the flat vars
    (then the host-side :func:`_finalise` must handle it)."""
    fvars = tuple(fvars)
    order = tuple(v for v in plan.keep if v in fvars)
    if set(order) != set(fvars) or len(order) != len(fvars):
        return None
    shape = tuple(v.card for v in fvars)
    perm = tuple(fvars.index(v) for v in order)
    return shape, perm


def _finalise(flat: jnp.ndarray, mvars: Sequence[CtVar],
              keep: Sequence[CtVar], stats: Optional[CostStats]) -> CtTable:
    mvars = tuple(mvars)
    counts = flat.reshape(tuple(v.card for v in mvars)) if mvars \
        else flat.reshape(())
    tab = CtTable(mvars, counts)
    order = tuple(v for v in keep if v in tab.vars)
    if order != tab.vars:
        tab = tab.transpose_to(order)
    if stats is not None:
        stats.ct_cells += tab.size
    return tab


class _DeviceMirrors:
    """Device copies of host columns, one per live host array.

    A copy is keyed by the identity of the host array it copies and lives
    no longer than that array: a weak reference drops it, so the small
    arrays of a delta view do not pile up.  ``tag`` marks the copy of a
    column that is written in place (entity attributes): a copy made
    under another tag is replaced, never read.  Edge columns are never
    written in place -- a write assigns new arrays -- so their copies
    carry no tag and serve every store version that shares the array.
    Only raw store columns are held here, never counts."""

    def __init__(self):
        # id(host) -> (weak ref to host, tag, device copy)
        self._live: dict = {}

    def __len__(self) -> int:
        return len(self._live)

    def get(self, host: np.ndarray, tag, upload
            ) -> Tuple[jnp.ndarray, bool]:
        """``(device copy, resident)``: the copy of ``host`` made under
        ``tag``, uploaded through ``upload`` unless one is held."""
        key = id(host)
        hit = self._live.get(key)
        if hit is not None and hit[0]() is host and hit[1] == tag:
            return hit[2], True
        dev = upload(host)
        owner = weakref.ref(self)

        def drop(ref, key=key):
            live = owner()
            if live is not None and live._live.get(key, (None,))[0] is ref:
                del live._live[key]

        self._live[key] = (weakref.ref(host, drop), tag, dev)
        return dev, False


class Executor:
    """Backend interface: evaluate plans against a database."""

    name = "base"

    def __init__(self, dtype=jnp.float32):
        self.dtype = dtype
        # (stack key, padded batch) -> (db, jitted vmapped evaluator)
        self._batch_cache: dict = {}
        # request tracer for jit-dispatch spans (NULL_TRACER is free); a
        # real one is wired in by CountingService.set_tracer
        self.tracer = NULL_TRACER
        # device copies of the store columns eager hops read, and the hops
        # that found their edge columns there or had to upload them; the
        # lock guards both, since tenant services share one executor
        self._mirrors = _DeviceMirrors()
        self.edges_resident = 0
        self.edges_uploaded = 0
        self._mirror_lock = threading.Lock()

    def _stage(self, host: np.ndarray, copy=None) -> jnp.ndarray:
        """Upload one host array to the device, inside a ``host.stage``
        span carrying its bytes."""
        tr = self.tracer
        with tr.span("host.stage") as sp:
            if tr.enabled:
                sp.set(nbytes=int(host.nbytes))
            return jnp.asarray(host, copy=copy)

    def _mirror(self, host, tag=None) -> Tuple[jnp.ndarray, bool]:
        """``(device copy, resident)`` of a store column: staged on first
        use, then kept while the host array lives (see
        :class:`_DeviceMirrors`; ``tag`` for in-place columns).  The
        copy never aliases the host array, which the CPU backend would
        otherwise share and so keep alive."""
        with self._mirror_lock:
            return self._mirrors.get(
                np.asarray(host), tag,
                functools.partial(self._stage, copy=True))

    def local_mode(self):
        """Context for tiny side computations — the engine's delta count
        maintenance runs its delta-edge contractions inside it.  The
        single-device executors are already local (no-op); mesh-sharded
        backends drop to their single-device primitives so a handful of
        delta edges never pays padding + collectives (see
        :meth:`repro.core.distributed.ShardedSparseExecutor.local_mode`).
        """
        return nullcontext()

    # -- positive phase -----------------------------------------------------
    def positive(self, db: RelationalDB, plan: ContractionPlan,
                 stats: Optional[CostStats] = None) -> CtTable:
        """Evaluate a compiled plan: one message per root hop, then the
        root combine.  Backends only implement the two primitives."""
        factors = [self.hop_message(db, hop, stats) for hop in plan.root.hops]
        return self.root_reduce(db, plan.root.own, factors, plan.keep, stats)

    # -- batched positive phase (serve-layer entry point) -------------------
    def positive_batch(self, db: RelationalDB,
                       plans: Sequence[ContractionPlan],
                       stats: Optional[CostStats] = None,
                       min_stack: int = 2) -> List[CtTable]:
        """Evaluate many compiled plans at once.

        Plans whose computations are structurally identical (equal
        :func:`plan_stack_key` — same hop-tree topology, array sizes and
        axis cards) have their input arrays stacked along a new batch axis
        and run through ONE jitted+vmapped evaluation; groups smaller than
        ``min_stack`` (and backends without a traced evaluator) fall back
        to :meth:`positive` per plan.

        Args:
            db: the database the plans were compiled against.
            plans: compiled :class:`~repro.core.plan.ContractionPlan`
                sequence (any mix of signatures).
            stats: optional :class:`~repro.core.contract.CostStats`; join
                and row accounting matches the unbatched path exactly.
            min_stack: smallest group worth tracing a stacked evaluator
                for.

        Returns:
            One :class:`~repro.core.ct.CtTable` per plan, positionally
            aligned with ``plans`` and numerically identical to the
            unbatched path (counts are integer-valued, so the op
            reordering is exact).

        Usage::

            tabs = executor.positive_batch(db, plans)
        """
        results: List[Optional[CtTable]] = [None] * len(plans)
        groups: "dict" = {}
        for i, plan in enumerate(plans):
            groups.setdefault(plan_stack_key(db, plan), []).append(i)
        for idxs in groups.values():
            members = [plans[i] for i in idxs]
            tabs = None
            if len(members) >= min_stack:
                try:
                    tabs = self._positive_stacked(db, members, stats)
                except NotImplementedError:
                    tabs = None
            if tabs is None:
                tabs = [self.positive(db, p, stats) for p in members]
            for i, t in zip(idxs, tabs):
                results[i] = t
        return results

    def _positive_stacked(self, db: RelationalDB,
                          plans: Sequence[ContractionPlan],
                          stats: Optional[CostStats]) -> List[CtTable]:
        """One vmapped execution of stack-compatible plans.  The batch axis
        is padded to the next power of two (padding replays the first plan)
        so the jit cache is keyed by a handful of sizes, not every flood
        length seen.  The stacked device inputs are cached per (store
        version, plan list): a repeated flood over an unchanged store
        re-dispatches without re-staging a single host byte — any write
        bumps ``db.version`` and naturally misses."""
        template = plans[0]
        b = len(plans)
        b_pad = 1 << max(b - 1, 0).bit_length()
        stacked = self._staged_inputs(db, plans, b_pad)
        # finalise (reshape to table shape + transpose to request order) is
        # fused INTO the jitted dispatch when every plan in the group
        # shares the template's layout — the flood case — killing two
        # eager dispatches per plan per shard; mixed-layout groups fall
        # back to host-side finalise
        t_layout = _finalise_layout(template, self._flat_vars(template))
        fused = t_layout is not None and all(
            _finalise_layout(p, self._flat_vars(p)) == t_layout
            for p in plans[1:])
        fn = self._stacked_fn(db, template, b_pad,
                              t_layout if fused else None)
        with self.tracer.span("exec.positive_batch", plans=b, b_pad=b_pad,
                              fused=fused):
            rows = fn(*stacked)                   # drops the pad rows
        out: List[CtTable] = []
        for plan, row in zip(plans, rows):
            if fused:
                fvars = self._flat_vars(plan)
                out_vars = tuple(fvars[i] for i in t_layout[1])
                out.append(CtTable(out_vars, row))
                if stats is not None:
                    stats.ct_cells += int(np.prod(t_layout[0],
                                                  dtype=np.int64))
            else:
                out.append(_finalise(row, self._flat_vars(plan), plan.keep,
                                     stats))
            if stats is not None:
                _count_plan_joins(db, plan, stats)
        return out

    def _staged_inputs(self, db: RelationalDB,
                       plans: Sequence[ContractionPlan],
                       b_pad: int) -> Tuple[jnp.ndarray, ...]:
        """The plans' input packs stacked on device, batch axis padded to
        ``b_pad`` by replaying plan 0 — cached per (db, store version,
        plan list).  Plans come out of ``compile_plan_cached``, so
        identical queries hand back the SAME plan objects — id() keys
        hash as plain ints (the structural plan key costs more to hash
        than the staging saves) and the cached entry pins the plan list
        so no id is ever reused while its key is live.  ``id(db)`` is in
        the key because shard databases SHARE plan objects (one schema,
        one compile cache) and may share version counters."""
        in_key = ("stacked_inputs", id(db), db.version,
                  tuple(id(p) for p in plans), b_pad)
        hit = self._batch_cache.get(in_key)
        if hit is not None and hit[0] is db:
            return hit[2]
        packs = [plan_input_arrays(db, p) for p in plans]
        packs = packs + [packs[0]] * (b_pad - len(plans))
        stacked = tuple(self._stage(np.stack([p[j] for p in packs]))
                        for j in range(len(packs[0])))
        self._trim_input_cache()
        self._batch_cache[in_key] = (db, list(plans), stacked)
        return stacked

    _MAX_INPUT_CACHE = 128

    def _trim_input_cache(self) -> None:
        """Bound the staged-input entries in ``_batch_cache`` (jitted fns
        are tiny and stay; staged input stacks hold device memory)."""
        staged = [k for k in self._batch_cache
                  if isinstance(k, tuple) and k
                  and k[0] in ("stacked_inputs", "fanout_inputs",
                               "multi_inputs")]
        while len(staged) >= self._MAX_INPUT_CACHE:
            self._batch_cache.pop(staged.pop(0), None)

    def _stacked_fn(self, db: RelationalDB, template: ContractionPlan,
                    b_pad: int, layout=None):
        key = (plan_stack_key(db, template), b_pad, layout)
        hit = self._batch_cache.get(key)
        if hit is not None and hit[0] is db:
            return hit[1]

        def one(*arrays):
            cur = _ArrayCursor(arrays)
            flat = self._flat_from_arrays(db, template, cur)
            assert cur.exhausted, "plan evaluator out of sync with inputs"
            if layout is None:
                return flat
            shape, perm = layout          # fused finalise (see caller)
            y = flat.reshape(shape)
            if perm != tuple(range(len(perm))):
                y = jnp.transpose(y, perm)
            return y

        vm = jax.vmap(one)

        def run(*arrays):
            y = vm(*arrays)
            # per-plan results sliced INSIDE the jit: callers get a tuple
            # of ready tables, not b eager gather dispatches
            return tuple(y[i] for i in range(b_pad))

        fn = jax.jit(run)
        self._batch_cache[key] = (db, fn)
        return fn

    # -- cross-database stacked evaluation (multi-tenant serve path) --------
    def positive_batch_multi(self, dbs: Sequence[RelationalDB],
                             plans: Sequence[ContractionPlan],
                             stats_list: Optional[Sequence[
                                 Optional[CostStats]]] = None,
                             min_stack: int = 2) -> List[CtTable]:
        """:meth:`positive_batch` across MANY databases: item ``i`` is
        ``plans[i]`` evaluated against ``dbs[i]``.

        The traced evaluator reads only the plan's input arrays — the
        database supplies static metadata (sizes, cards) that
        :func:`plan_stack_key` captures — so rows from *different*
        databases with equal stack keys stack into the SAME jitted
        dispatch.  This is what makes a shared multi-tenant fleet faster
        than N isolated services: same-shape plans from different tenants
        ride one trace.

        Args:
            dbs: one database per plan (repeats allowed and common).
            plans: compiled plans, positionally paired with ``dbs``.
            stats_list: optional per-item
                :class:`~repro.core.contract.CostStats` (typically each
                tenant engine's); accounting matches each database
                running its own plans.
            min_stack: smallest group worth tracing a stacked evaluator
                for.

        Returns:
            One :class:`~repro.core.ct.CtTable` per item, positionally
            aligned and numerically identical to evaluating each
            ``(db, plan)`` pair alone.

        Usage::

            tabs = executor.positive_batch_multi(dbs, plans)
        """
        results: List[Optional[CtTable]] = [None] * len(plans)
        groups: "dict" = {}
        for i, (db, plan) in enumerate(zip(dbs, plans)):
            groups.setdefault(plan_stack_key(db, plan), []).append(i)
        for idxs in groups.values():
            g_dbs = [dbs[i] for i in idxs]
            g_plans = [plans[i] for i in idxs]
            g_stats = [stats_list[i] if stats_list is not None else None
                       for i in idxs]
            tabs = None
            if len(idxs) >= min_stack:
                try:
                    tabs = self._positive_stacked_multi(g_dbs, g_plans,
                                                        g_stats)
                except NotImplementedError:
                    tabs = None
            if tabs is None:
                tabs = [self.positive(d, p, s)
                        for d, p, s in zip(g_dbs, g_plans, g_stats)]
            for i, t in zip(idxs, tabs):
                results[i] = t
        return results

    def _positive_stacked_multi(self, dbs: Sequence[RelationalDB],
                                plans: Sequence[ContractionPlan],
                                stats_list: Sequence[Optional[CostStats]]
                                ) -> List[CtTable]:
        """One vmapped execution of stack-compatible ``(db, plan)`` rows.
        The jitted evaluator is the same one :meth:`_positive_stacked`
        uses (traced against the group's first database — valid for every
        member because equal stack keys pin all static metadata); only the
        input staging differs, pulling each row's arrays from its own
        database."""
        template = plans[0]
        b = len(plans)
        b_pad = 1 << max(b - 1, 0).bit_length()
        stacked = self._staged_inputs_multi(dbs, plans, b_pad)
        t_layout = _finalise_layout(template, self._flat_vars(template))
        fused = t_layout is not None and all(
            _finalise_layout(p, self._flat_vars(p)) == t_layout
            for p in plans[1:])
        fn = self._stacked_fn(dbs[0], template, b_pad,
                              t_layout if fused else None)
        with self.tracer.span("exec.positive_batch_multi", plans=b,
                              b_pad=b_pad, fused=fused,
                              dbs=len({id(d) for d in dbs})):
            rows = fn(*stacked)
        out: List[CtTable] = []
        for db, plan, row, stats in zip(dbs, plans, rows, stats_list):
            if fused:
                fvars = self._flat_vars(plan)
                out_vars = tuple(fvars[i] for i in t_layout[1])
                out.append(CtTable(out_vars, row))
                if stats is not None:
                    stats.ct_cells += int(np.prod(t_layout[0],
                                                  dtype=np.int64))
            else:
                out.append(_finalise(row, self._flat_vars(plan), plan.keep,
                                     stats))
            if stats is not None:
                _count_plan_joins(db, plan, stats)
        return out

    def _staged_inputs_multi(self, dbs: Sequence[RelationalDB],
                             plans: Sequence[ContractionPlan],
                             b_pad: int) -> Tuple[jnp.ndarray, ...]:
        """Per-row input packs stacked on device, each row staged from its
        own database — cached per (db ids, store versions, plan list) like
        the fan-out path, so a repeated multi-tenant flood over unchanged
        stores re-dispatches without re-staging a host byte."""
        in_key = ("multi_inputs", tuple(id(db) for db in dbs),
                  tuple(db.version for db in dbs),
                  tuple(id(p) for p in plans), b_pad)
        hit = self._batch_cache.get(in_key)
        if hit is not None and all(a is b for a, b in zip(hit[0], dbs)):
            return hit[2]
        packs = [plan_input_arrays(db, p) for db, p in zip(dbs, plans)]
        packs = packs + [packs[0]] * (b_pad - len(plans))
        stacked = tuple(self._stage(np.stack([p[j] for p in packs]))
                        for j in range(len(packs[0])))
        self._trim_input_cache()
        self._batch_cache[in_key] = (list(dbs), list(plans), stacked)
        return stacked

    # -- cross-shard fused evaluation (router flood path) -------------------
    def stacked_layout(self, plan: ContractionPlan):
        """Fused finalise layout of one plan — ``(shape, perm)`` when the
        flat counts can be reshaped + transposed to the request order
        inside the jit, ``None`` otherwise (see :func:`_finalise_layout`).
        Raises ``NotImplementedError`` for backends without a traced
        evaluator."""
        return _finalise_layout(plan, self._flat_vars(plan))

    def positive_stacked_merged(self, dbs: Sequence[RelationalDB],
                                executors: Sequence["Executor"],
                                plans: Sequence[ContractionPlan],
                                stats_list: Optional[Sequence[
                                    Optional[CostStats]]] = None
                                ) -> Tuple[List[List[CtTable]],
                                           List[CtTable]]:
        """ONE jitted dispatch for a whole cross-shard flood group: every
        shard's stacked input pack is evaluated under the same trace and
        the per-plan tables are summed over the shard axis inside the jit
        — the per-shard tables (for the shard services' caches) and the
        merged tables (for the router) come back from the same call, so a
        2-shard flood costs one dispatch instead of two shard dispatches
        plus a merge dispatch.

        The caller (``CountingRouter._flush_fused``) must pre-check
        feasibility: the SAME plan objects on every shard, equal
        :func:`plan_stack_key` per plan across all shard databases (entity
        tables are replicated and edge arrays pad to shared pow2 buckets,
        so this is the common case), and one shared non-``None``
        :meth:`stacked_layout` across the group's plans.

        Args:
            dbs: one shard database per shard.
            executors: the shard executors (staging caches stay per
                shard); ``self`` compiles and owns the fused function.
            plans: the group's plans (identical objects on every shard).
            stats_list: per-shard :class:`~repro.core.contract.CostStats`;
                accounting matches each shard running the plans itself.

        Returns:
            ``(per_shard, merged)`` — ``per_shard[s][q]`` is shard ``s``'s
            table for plan ``q``; ``merged[q]`` is their exact sum.
        """
        template = plans[0]
        m = len(plans)
        b_pad = 1 << max(m - 1, 0).bit_length()
        layout = self.stacked_layout(template)
        staged = [ex._staged_inputs(db, plans, b_pad)
                  for ex, db in zip(executors, dbs)]
        k = len(staged[0])
        fn = self._fused_stacked_fn(dbs[0], template, b_pad, len(dbs), k,
                                    layout)
        flat = fn(*(a for pack in staged for a in pack))
        cells = int(np.prod(layout[0], dtype=np.int64))
        out_vars: List[Tuple[CtVar, ...]] = []
        for p in plans:
            fvars = self._flat_vars(p)
            out_vars.append(tuple(fvars[i] for i in layout[1]))
        merged = [CtTable(out_vars[q], flat[q]) for q in range(m)]
        per_shard: List[List[CtTable]] = []
        for s in range(len(dbs)):
            rows = flat[b_pad + s * b_pad:b_pad + (s + 1) * b_pad]
            per_shard.append([CtTable(out_vars[q], rows[q])
                              for q in range(m)])
            stats = stats_list[s] if stats_list is not None else None
            if stats is not None:
                stats.ct_cells += cells * m
                for p in plans:
                    _count_plan_joins(dbs[s], p, stats)
        return per_shard, merged

    def positive_fanout_merged(self, dbs: Sequence[RelationalDB],
                               plans: Sequence[ContractionPlan],
                               partitioned: frozenset,
                               stats_list: Optional[Sequence[
                                   Optional[CostStats]]] = None
                               ) -> List[CtTable]:
        """Merged fan-out tables at SINGLE-DATABASE cost: instead of
        evaluating every shard separately and summing tables (which
        materialises ``n_shards`` full segment spaces), the shards' input
        arrays are reassembled into the unsharded database's arrays
        (:func:`fanout_input_arrays`) and evaluated once — the answer IS
        the merged table, by the same argument that makes the fan-out sum
        exact (every partitioned edge lives on exactly one shard;
        replicated tables are identical everywhere).

        The caller must pre-check: a routable fan-out plan group with one
        shared non-``None`` :meth:`stacked_layout` and equal
        :func:`fanout_stack_key`.  ``self`` is the front-end's compiling
        executor (shard 0's); reassembled input stacks are cached per
        (shard dbs, store versions, plan list) so a repeated flood
        re-dispatches without touching a host byte.

        Returns one merged :class:`~repro.core.ct.CtTable` per plan.
        """
        template = plans[0]
        m = len(plans)
        b_pad = 1 << max(m - 1, 0).bit_length()
        layout = self.stacked_layout(template)
        in_key = ("fanout_inputs", tuple(id(db) for db in dbs),
                  tuple(db.version for db in dbs),
                  tuple(id(p) for p in plans), b_pad)
        hit = self._batch_cache.get(in_key)
        if hit is not None and all(a is b for a, b in zip(hit[0], dbs)):
            stacked = hit[3]
        else:
            packs = [fanout_input_arrays(dbs, p, partitioned)
                     for p in plans]
            packs = packs + [packs[0]] * (b_pad - m)
            stacked = tuple(self._stage(np.stack([p[j] for p in packs]))
                            for j in range(len(packs[0])))
            self._trim_input_cache()
            self._batch_cache[in_key] = (tuple(dbs), None, list(plans),
                                         stacked)
        # the single-db stacked evaluator retraces on the reassembled
        # array shapes and is correct as-is: its only database inputs are
        # replicated static metadata (entity sizes, cards)
        fn = self._stacked_fn(dbs[0], template, b_pad, layout)
        rows = fn(*stacked)
        out: List[CtTable] = []
        for q, p in enumerate(plans):
            fvars = self._flat_vars(p)
            out.append(CtTable(tuple(fvars[i] for i in layout[1]),
                               rows[q]))
        if stats_list:
            for db, stats in zip(dbs, stats_list):
                if stats is not None:
                    for p in plans:
                        _count_plan_joins(db, p, stats)
            if stats_list[0] is not None:
                stats_list[0].ct_cells += m * int(
                    np.prod(layout[0], dtype=np.int64))
        return out

    def _fused_stacked_fn(self, db0: RelationalDB,
                          template: ContractionPlan, b_pad: int,
                          n_shards: int, k: int, layout):
        """The jitted cross-shard evaluator behind
        :meth:`positive_stacked_merged`: args are shard-major input packs
        (``k`` arrays per shard); returns ``b_pad`` merged rows followed
        by ``n_shards * b_pad`` per-shard rows, all sliced inside the
        jit.  Traced against shard 0's database — equal stack keys
        guarantee the static metadata (entity sizes, cards, bucketed edge
        lengths) matches every shard."""
        key = ("fused_stacked", plan_stack_key(db0, template), b_pad,
               n_shards, k, layout)
        hit = self._batch_cache.get(key)
        if hit is not None and hit[0] is db0:
            return hit[1]
        shape, perm = layout

        def one(*arrays):
            cur = _ArrayCursor(arrays)
            flat = self._flat_from_arrays(db0, template, cur)
            assert cur.exhausted, "plan evaluator out of sync with inputs"
            y = flat.reshape(shape)
            if perm != tuple(range(len(perm))):
                y = jnp.transpose(y, perm)
            return y

        vm = jax.vmap(one)

        def run(*all_arrays):
            outs = [vm(*all_arrays[s * k:(s + 1) * k])
                    for s in range(n_shards)]
            merged = outs[0]
            for o in outs[1:]:
                merged = merged + o
            rows = [merged[q] for q in range(b_pad)]
            for s in range(n_shards):
                rows.extend(outs[s][q] for q in range(b_pad))
            return tuple(rows)

        fn = jax.jit(run)
        self._batch_cache[key] = (db0, fn)
        return fn

    def _flat_from_arrays(self, db: RelationalDB, plan: ContractionPlan,
                          cur: "_ArrayCursor") -> jnp.ndarray:
        """Traced single-plan evaluation over an input-array pack (see
        :func:`plan_input_arrays`); returns the flat counts in
        ``_flat_vars(plan)`` axis order.  Backends that implement this get
        stacked execution for free."""
        raise NotImplementedError

    def _flat_vars(self, plan: ContractionPlan) -> Tuple[CtVar, ...]:
        """Axis order of :meth:`_flat_from_arrays` output."""
        raise NotImplementedError

    def hop_message(self, db: RelationalDB, hop: HopSpec,
                    stats: Optional[CostStats] = None
                    ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        """Full message matrix ``(n_parent, D)`` of one root-adjacent hop,
        including the child's entire subtree."""
        raise NotImplementedError

    def hist(self, db: RelationalDB, var: Var, attrs: Tuple[CtVar, ...],
             stats: Optional[CostStats] = None) -> CtTable:
        raise NotImplementedError

    def leaf_hop(self, db: RelationalDB, atom: Atom, child: Var, parent: Var,
                 child_attrs: Tuple[CtVar, ...],
                 edge_attrs: Tuple[CtVar, ...],
                 stats: Optional[CostStats] = None
                 ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        """Message matrix ``(n_parent, D)`` a bare child variable sends
        through one relationship — the tuple-ID precompute primitive."""
        raise NotImplementedError

    def root_reduce(self, db: RelationalDB, own: FactorSpec,
                    factors: Sequence[Tuple[jnp.ndarray, Tuple[CtVar, ...]]],
                    keep: Sequence[CtVar],
                    stats: Optional[CostStats] = None) -> CtTable:
        """Combine the root variable's own attributes with entity-indexed
        factor matrices ``(n_root, D_i)`` into a ct-table."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# shared edge-list bookkeeping
# ---------------------------------------------------------------------------

def _hop_indices(db: RelationalDB, atom: Atom, child: Var, parent: Var):
    rt = db.relations[atom.rel]
    if child == atom.src and parent == atom.dst:
        return rt, rt.src, rt.dst, db.entities[atom.dst.etype].size
    if child == atom.dst and parent == atom.src:
        return rt, rt.dst, rt.src, db.entities[atom.src.etype].size
    raise AssertionError("atom does not connect child/parent")


# ---------------------------------------------------------------------------
# batched execution plumbing: plans as (static structure, input-array pack)
# ---------------------------------------------------------------------------

class _ArrayCursor:
    """Sequential reader over a plan's flattened input-array pack.  The
    collection (:func:`plan_input_arrays`) and consumption
    (``_flat_from_arrays``) sides share one traversal order: per node its
    kept attribute columns, then per hop the child subtree (recursively),
    the gather index, the scatter index, and the kept edge-attr columns."""

    __slots__ = ("arrays", "i")

    def __init__(self, arrays: Sequence):
        self.arrays, self.i = arrays, 0

    def take(self):
        a = self.arrays[self.i]
        self.i += 1
        return a

    @property
    def exhausted(self) -> bool:
        return self.i == len(self.arrays)


def _edge_bucket(n: int) -> int:
    """Bucketed edge-array length: the next power of two at or above
    ``n`` (floor 16).  Hash-partitioned shards have *ragged*
    per-relationship edge counts, so keying stacked execution on exact
    counts would put every shard plan in its own group and fall back to
    per-plan eager dispatch; bucketing restores stacking at the cost of
    masked pad rows.  Power-of-two buckets make one group per shard the
    common case (per-dispatch overhead dominates the extra pad rows —
    the segment-sum is linear and memory-bound)."""
    if n <= 0:
        return 0
    return max(16, 1 << max(n - 1, 0).bit_length())


def plan_stack_key(db: RelationalDB, plan: ContractionPlan) -> Tuple:
    """Stacked-execution key: plans with equal keys against the same
    database run the exact same operation sequence on same-shape arrays
    (hop-tree topology + entity sizes + bucketed edge counts + axis
    cards), so their input packs can be stacked and evaluated under one
    ``vmap``.  Edge counts are bucketed (:func:`_edge_bucket`) and the
    packs padded to match — padded edges scatter to segment ``n_parent``,
    one past the last real segment, which ``segment_sum`` drops — so
    plans with nearby edge counts stack exactly."""
    def node(n: NodeSpec) -> Tuple:
        hops = []
        for h in n.hops:
            _, g, _, n_parent = _hop_indices(db, h.atom, h.child, h.parent)
            hops.append((_edge_bucket(int(np.asarray(g).shape[0])), n_parent,
                         tuple(cv.card for cv in h.edge_attrs),
                         node(h.child_node)))
        return (db.entities[n.var.etype].size,
                tuple(cv.card for cv in n.own.attrs), tuple(hops))
    return node(plan.root)


def plan_input_arrays(db: RelationalDB, plan: ContractionPlan
                      ) -> List[np.ndarray]:
    """The plan's data inputs as a flat host-array list in cursor order
    (see :class:`_ArrayCursor`) — everything an executor reads from the
    database, ready to be ``np.stack``-ed across stack-compatible plans.

    Edge arrays are padded to :func:`_edge_bucket` length to match
    :func:`plan_stack_key`: pad gathers read row 0 (any valid row), pad
    scatters target segment ``n_parent`` — out of range, so XLA's scatter
    drops them — and pad edge-attr entries are 0.  The padded evaluation
    is therefore numerically identical to the exact-length one."""
    arrs: List[np.ndarray] = []

    def node(n: NodeSpec) -> None:
        tab = db.entities[n.var.etype]
        for cv in n.own.attrs:
            arrs.append(np.asarray(tab.attrs[cv.owner[1]]))
        for h in n.hops:
            node(h.child_node)
            rt, g, s, n_parent = _hop_indices(db, h.atom, h.child, h.parent)
            g_np, s_np = np.asarray(g), np.asarray(s)
            n_edges = int(g_np.shape[0])
            pad = _edge_bucket(n_edges) - n_edges
            if pad > 0:
                g_np = np.concatenate(
                    [g_np, np.zeros(pad, dtype=g_np.dtype)])
                s_np = np.concatenate(
                    [s_np, np.full(pad, n_parent, dtype=s_np.dtype)])
            arrs.append(g_np)
            arrs.append(s_np)
            for cv in h.edge_attrs:
                col = np.asarray(rt.attrs[cv.owner[1]])
                if pad > 0:
                    col = np.concatenate(
                        [col, np.zeros(pad, dtype=col.dtype)])
                arrs.append(col)

    node(plan.root)
    return arrs


def _plan_input_roles(plan: ContractionPlan,
                      partitioned: frozenset) -> List[bool]:
    """Per input-pack slot (cursor order of :func:`plan_input_arrays`):
    ``True`` when the array belongs to a partitioned relationship's edge
    table, ``False`` for entity-attribute columns and replicated
    relationships' arrays."""
    roles: List[bool] = []

    def node(n: NodeSpec) -> None:
        roles.extend(False for _ in n.own.attrs)
        for h in n.hops:
            node(h.child_node)
            part = h.atom.rel in partitioned
            roles.append(part)             # gather index
            roles.append(part)             # scatter index
            roles.extend(part for _ in h.edge_attrs)

    node(plan.root)
    return roles


def fanout_input_arrays(dbs: Sequence[RelationalDB], plan: ContractionPlan,
                        partitioned: frozenset) -> List[np.ndarray]:
    """The UNSHARDED database's input pack, reassembled from its shards:
    entity-attribute columns and replicated relationship arrays come from
    shard 0 (replicas are identical on every shard), partitioned
    relationship arrays are the shards' arrays concatenated (every edge
    lives on exactly one shard, so the concatenation is the full edge
    table; per-shard pad rows scatter out of range and stay inert).
    Evaluating a routable fan-out plan on this pack therefore yields the
    MERGED table directly — same correctness argument as the fan-out sum,
    one segment space instead of ``n_shards``."""
    packs = [plan_input_arrays(db, plan) for db in dbs]
    roles = _plan_input_roles(plan, partitioned)
    return [np.concatenate(arrs) if part else arrs[0]
            for part, arrs in zip(roles, zip(*packs))]


def fanout_stack_key(dbs: Sequence[RelationalDB], plan: ContractionPlan,
                     partitioned: frozenset) -> Tuple:
    """Stacking key of the reassembled fan-out evaluation
    (:func:`fanout_input_arrays`): like :func:`plan_stack_key` but with
    each partitioned relationship's edge length equal to the SUM of the
    shards' bucketed lengths.  Plans with equal keys share one stacked
    dispatch."""
    def node(n: NodeSpec) -> Tuple:
        hops = []
        for h in n.hops:
            lens = []
            for db in dbs:
                _, g, _, n_parent = _hop_indices(db, h.atom, h.child,
                                                 h.parent)
                lens.append(_edge_bucket(int(np.asarray(g).shape[0])))
            length = sum(lens) if h.atom.rel in partitioned else lens[0]
            hops.append((length, n_parent,
                         tuple(cv.card for cv in h.edge_attrs),
                         node(h.child_node)))
        return (dbs[0].entities[n.var.etype].size,
                tuple(cv.card for cv in n.own.attrs), tuple(hops))
    return node(plan.root)


def _count_plan_joins(db: RelationalDB, plan: ContractionPlan,
                      stats: CostStats) -> None:
    """Mirror the per-hop join accounting of the unbatched path."""
    def node(n: NodeSpec) -> None:
        for h in n.hops:
            node(h.child_node)
            _, g, _, _ = _hop_indices(db, h.atom, h.child, h.parent)
            stats.joins += 1
            stats.rows_scanned += int(np.asarray(g).shape[0])
    node(plan.root)


# ---------------------------------------------------------------------------
# dense executor (one-hot contraction)
# ---------------------------------------------------------------------------

class DenseExecutor(Executor):
    name = "dense"

    def _entity_factor(self, db: RelationalDB, fs: FactorSpec
                       ) -> Tuple[jnp.ndarray, List[CtVar]]:
        tab = db.entities[fs.var.etype]
        msg = jnp.ones((tab.size, 1), dtype=self.dtype)
        mvars: List[CtVar] = []
        for cv in fs.attrs:
            hot = _onehot(self._stage(tab.attrs[cv.owner[1]]), cv.card,
                          self.dtype)
            n, d = msg.shape
            msg = (msg[:, :, None] * hot[:, None, :]).reshape(n, d * cv.card)
            mvars.append(cv)
        return msg, mvars

    def _hop(self, db: RelationalDB, hop: HopSpec, child_msg: jnp.ndarray,
             child_vars: List[CtVar], stats: Optional[CostStats]
             ) -> Tuple[jnp.ndarray, List[CtVar]]:
        rt, gather_idx, scatter_idx, n_parent = _hop_indices(
            db, hop.atom, hop.child, hop.parent)
        m = child_msg[self._stage(gather_idx)]            # (edges, D)
        mvars = list(child_vars)
        for cv in hop.edge_attrs:
            hot = _onehot(self._stage(rt.attrs[cv.owner[1]]), cv.card,
                          self.dtype)                     # card+1, NA empty
            n, d = m.shape
            m = (m[:, :, None] * hot[:, None, :]).reshape(n, d * cv.card)
            mvars.append(cv)
        out = jax.ops.segment_sum(m, self._stage(scatter_idx),
                                  num_segments=n_parent)
        if stats is not None:
            stats.joins += 1
            stats.rows_scanned += int(gather_idx.shape[0])
        return out, mvars

    def _node_message(self, db: RelationalDB, node: NodeSpec,
                      stats: Optional[CostStats]
                      ) -> Tuple[jnp.ndarray, List[CtVar]]:
        msg, mvars = self._entity_factor(db, node.own)
        for hop in node.hops:
            child_msg, child_vars = self._node_message(db, hop.child_node,
                                                       stats)
            h, hvars = self._hop(db, hop, child_msg, child_vars, stats)
            n, d = msg.shape
            msg = (msg[:, :, None] * h[:, None, :]).reshape(n, d * h.shape[1])
            mvars = mvars + hvars
        return msg, mvars

    def hop_message(self, db: RelationalDB, hop: HopSpec,
                    stats: Optional[CostStats] = None
                    ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        child_msg, child_vars = self._node_message(db, hop.child_node, stats)
        m, mvars = self._hop(db, hop, child_msg, child_vars, stats)
        return m, tuple(mvars)

    def hist(self, db: RelationalDB, var: Var, attrs: Tuple[CtVar, ...],
             stats: Optional[CostStats] = None) -> CtTable:
        msg, mvars = self._entity_factor(db, FactorSpec(var, tuple(attrs)))
        flat = jnp.sum(msg, axis=0)
        counts = flat.reshape(tuple(v.card for v in mvars)) if mvars \
            else flat[0]
        return CtTable(tuple(mvars), counts)

    def leaf_hop(self, db: RelationalDB, atom: Atom, child: Var, parent: Var,
                 child_attrs: Tuple[CtVar, ...],
                 edge_attrs: Tuple[CtVar, ...],
                 stats: Optional[CostStats] = None
                 ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        fs = FactorSpec(child, tuple(child_attrs))
        leaf = NodeSpec(fs, (), fs.attrs)
        hop = HopSpec(atom, child, parent, tuple(edge_attrs), leaf,
                      fs.attrs + tuple(edge_attrs))
        return self.hop_message(db, hop, stats)

    def root_reduce(self, db: RelationalDB, own: FactorSpec,
                    factors: Sequence[Tuple[jnp.ndarray, Tuple[CtVar, ...]]],
                    keep: Sequence[CtVar],
                    stats: Optional[CostStats] = None) -> CtTable:
        fs: List[Tuple[jnp.ndarray, List[CtVar]]] = [
            self._entity_factor(db, own)]
        fs.extend((m, list(vs)) for m, vs in factors)
        flat, mvars = _khatri_rao_reduce(fs)
        return _finalise(flat, mvars, keep, stats)

    # -- traced batched evaluation ------------------------------------------
    def _flat_from_arrays(self, db: RelationalDB, plan: ContractionPlan,
                          cur: _ArrayCursor) -> jnp.ndarray:
        """Mirror of ``_entity_factor``/``_hop``/``_node_message`` +
        ``root_reduce`` reading from an array pack — same op sequence, so
        batched results match the unbatched path exactly."""
        def entity_factor(fs: FactorSpec) -> jnp.ndarray:
            n = db.entities[fs.var.etype].size
            msg = jnp.ones((n, 1), dtype=self.dtype)
            for cv in fs.attrs:
                hot = _onehot(cur.take(), cv.card, self.dtype)
                nn, d = msg.shape
                msg = (msg[:, :, None] * hot[:, None, :]).reshape(
                    nn, d * cv.card)
            return msg

        def hop_from(hop: HopSpec, child_msg: jnp.ndarray) -> jnp.ndarray:
            g, s = cur.take(), cur.take()
            n_parent = db.entities[hop.parent.etype].size
            m = child_msg[g]
            for cv in hop.edge_attrs:
                hot = _onehot(cur.take(), cv.card, self.dtype)
                nn, d = m.shape
                m = (m[:, :, None] * hot[:, None, :]).reshape(nn, d * cv.card)
            return jax.ops.segment_sum(m, s, num_segments=n_parent)

        def node_msg(node: NodeSpec) -> jnp.ndarray:
            msg = entity_factor(node.own)
            for hop in node.hops:
                h = hop_from(hop, node_msg(hop.child_node))
                nn, d = msg.shape
                msg = (msg[:, :, None] * h[:, None, :]).reshape(
                    nn, d * h.shape[1])
            return msg

        factors: List[Tuple[jnp.ndarray, List[CtVar]]] = [
            (entity_factor(plan.root.own), [])]
        for hop in plan.root.hops:
            factors.append((hop_from(hop, node_msg(hop.child_node)), []))
        flat, _ = _khatri_rao_reduce(factors)
        return flat

    def _flat_vars(self, plan: ContractionPlan) -> Tuple[CtVar, ...]:
        # replicate _khatri_rao_reduce's widest-last reorder on var metadata
        fvars = [tuple(plan.root.own.attrs)] + [tuple(h.out_vars)
                                                for h in plan.root.hops]
        widths = [int(np.prod([v.card for v in vs], dtype=np.int64))
                  for vs in fvars]
        widest = max(range(len(fvars)), key=widths.__getitem__)
        order = [i for i in range(len(fvars)) if i != widest] + [widest]
        out: List[CtVar] = []
        for i in order:
            out.extend(fvars[i])
        return tuple(out)


# ---------------------------------------------------------------------------
# sparse executor (int32 codes + segment_sum over edge lists)
# ---------------------------------------------------------------------------

class _SparseMsg:
    """Per-entity message: a mixed-radix scalar code over ``svars`` (one
    value per entity — exact, no one-hot) plus an optional dense block over
    ``dvars`` (present only after an aggregation made the distribution
    genuinely multi-valued)."""

    __slots__ = ("code", "ds", "svars", "dense", "dvars")

    def __init__(self, code, ds, svars, dense, dvars):
        self.code, self.ds, self.svars = code, ds, svars
        self.dense, self.dvars = dense, dvars


@functools.partial(jax.jit, static_argnames=("cards",))
def _mixed_radix(cols: Tuple[jnp.ndarray, ...],
                 cards: Tuple[int, ...]) -> jnp.ndarray:
    """Mixed-radix ``int32`` code of aligned value columns."""
    code = cols[0].astype(jnp.int32)
    for col, card in zip(cols[1:], cards[1:]):
        code = code * card + col.astype(jnp.int32)
    return code


def _hop_code_space(child_ds: int, cards: Tuple[int, ...],
                    n_parent: int) -> Tuple[int, int]:
    """``(ds, total)`` of a sparse hop: its code space, the child's
    extended by the kept edge attributes, and its segment space, which
    must fit ``int32``."""
    ds = child_ds * int(np.prod(cards, dtype=np.int64))
    total = n_parent * ds
    if total > _INT32_LIMIT:
        raise OverflowError(
            f"sparse hop segment space {total} exceeds int32; use the "
            f"dense executor or reduce kept axes")
    return ds, total


_ROW = 128              # codes per gathered row: one lane-wide vector
_ROW_CHUNK = 1 << 16    # ids per step of a row gather: 32 MB of rows


def _gathers_by_rows() -> bool:
    """Should an eager hop gather its child codes by rows
    (:func:`_take_by_rows`)?  On a TPU a lane-wide row per id comes about
    three times faster than one element; other backends gather
    elements."""
    return jax.default_backend() == "tpu"


def _take_by_rows(code: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``code[idx]`` for in-range ids: fetch each id's row of ``_ROW``
    codes, then pick its lane with a compare and a sum.  Ids go in
    steps of ``_ROW_CHUNK``, so the fetched rows stay at 32 MB."""
    tab = jnp.pad(code, (0, -code.shape[0] % _ROW)).reshape(-1, _ROW)
    lane = jax.lax.broadcasted_iota(idx.dtype, (1, _ROW), 1)

    def take(ids):
        rows = tab.at[ids // _ROW].get(mode="promise_in_bounds")
        return jnp.sum(jnp.where(lane == (ids % _ROW)[:, None], rows, 0),
                       axis=1, dtype=code.dtype)

    n = idx.shape[0]
    if n <= _ROW_CHUNK:
        return take(idx)
    steps = -(-n // _ROW_CHUNK)
    ids = jnp.pad(idx, (0, steps * _ROW_CHUNK - n)).reshape(steps, -1)
    return jax.lax.map(take, ids).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("ds", "cards", "by_rows"))
def _hop_segment_ids(code: Optional[jnp.ndarray], gather: jnp.ndarray,
                     scatter: jnp.ndarray,
                     edge_cols: Tuple[jnp.ndarray, ...], ds: int,
                     cards: Tuple[int, ...],
                     by_rows: bool = False) -> jnp.ndarray:
    """Segment id of every edge of a sparse hop, in ``int32``:
    ``scatter * ds + ((code[gather] * c1 + e1) * c2 + e2 ...)``, the
    parent end of the edge times the hop's code space plus the child's
    code extended with the kept edge attributes.  ``code`` is ``None``
    when the child keeps no attribute; ``by_rows`` gathers it with
    :func:`_take_by_rows`."""
    if code is None:
        ecode = jnp.zeros(gather.shape, dtype=jnp.int32)
    elif by_rows:
        ecode = _take_by_rows(code, gather)
    else:
        ecode = code[gather]
    for col, card in zip(edge_cols, cards):
        ecode = ecode * card + col.astype(jnp.int32)
    return scatter.astype(jnp.int32) * ds + ecode


def _kr_segment_sum(code, mats: Sequence[jnp.ndarray], ds: int,
                    dtype) -> jnp.ndarray:
    """Chunked Khatri-Rao expansion + segment-sum accumulation:
    ``out[c, :] = sum_{i: code[i]=c} ⊗_m mats[m][i, :]`` as a ``(ds,
    prod_D)`` table, chunking rows so the expansion never materialises
    more than ``_MAX_CHUNK_CELLS`` cells.  Pure jnp — also traced inside
    the sharded executor's ``shard_map`` body."""
    d_prod = int(np.prod([m.shape[1] for m in mats], dtype=np.int64))
    n = int(mats[0].shape[0])
    chunk = max(64, min(max(n, 1), _MAX_CHUNK_CELLS // max(d_prod, 1)))
    out = jnp.zeros((ds, d_prod), dtype=dtype)
    for s in range(0, n, chunk):
        kr = mats[0][s:s + chunk]
        for m in mats[1:]:
            blk = m[s:s + chunk]
            kr = (kr[:, :, None] * blk[:, None, :]).reshape(kr.shape[0], -1)
        out = out + jax.ops.segment_sum(kr, code[s:s + chunk],
                                        num_segments=ds)
    return out


def _segsum_kernel_enabled(num_segments: int) -> bool:
    """Route this scatter-add through the Pallas segment-sum kernel?
    Thin lazy alias of :func:`repro.kernels.ops.segsum_kernel_enabled`
    so the kernels package (and its Pallas import) stays off the core
    import path until a sparse hop actually consults it."""
    from ..kernels import ops as kernel_ops
    return kernel_ops.segsum_kernel_enabled(num_segments)


class SparseExecutor(Executor):
    name = "sparse"

    def _entity_code(self, db: RelationalDB, fs: FactorSpec
                     ) -> Tuple[Optional[jnp.ndarray], int]:
        """Mixed-radix device code per entity, from device copies of the
        entity's attribute columns made at the store's version.  The
        table's write count joins the tag: stores that share the table
        (shards, delta views) may stand at one version number with
        different writes behind it."""
        if not fs.attrs:
            return None, 1
        tab = db.entities[fs.var.etype]
        tag = (db.version, tab.writes)
        cols = tuple(self._mirror(tab.attrs[cv.owner[1]], tag)[0]
                     for cv in fs.attrs)
        return _mixed_radix(cols, tuple(cv.card for cv in fs.attrs)), fs.card

    def _hop(self, db: RelationalDB, hop: HopSpec, msg: _SparseMsg,
             stats: Optional[CostStats]
             ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        """Push a child message through one relationship.  Scalar-coded axes
        travel as index arithmetic inside the segment ids, computed on the
        device from the relationship's device-resident edge columns; only
        genuinely dense axes (from deeper aggregations) are carried as row
        vectors."""
        rt, gather_idx, scatter_idx, n_parent = _hop_indices(
            db, hop.atom, hop.child, hop.parent)
        cards = tuple(cv.card for cv in hop.edge_attrs)
        ds, total = _hop_code_space(msg.ds, cards, n_parent)
        mirrored = [self._mirror(a) for a in (
            gather_idx, scatter_idx,
            *(rt.attrs[cv.owner[1]] for cv in hop.edge_attrs))]
        with self._mirror_lock:
            if all(resident for _, resident in mirrored):
                self.edges_resident += 1
            else:
                self.edges_uploaded += 1
        gather, scatter, *edge_cols = (dev for dev, _ in mirrored)
        n_edges = int(gather.shape[0])
        svars = tuple(msg.svars) + tuple(hop.edge_attrs)
        seg = _hop_segment_ids(msg.code, gather, scatter, tuple(edge_cols),
                               ds=ds, cards=cards,
                               by_rows=_gathers_by_rows())
        if msg.dense is None:
            flat = self._edge_segment_sum(seg, None, total)
            out = flat.reshape(n_parent, ds)
            out_vars = svars
        else:
            rows = msg.dense[gather]                       # (edges, Dd)
            agg = self._edge_segment_sum(seg, rows, total)
            out = agg.reshape(n_parent, ds * msg.dense.shape[1])
            out_vars = svars + tuple(msg.dvars)
        if stats is not None:
            stats.joins += 1
            stats.rows_scanned += n_edges
        return out, out_vars

    def _edge_segment_sum(self, seg: jnp.ndarray,
                          rows: Optional[jnp.ndarray],
                          total: int) -> jnp.ndarray:
        """Device step of one sparse hop: scatter-add per-edge contributions
        into the flattened ``(parent, code)`` segment space at the device
        segment ids ``seg``.  ``rows`` is ``None`` for a leaf hop (each
        edge contributes 1) or the gathered dense block ``(edges, Dd)``.
        The single-device base runs one ``jax.ops.segment_sum``;
        :class:`~repro.core.distributed.ShardedSparseExecutor` overrides
        this with an edge-sharded ``shard_map`` + ``psum``.

        Backend routing: when :func:`repro.kernels.ops
        .segsum_kernel_enabled` says so (accelerator present, or
        ``REPRO_SEGSUM_PALLAS=1`` on CPU CI, and the segment space is
        small enough for the one-hot sweep) the scatter-add runs through
        the Pallas kernel (:mod:`repro.kernels.segsum_kernel`) with
        ``interpret`` resolved by the same backend probe — Mosaic on
        TPU, Triton on GPU, the interpreter on CPU."""
        if _segsum_kernel_enabled(total):
            from ..kernels import ops as kernel_ops
            if rows is None:
                out = kernel_ops.ones_segment_sum(
                    seg, jnp.ones((seg.shape[0],), dtype=jnp.float32),
                    total)
            else:
                out = kernel_ops.edge_segment_sum(seg, rows, total)
            return out.astype(self.dtype)
        if rows is None:
            return jax.ops.segment_sum(
                jnp.ones((seg.shape[0],), dtype=self.dtype), seg,
                num_segments=total)
        return jax.ops.segment_sum(rows, seg, num_segments=total)

    def _node_message(self, db: RelationalDB, node: NodeSpec,
                      stats: Optional[CostStats]) -> _SparseMsg:
        code, ds = self._entity_code(db, node.own)
        dense: Optional[jnp.ndarray] = None
        dvars: Tuple[CtVar, ...] = ()
        for hop in node.hops:
            child = self._node_message(db, hop.child_node, stats)
            h, hvars = self._hop(db, hop, child, stats)
            if dense is None:
                dense, dvars = h, hvars
            else:
                n, d = dense.shape
                dense = (dense[:, :, None] * h[:, None, :]).reshape(
                    n, d * h.shape[1])
                dvars = dvars + hvars
        return _SparseMsg(code, ds, tuple(node.own.attrs), dense, dvars)

    def _ones_segment_sum(self, code: jnp.ndarray, ds: int) -> jnp.ndarray:
        """Jitted ``segment_sum`` of ones — the histogram primitive.  An
        eager scatter dispatch costs milliseconds on CPU and histograms
        are recomputed on every cache miss, so the compiled kernel is
        cached per ``(n, ds)`` in ``_batch_cache``."""
        n = int(code.shape[0])
        if _segsum_kernel_enabled(ds):
            from ..kernels import ops as kernel_ops
            return kernel_ops.ones_segment_sum(
                code, jnp.ones((n,), dtype=jnp.float32), ds
            ).astype(self.dtype)
        key = ("ones_seg", n, ds)
        fn = self._batch_cache.get(key)
        if fn is None:
            def run(c):
                return jax.ops.segment_sum(
                    jnp.ones((n,), dtype=self.dtype), c, num_segments=ds)

            fn = self._batch_cache[key] = jax.jit(run)
        return fn(code)

    def _reduce_by_code(self, code: Optional[jnp.ndarray], ds: int, n: int,
                        factors: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """``out[c, :] = sum_{i: code[i]=c} ⊗_f factors[f][i, :]`` —
        the root combine as one segment-sum (chunked when the Khatri-Rao
        expansion would not fit)."""
        if code is None:
            code = jnp.zeros((n,), dtype=jnp.int32)
        if not factors:
            return self._ones_segment_sum(code, ds)
        if len(factors) == 1:
            return jax.ops.segment_sum(factors[0], code,
                                       num_segments=ds).reshape(-1)
        return _kr_segment_sum(code, factors, ds, self.dtype).reshape(-1)

    def hop_message(self, db: RelationalDB, hop: HopSpec,
                    stats: Optional[CostStats] = None
                    ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        child = self._node_message(db, hop.child_node, stats)
        return self._hop(db, hop, child, stats)

    def hist(self, db: RelationalDB, var: Var, attrs: Tuple[CtVar, ...],
             stats: Optional[CostStats] = None) -> CtTable:
        fs = FactorSpec(var, tuple(attrs))
        code, ds = self._entity_code(db, fs)
        n = db.entities[var.etype].size
        flat = self._reduce_by_code(code, ds, n, ())
        if not fs.attrs:
            return CtTable((), flat[0])
        return CtTable(fs.attrs, flat.reshape(tuple(v.card for v in fs.attrs)))

    def leaf_hop(self, db: RelationalDB, atom: Atom, child: Var, parent: Var,
                 child_attrs: Tuple[CtVar, ...],
                 edge_attrs: Tuple[CtVar, ...],
                 stats: Optional[CostStats] = None
                 ) -> Tuple[jnp.ndarray, Tuple[CtVar, ...]]:
        fs = FactorSpec(child, tuple(child_attrs))
        leaf = NodeSpec(fs, (), fs.attrs)
        hop = HopSpec(atom, child, parent, tuple(edge_attrs), leaf,
                      fs.attrs + tuple(edge_attrs))
        return self.hop_message(db, hop, stats)

    def root_reduce(self, db: RelationalDB, own: FactorSpec,
                    factors: Sequence[Tuple[jnp.ndarray, Tuple[CtVar, ...]]],
                    keep: Sequence[CtVar],
                    stats: Optional[CostStats] = None) -> CtTable:
        code, ds = self._entity_code(db, own)
        n = db.entities[own.var.etype].size
        mvars: List[CtVar] = list(own.attrs)
        mats: List[jnp.ndarray] = []
        for m, vs in factors:
            mats.append(m)
            mvars.extend(vs)
        flat = self._reduce_by_code(code, ds, n, mats)
        return _finalise(flat, mvars, keep, stats)

    # -- traced batched evaluation ------------------------------------------
    def _flat_from_arrays(self, db: RelationalDB, plan: ContractionPlan,
                          cur: _ArrayCursor) -> jnp.ndarray:
        """Traced mirror of ``_entity_code``/``_hop``/``_node_message``
        + ``root_reduce``, reading an input pack, so the whole evaluation
        traces under ``vmap``; it runs the eager path's code and segment-id
        programs inside its trace, gathering codes element by element
        (by rows, a batch of plans would fetch a batch of rows per step).
        The int32 segment-space guard is static, so it still raises at
        trace time."""
        def entity_code(fs: FactorSpec):
            if not fs.attrs:
                return None, 1
            cols = tuple(cur.take() for _ in fs.attrs)
            return (_mixed_radix(cols, tuple(cv.card for cv in fs.attrs)),
                    fs.card)

        def hop_from(hop: HopSpec, msg: _SparseMsg) -> jnp.ndarray:
            g, s = cur.take(), cur.take()
            n_parent = db.entities[hop.parent.etype].size
            n_edges = int(g.shape[0])
            cards = tuple(cv.card for cv in hop.edge_attrs)
            ds, total = _hop_code_space(msg.ds, cards, n_parent)
            seg = _hop_segment_ids(msg.code, g, s,
                                   tuple(cur.take() for _ in cards),
                                   ds=ds, cards=cards)
            if msg.dense is None:
                flat = jax.ops.segment_sum(
                    jnp.ones((n_edges,), dtype=self.dtype), seg,
                    num_segments=total)
                return flat.reshape(n_parent, ds)
            agg = jax.ops.segment_sum(msg.dense[g], seg, num_segments=total)
            return agg.reshape(n_parent, ds * msg.dense.shape[1])

        def node_msg(node: NodeSpec) -> _SparseMsg:
            code, ds = entity_code(node.own)
            dense: Optional[jnp.ndarray] = None
            for hop in node.hops:
                h = hop_from(hop, node_msg(hop.child_node))
                if dense is None:
                    dense = h
                else:
                    nn, d = dense.shape
                    dense = (dense[:, :, None] * h[:, None, :]).reshape(
                        nn, d * h.shape[1])
            return _SparseMsg(code, ds, (), dense, ())

        code, ds = entity_code(plan.root.own)
        n = db.entities[plan.root.var.etype].size
        mats = [hop_from(hop, node_msg(hop.child_node))
                for hop in plan.root.hops]
        return self._reduce_by_code(code, ds, n, mats)

    def _flat_vars(self, plan: ContractionPlan) -> Tuple[CtVar, ...]:
        # the sparse recursion emits (child own attrs, edge attrs) scalar-
        # coded first, then the child's aggregated dense axes — NOT the
        # planner's out_vars order; mirror it structurally
        def hop_vars(hop: HopSpec) -> List[CtVar]:
            child = hop.child_node
            out = list(child.own.attrs) + list(hop.edge_attrs)
            for h in child.hops:
                out.extend(hop_vars(h))
            return out

        out: List[CtVar] = list(plan.root.own.attrs)
        for hop in plan.root.hops:
            out.extend(hop_vars(hop))
        return tuple(out)


EXECUTORS = {"dense": DenseExecutor, "sparse": SparseExecutor}


def make_executor(name, **kw) -> Executor:
    """Resolve an executor by name (or pass an instance through)."""
    if isinstance(name, Executor):
        return name
    return EXECUTORS[name.lower()](**kw)
