"""Routed mixture-of-experts with sort-based capacity dispatch.

TPU adaptation: instead of the GShard one-hot dispatch einsum (whose
[groups, tokens, experts, capacity] tensor is quadratically wasteful at
top-8/128e), tokens are ranked *within their expert* via an argsort +
running-position trick — all static shapes — and scattered into a
[B, E, C, D] capacity buffer.  Expert FFNs are a batched einsum over the
expert axis, which the sharding rules place on the ``model`` mesh axis
(expert parallelism); the scatter/gather across the batch->expert sharding
boundary is the MoE all-to-all.

Over-capacity tokens are dropped (standard capacity-factor semantics); the
router uses softmax-then-topk with the auxiliary load-balancing loss of
Shazeer et al.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import dense_init
from .mlp import MlpParams, mlp_apply
from .pspec import constrain


class MoeParams(NamedTuple):
    router: jnp.ndarray              # [D, E]
    wi: jnp.ndarray                  # [E, D, F]
    wo: jnp.ndarray                  # [E, F, D]
    wg: Optional[jnp.ndarray] = None # [E, D, F] (swiglu)


def moe_init(key, cfg: ModelConfig) -> MoeParams:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 4)
    shape_in = (e, d, f)
    wi = (jax.random.normal(ks[0], shape_in, jnp.float32) * d ** -0.5).astype(dt)
    wo = (jax.random.normal(ks[1], (e, f, d), jnp.float32) * f ** -0.5).astype(dt)
    wg = ((jax.random.normal(ks[3], shape_in, jnp.float32) * d ** -0.5).astype(dt)
          if cfg.mlp == "swiglu" else None)
    return MoeParams(router=dense_init(ks[2], d, e, jnp.float32),
                     wi=wi, wo=wo, wg=wg)


def _capacity(tokens_per_group: int, top_k: int, n_experts: int,
              factor: float) -> int:
    c = int(tokens_per_group * top_k * factor / n_experts)
    return max(c, 1)


def moe_apply(p: MoeParams, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar).  Dispatches on
    ``cfg.moe_impl``: "ep" = shard_map expert parallelism (local dispatch +
    one psum combine), "spmd" = sharding-constraint GSPMD path (baseline;
    XLA replicates the dispatch scatter — see EXPERIMENTS.md §Perf H1)."""
    if cfg.moe_impl == "ep":
        am = jax.sharding.get_abstract_mesh()
        if am is not None and not am.empty and "model" in am.axis_names \
                and cfg.n_experts % am.shape["model"] == 0:
            return _moe_apply_ep(p, x, cfg, am)
    return _moe_apply_spmd(p, x, cfg)


def _moe_apply_ep(p: MoeParams, x: jnp.ndarray, cfg: ModelConfig, am
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism as shard_map: activations are replicated across
    ``model`` (the Megatron MLP invariant), so every expert shard computes
    the (cheap) routing redundantly, *locally* gathers only the tokens bound
    for its own experts, runs its expert FFNs, scatters partial outputs back
    to token order, and one ``psum`` over ``model`` combines.  Dispatch
    moves ZERO bytes over links; combine costs one [b_l, S, D] all-reduce
    per layer — the same wire cost as a dense TP MLP."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    names = set(am.axis_names)
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    n_fsdp = int(np.prod([am.shape[a] for a in fsdp])) if fsdp else 1
    bspec = fsdp if (fsdp and x.shape[0] % n_fsdp == 0) else None
    n_model = am.shape["model"]

    x_spec = P(bspec, None, None)
    w_spec = MoeParams(router=P(None, None), wi=P("model", None, None),
                       wo=P("model", None, None),
                       wg=None if p.wg is None else P("model", None, None))

    def body(x_l, p_l):
        out, me, ce = _moe_local(p_l, x_l, cfg, n_model)
        out = jax.lax.psum(out, "model")
        if bspec:
            me = jax.lax.pmean(me, bspec)    # global load stats, so the
            ce = jax.lax.pmean(ce, bspec)    # nonlinear aux matches GSPMD
        aux = jnp.sum(me * ce) * cfg.n_experts
        return out, aux.astype(jnp.float32)

    fn = shard_map(body, mesh=am, in_specs=(x_spec, w_spec),
                   out_specs=(x_spec, P()), check_vma=False)
    return fn(x, p)


def _moe_local(p: MoeParams, x: jnp.ndarray, cfg: ModelConfig, n_model: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-shard MoE: route all tokens, keep only local experts' slots."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    epl = e // n_model                                   # experts per shard
    c = _capacity(s, k, e, cfg.capacity_factor)
    m_idx = jax.lax.axis_index("model") if n_model > 1 else 0
    lo = m_idx * epl

    logits = jnp.einsum("bsd,de->bse", x, p.router.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                 # [B,S,K]
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, e, dtype=jnp.float32), axis=2),
        axis=(0, 1)) / k

    a = s * k
    flat_e = eidx.reshape(b, a)
    flat_t = jnp.broadcast_to(jnp.arange(s)[:, None], (s, k)).reshape(a)
    flat_g = gate.reshape(b, a)
    order = jnp.argsort(flat_e, axis=1)
    e_sorted = jnp.take_along_axis(flat_e, order, axis=1)
    t_sorted = jnp.take_along_axis(jnp.broadcast_to(flat_t, (b, a)), order, axis=1)
    g_sorted = jnp.take_along_axis(flat_g, order, axis=1)
    ar = jnp.arange(a)
    change = jnp.concatenate(
        [jnp.ones((b, 1), bool), e_sorted[:, 1:] != e_sorted[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(change, ar[None, :], 0), axis=1)
    pos = ar[None, :] - run_start
    local = (pos < c) & (e_sorted >= lo) & (e_sorted < lo + epl)
    slot = jnp.where(local, (e_sorted - lo) * c + pos, epl * c)

    xt = jnp.take_along_axis(x, t_sorted[..., None], axis=1)   # [B, A, D]
    bidx = jnp.arange(b)[:, None]
    buf = jnp.zeros((b, epl * c + 1, d), x.dtype)
    buf = buf.at[bidx, slot].add(xt)                     # local scatter
    buf = buf[:, : epl * c].reshape(b, epl, c, d)

    wi = jax.lax.dynamic_slice_in_dim(p.wi, lo, epl, 0) \
        if p.wi.shape[0] != epl else p.wi
    wo = jax.lax.dynamic_slice_in_dim(p.wo, lo, epl, 0) \
        if p.wo.shape[0] != epl else p.wo
    h = jnp.einsum("becd,edf->becf", buf, wi.astype(buf.dtype))
    if p.wg is not None:
        wg = jax.lax.dynamic_slice_in_dim(p.wg, lo, epl, 0) \
            if p.wg.shape[0] != epl else p.wg
        g2 = jnp.einsum("becd,edf->becf", buf, wg.astype(buf.dtype))
        h = jax.nn.silu(g2.astype(jnp.float32)).astype(buf.dtype) * h
    else:
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(buf.dtype)
    eo = jnp.einsum("becf,efd->becd", h, wo.astype(buf.dtype))
    eo = eo.reshape(b, epl * c, d)
    eo = jnp.concatenate([eo, jnp.zeros((b, 1, d), eo.dtype)], axis=1)

    back = eo[bidx, slot]                                # [B, A, D]
    back = back * (g_sorted * local)[..., None].astype(back.dtype)
    out = jnp.zeros((b, s, d), x.dtype)
    out = out.at[bidx, t_sorted].add(back)
    return out, me, ce


def _moe_apply_spmd(p: MoeParams, x: jnp.ndarray, cfg: ModelConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GSPMD baseline (sharding constraints only)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = _capacity(s, k, e, cfg.capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p.router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                 # [B,S,K]
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # aux load-balancing loss
    me = jnp.mean(probs, axis=(0, 1))                    # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, e, dtype=jnp.float32), axis=2), axis=(0, 1)) / k
    aux = jnp.sum(me * ce) * e

    # ---- sort-based positions within expert, per group --------------------
    a = s * k
    flat_e = eidx.reshape(b, a)                          # [B, A]
    flat_t = jnp.broadcast_to(jnp.arange(s)[:, None], (s, k)).reshape(a)
    flat_g = gate.reshape(b, a)
    order = jnp.argsort(flat_e, axis=1)                  # stable
    e_sorted = jnp.take_along_axis(flat_e, order, axis=1)
    t_sorted = jnp.take_along_axis(jnp.broadcast_to(flat_t, (b, a)), order, axis=1)
    g_sorted = jnp.take_along_axis(flat_g, order, axis=1)
    ar = jnp.arange(a)
    change = jnp.concatenate(
        [jnp.ones((b, 1), bool), e_sorted[:, 1:] != e_sorted[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(change, ar[None, :], 0), axis=1)
    pos = ar[None, :] - run_start                        # rank within expert
    keep = pos < c
    slot = jnp.where(keep, e_sorted * c + pos, e * c)    # drop -> sentinel row

    # ---- dispatch: gather token features into the capacity buffer ---------
    xt = jnp.take_along_axis(x, t_sorted[..., None], axis=1)   # [B, A, D]
    buf = jnp.zeros((b, e * c + 1, d), x.dtype)
    bidx = jnp.arange(b)[:, None]
    buf = buf.at[bidx, slot].add(xt)                     # all-to-all boundary
    buf = buf[:, : e * c].reshape(b, e, c, d)
    buf = constrain(buf, "B", "T", None, None)           # EP layout

    # ---- expert FFN (batched over the expert axis = EP) -------------------
    h = jnp.einsum("becd,edf->becf", buf, p.wi.astype(buf.dtype))
    if p.wg is not None:
        g2 = jnp.einsum("becd,edf->becf", buf, p.wg.astype(buf.dtype))
        h = jax.nn.silu(g2.astype(jnp.float32)).astype(buf.dtype) * h
    else:
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(buf.dtype)
    eo = jnp.einsum("becf,efd->becd", h, p.wo.astype(buf.dtype))
    eo = constrain(eo, "B", "T", None, None)
    eo = eo.reshape(b, e * c, d)
    eo = jnp.concatenate([eo, jnp.zeros((b, 1, d), eo.dtype)], axis=1)

    # ---- combine: weighted scatter-add back to token order ----------------
    back = eo[bidx, slot]                                # [B, A, D]
    back = back * (g_sorted * keep)[..., None].astype(back.dtype)
    out = jnp.zeros((b, s, d), x.dtype)
    out = out.at[bidx, t_sorted].add(back)
    return out, aux.astype(jnp.float32)
