"""Chunked linear attention with data-dependent decay.

Shared sequence-mixing core for RWKV-6 (vector decay per key channel, Finch)
and the Mamba-2/SSD-style heads in Hymba (scalar decay per head, broadcast to
the key channels).  Recurrence per head:

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)      (u = 0 for SSD heads)

Chunk algorithm (numerically safe — every exponent is <= 0 because the
cumulative log-decay P is non-increasing):

    inter:  o_t += (r_t  exp(P_{t-1})) . S_0
    intra:  A[t,i] = sum_d r_t[d] k_i[d] exp(P_{t-1,d} - P_{i,d}),  i < t
    state:  S' = diag(exp(P_last)) S_0 + sum_i (k_i exp(P_last - P_i)) v_i^T

The O(c^2 d_k) pairwise tensor lives only inside one scan step — memory is
bounded by the chunk size, never by the sequence (this is what makes the
``long_500k`` cells runnable).  Decode is the O(1) recurrence update.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _chunk_mesh(n: int, b: int):
    """(mesh, batch_axes) when the chunk axis can shard over ``model``.

    The two heavy passes below are *batched over chunks* (no cross-chunk
    dependency), so the chunk axis shards over the TP axis — this is what
    makes the recurrent mixers scale on the mesh even when their head count
    (hymba: 25) does not divide it (§Perf H2 it.3).  shard_map (not a mere
    constraint) is required: GSPMD otherwise re-gathers around the
    surrounding transposes and keeps the compute replicated (measured —
    §Perf H2 it.3a, refuted)."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty or "model" not in am.axis_names:
        return None, None
    if am.shape["model"] == 1 or n % am.shape["model"] != 0:
        return None, None
    names = set(am.axis_names)
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    import numpy as _np
    n_fsdp = int(_np.prod([am.shape[a] for a in fsdp])) if fsdp else 1
    bspec = fsdp if (fsdp and b % n_fsdp == 0) else None
    return am, bspec


def chunked_linear_attention(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             logw: jnp.ndarray,
                             u: Optional[jnp.ndarray] = None,
                             chunk: int = 64,
                             state0: Optional[jnp.ndarray] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,logw: [B,S,H,dk]; v: [B,S,H,dv]; u: [H,dk] or None.

    Returns (o [B,S,H,dv], final_state [B,H,dk,dv]).

    Two-pass parallel-scan formulation (Mamba-2 / GLA style):
      pass 1 (chunk-parallel): local state contribution + total decay per chunk;
      combine (sequential, tiny): [n] x [b,h,dk,dv] state recurrence;
      pass 2 (chunk-parallel): inter- + intra-chunk outputs.
    Both heavy passes are batched einsums over the chunk axis, which is
    sharded over the ``model`` mesh axis — compute parallelises even for
    head counts that do not divide it.  All exponents remain <= 0."""
    b, s, h, dk = r.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    while s % c:
        c -= 1
    n = s // c

    rr = r.astype(jnp.float32).reshape(b, n, c, h, dk).transpose(1, 0, 3, 2, 4)
    kk = k.astype(jnp.float32).reshape(b, n, c, h, dk).transpose(1, 0, 3, 2, 4)
    vv = v.astype(jnp.float32).reshape(b, n, c, h, dv).transpose(1, 0, 3, 2, 4)
    lw = logw.astype(jnp.float32).reshape(b, n, c, h, dk).transpose(1, 0, 3, 2, 4)
    # shapes now [n, b, h, c, d*]

    if state0 is None:
        state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    uu = None if u is None else u.astype(jnp.float32)

    # ---- pass 1: per-chunk local state contribution (no carry) ------------
    def local_state(ri, ki, vi, lwi):
        P = jnp.cumsum(lwi, axis=2)
        Plast = P[:, :, -1:, :]
        k_dec = ki * jnp.exp(Plast - P)                   # <= 0 exponents
        S_loc = jnp.einsum("bhtd,bhtv->bhdv", k_dec, vi)
        return S_loc, jnp.exp(Plast.squeeze(2))           # [b,h,dk,dv], [b,h,dk]

    # ---- pass 2: per-chunk outputs (inter from S0, intra pairwise) --------
    mask_ti = jnp.tril(jnp.ones((c, c), bool), k=-1)

    def chunk_out(ri, ki, vi, lwi, S0):
        P = jnp.cumsum(lwi, axis=2)
        Pprev = P - lwi
        r_dec = ri * jnp.exp(Pprev)
        o_inter = jnp.einsum("bhtd,bhdv->bhtv", r_dec, S0)
        diff = Pprev[:, :, :, None, :] - P[:, :, None, :, :]   # [b,h,t,i,dk]
        M = jnp.where(mask_ti[None, None, :, :, None], jnp.exp(diff), 0.0)
        A = jnp.einsum("bhtd,bhid,bhtid->bhti", ri, ki, M)
        o_intra = jnp.einsum("bhti,bhiv->bhtv", A, vi)
        if uu is not None:  # current-token bonus
            cur = jnp.einsum("bhtd,hd,bhtd->bht", ri, uu, ki)
            o_intra = o_intra + cur[..., None] * vi
        return o_inter + o_intra

    # recompute the O(c^2) pairwise tensors in the backward pass instead of
    # saving them (the [n,b,h,c,c,dk] f32 stack dominated HBM — §Perf H2)
    chunk_out = jax.checkpoint(chunk_out)

    # ---- combine: tiny sequential recurrence over n chunk states ----------
    def comb(S, inp):
        S_l, dec = inp
        S_new = S * dec[..., None] + S_l
        return S_new, S                                   # emit state *before* chunk

    am, bspec = _chunk_mesh(n, b)
    if am is None:
        S_loc, decay = jax.vmap(local_state)(rr, kk, vv, lw)
        S_final, S0s = jax.lax.scan(comb, state0, (S_loc, decay))
        outs = jax.vmap(chunk_out)(rr, kk, vv, lw, S0s)
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        cspec = P("model", bspec, None, None, None)       # [n, b, h, c, d]
        sspec = P("model", bspec, None, None, None)       # [n, b, h, dk, dv]
        dspec = P("model", bspec, None, None)             # [n, b, h, dk]
        p1 = shard_map(lambda a, b_, c_, d_: jax.vmap(local_state)(a, b_, c_, d_),
                       mesh=am, in_specs=(cspec,) * 4,
                       out_specs=(sspec, dspec), check_vma=False)
        S_loc, decay = p1(rr, kk, vv, lw)
        # tiny sequential combine over n states: replicated (105 MB-scale)
        S_final, S0s = jax.lax.scan(comb, state0, (S_loc, decay))
        p2 = shard_map(lambda a, b_, c_, d_, e_: jax.vmap(chunk_out)(a, b_, c_, d_, e_),
                       mesh=am, in_specs=(cspec,) * 4 + (sspec,),
                       out_specs=P("model", bspec, None, None, None),
                       check_vma=False)
        outs = p2(rr, kk, vv, lw, S0s)

    o = outs.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dv)
    return o.astype(r.dtype), S_final


def linear_attention_decode(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            logw: jnp.ndarray, state: jnp.ndarray,
                            u: Optional[jnp.ndarray] = None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token update.  r,k,logw [B,H,dk]; v [B,H,dv]; state [B,H,dk,dv]."""
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    w = jnp.exp(logw.astype(jnp.float32))
    kv = kf[..., :, None] * vf[..., None, :]               # [B,H,dk,dv]
    if u is not None:
        eff = state + u.astype(jnp.float32)[None, :, :, None] * kv
    else:
        eff = state
    o = jnp.einsum("bhd,bhdv->bhv", rf, eff)
    new_state = state * w[..., None] + kv
    return o.astype(r.dtype), new_state
