"""GQA attention: chunked-causal train/prefill and partial-softmax decode.

* Train/prefill runs a ``lax.scan`` over query blocks (bounded [B, C, H, S]
  logits workspace — 32k prefill never materialises the full S x S matrix).
  On real TPUs the Pallas flash kernel (kernels/attention_kernel.py) replaces
  the inner block computation; the scanned-jnp path is what the dry-run
  lowers (Mosaic cannot target the CPU backend) and is numerically identical.
* Decode computes *partial* softmax statistics (max, sum-exp, unnormalised
  output) so the sequence axis of the KV cache can be sharded over the
  ``model`` mesh axis and combined with one psum (flash-decoding style) —
  see ``distributed/decode.py``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import apply_mrope, apply_rope, dense_init


class AttnParams(NamedTuple):
    wq: jnp.ndarray           # [D, Hq*hd]
    wk: jnp.ndarray           # [D, Hkv*hd]
    wv: jnp.ndarray           # [D, Hkv*hd]
    wo: jnp.ndarray           # [Hq*hd, D]
    bq: Optional[jnp.ndarray] = None
    bk: Optional[jnp.ndarray] = None
    bv: Optional[jnp.ndarray] = None


def attn_init(key, cfg: ModelConfig) -> AttnParams:
    d, hq, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.p_dtype()
    bias = (jnp.zeros((hq * hd,), dt), jnp.zeros((hk * hd,), dt),
            jnp.zeros((hk * hd,), dt)) if cfg.qkv_bias else (None, None, None)
    return AttnParams(
        wq=dense_init(ks[0], d, hq * hd, dt),
        wk=dense_init(ks[1], d, hk * hd, dt),
        wv=dense_init(ks[2], d, hk * hd, dt),
        wo=dense_init(ks[3], hq * hd, d, dt, scale=(hq * hd) ** -0.5),
        bq=bias[0], bk=bias[1], bv=bias[2],
    )


def qkv_project(p: AttnParams, x: jnp.ndarray, cfg: ModelConfig,
                positions: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, ...]:
    b, s, _ = x.shape
    hq, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p.wq.astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p.wk.astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p.wv.astype(x.dtype))
    if p.bq is not None:
        q, k, v = q + p.bq.astype(x.dtype), k + p.bk.astype(x.dtype), v + p.bv.astype(x.dtype)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hk, hd)
    v = v.reshape(b, s, hk, hd)
    if cfg.rope == "rope" and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope" and positions is not None:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _pick_chunk(s: int, want: int) -> int:
    """Largest divisor of ``s`` that is <= want (prefer the configured block)."""
    want = min(want, s)
    if s % want == 0:
        return want
    for c in range(want, 0, -1):
        if s % c == 0:
            return c
    return s


def block_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool, chunk: int,
                    kv_valid: Optional[jnp.ndarray] = None,
                    q_offset=0) -> jnp.ndarray:
    """q [B,Sq,Hq,hd] x k,v [B,Skv,Hkv,hd] -> [B,Sq,Hq,hd].

    Scans over query blocks; logits workspace is [B, C, Hq, Skv] f32.
    ``kv_valid`` [B, Skv] masks padded keys (encoder / ragged cross-attn).
    ``q_offset`` is the global position of q row 0 (sequence-parallel
    shards pass their shard offset so the causal mask stays global)."""
    b, sq, hq, hd = q.shape
    _, skv, hk, _ = k.shape
    g = hq // hk
    c = _pick_chunk(sq, chunk)
    nblk = sq // c
    scale = hd ** -0.5

    qb = q.reshape(b, nblk, c, hk, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kv_pos = jnp.arange(skv)

    def one_block(carry, inp):
        qi, blk_idx = inp
        # f32 accumulation WITHOUT materialising f32 copies of q/k (the MXU
        # accumulates in f32 natively; preferred_element_type expresses it)
        logits = jnp.einsum("bchgd,bshd->bchgs", qi, k,
                            preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            q_pos = q_offset + blk_idx * c + jnp.arange(c)
            mask = q_pos[:, None] >= kv_pos[None, :]            # [c, skv]
            mask = mask[None, :, None, None, :]
        if kv_valid is not None:
            kvm = kv_valid[:, None, None, None, :]
            mask = kvm if mask is None else (mask & kvm)
        if mask is not None:
            logits = jnp.where(mask, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        # probs in activation dtype @ v, f32 accumulation (flash-kernel
        # dtype policy; avoids an f32 copy of v per block)
        out = jnp.einsum("bchgs,bshd->bchgd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return carry, out.astype(q.dtype)

    # flash-attention residency: recompute logits/probs in the backward pass
    # instead of stacking [nblk, B, C, H, Skv] f32 score residuals (that
    # stack IS the full S x S matrix — §Perf H1 it.2 / H2)
    one_block = jax.checkpoint(one_block)

    _, outs = jax.lax.scan(one_block, None, (qb, jnp.arange(nblk)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, hd)
    return out


def sharded_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool, chunk: int,
                      kv_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Attention with automatic sequence parallelism over ``model``.

    When the query-head count divides the TP axis, GSPMD head sharding is
    already optimal and this is a plain :func:`block_attention`.  Otherwise
    (hymba's 25 heads, whisper's 8 on a 16-way axis) GSPMD replicates the
    whole attention on every chip; here we shard the *query sequence* axis
    over ``model`` instead — each shard computes all heads for Sq/tp query
    rows against the full KV (which TP already replicates at this point),
    with the causal mask offset to global positions.  Compute and score
    traffic drop by the TP degree; no extra collectives are introduced
    (outputs come back sequence-sharded and the next op's constraint
    re-lays them out).  §Perf H2."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty or "model" not in am.axis_names:
        return block_attention(q, k, v, causal, chunk, kv_valid)
    tp = am.shape["model"]
    b, sq, hq, _ = q.shape
    if tp == 1 or hq % tp == 0 or sq % tp != 0 or q.shape[0] == 0:
        return block_attention(q, k, v, causal, chunk, kv_valid)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    names = set(am.axis_names)
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    n_fsdp = int(np.prod([am.shape[a] for a in fsdp])) if fsdp else 1
    bspec = fsdp if (fsdp and b % n_fsdp == 0) else None
    s_local = sq // tp

    qspec = P(bspec, "model", None, None)
    kvspec = P(bspec, None, None, None)
    vspec = None if kv_valid is None else P(bspec, None)

    if kv_valid is None:
        def body(q_l, k_l, v_l):
            off = jax.lax.axis_index("model") * s_local
            return block_attention(q_l, k_l, v_l, causal,
                                   min(chunk, s_local), None, q_offset=off)
        fn = shard_map(body, mesh=am, in_specs=(qspec, kvspec, kvspec),
                       out_specs=qspec, check_vma=False)
        return fn(q, k, v)

    def body_v(q_l, k_l, v_l, kvv_l):
        off = jax.lax.axis_index("model") * s_local
        return block_attention(q_l, k_l, v_l, causal,
                               min(chunk, s_local), kvv_l, q_offset=off)
    fn = shard_map(body_v, mesh=am, in_specs=(qspec, kvspec, kvspec, vspec),
                   out_specs=qspec, check_vma=False)
    return fn(q, k, v, kv_valid)


class DecodePartial(NamedTuple):
    """Unnormalised partial attention over a KV shard (flash-decoding)."""
    o: jnp.ndarray            # [B, Hq, hd]  sum softmax-unnorm * V
    m: jnp.ndarray            # [B, Hq]      running max logit
    l: jnp.ndarray            # [B, Hq]      sum exp(logit - m)


def decode_partial(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   kv_valid: jnp.ndarray) -> DecodePartial:
    """q [B,Hq,hd]; k,v [B,S_shard,Hkv,hd]; kv_valid [B,S_shard] bool."""
    b, hq, hd = q.shape
    hk = k.shape[2]
    g = hq // hk
    scale = hd ** -0.5
    qf = q.reshape(b, hk, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32)) * scale
    logits = jnp.where(kv_valid[:, None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    # guard fully-masked shards (m = -1e30): zero their weight
    dead = m <= -1e29
    p = jnp.where(dead[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return DecodePartial(o=o.reshape(b, hq, hd),
                         m=jnp.where(dead, -jnp.inf, m).reshape(b, hq),
                         l=l.reshape(b, hq))


def combine_partials(parts: DecodePartial, axis_name: Optional[str] = None
                     ) -> jnp.ndarray:
    """Combine partial softmax stats; with ``axis_name`` the reduction runs as
    psum/pmax across mesh shards, otherwise the partials are already total."""
    o, m, l = parts
    if axis_name is None:
        safe_m = jnp.where(jnp.isinf(m), 0.0, m)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(o.dtype)
    gm = jax.lax.pmax(m, axis_name)
    gm_safe = jnp.where(jnp.isinf(gm), 0.0, gm)
    m_safe = jnp.where(jnp.isinf(m), gm_safe - 80.0, m)
    corr = jnp.exp(m_safe - gm_safe)
    o_sum = jax.lax.psum(o * corr[..., None], axis_name)
    l_sum = jax.lax.psum(l * corr, axis_name)
    return o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
