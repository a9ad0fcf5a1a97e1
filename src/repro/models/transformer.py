"""Model assembly: blocks, scan-over-layers stacks, train/prefill/decode.

* One homogeneous block type per architecture (attn | moe | rwkv | hymba),
  stacked with ``lax.scan`` over a [L, ...] parameter pytree (HLO size is
  O(1) in depth — essential for 96-layer dry-runs) and per-layer ``remat``.
* Decode: KV caches are [L, B, S, Hkv, hd] with the sequence axis shardable
  over the ``model`` mesh axis; the flash-decoding combine runs inside
  ``shard_map`` (see ``decode_attention``).
* Whisper: encoder stack + decoder blocks with cross-attention; the audio
  frontend is a stub — inputs are precomputed frame embeddings.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .attention import (AttnParams, attn_init, block_attention,
                        combine_partials, decode_partial, qkv_project,
                        sharded_attention)
from .config import ModelConfig
from .layers import (embed_init, embed_lookup, rms_norm, sinusoidal_positions,
                     tied_logits)
from .mlp import MlpParams, mlp_apply, mlp_init
from .moe import MoeParams, moe_apply, moe_init
from .rwkv import (RwkvParams, rwkv_channel_mix, rwkv_channel_mix_decode,
                   rwkv_init, rwkv_token_mix, rwkv_token_mix_decode)
from .ssm import SsmParams, ssm_apply, ssm_decode, ssm_init


# ---------------------------------------------------------------- blocks ---

def block_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": jnp.ones((d,), jnp.float32),
                         "norm2": jnp.ones((d,), jnp.float32)}
    if cfg.block == "attn":
        p["attn"] = attn_init(ks[0], cfg)
        p["mlp"] = mlp_init(ks[1], cfg)
    elif cfg.block == "moe":
        p["attn"] = attn_init(ks[0], cfg)
        p["moe"] = moe_init(ks[1], cfg)
        if cfg.dense_residual:
            p["dense"] = mlp_init(ks[2], cfg)
    elif cfg.block == "rwkv":
        p["rwkv"] = rwkv_init(ks[0], cfg)
    elif cfg.block == "hymba":
        p["attn"] = attn_init(ks[0], cfg)
        p["ssm"] = ssm_init(ks[1], cfg)
        p["mlp"] = mlp_init(ks[2], cfg)
    else:
        raise ValueError(cfg.block)
    return p


def block_apply(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
                positions, causal: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block (train / prefill).  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.block == "rwkv":
        h, _ = rwkv_token_mix(p["rwkv"], rms_norm(x, p["norm1"]), cfg)
        x = x + h
        h, _ = rwkv_channel_mix(p["rwkv"], rms_norm(x, p["norm2"]))
        return x + h, aux
    n1 = rms_norm(x, p["norm1"])
    q, k, v = qkv_project(p["attn"], n1, cfg, positions)
    ao = sharded_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    b, s, hq, hd = ao.shape
    ao = jnp.einsum("bsh,hd->bsd", ao.reshape(b, s, hq * hd),
                    p["attn"].wo.astype(x.dtype))
    if cfg.block == "hymba":
        so, _ = ssm_apply(p["ssm"], n1, cfg)
        ao = (ao + so) * 0.5
    x = x + ao
    n2 = rms_norm(x, p["norm2"])
    if cfg.block == "moe":
        mo, aux = moe_apply(p["moe"], n2, cfg)
        if cfg.dense_residual:
            mo = mo + mlp_apply(p["dense"], n2, cfg.mlp)
    else:
        mo = mlp_apply(p["mlp"], n2, cfg.mlp)
    return x + mo, aux


# ------------------------------------------------------- decode attention ---

def decode_attention(q, cache_k, cache_v, new_k, new_v, pos,
                     dp_axes: Optional[tuple], seq_axis: Optional[str],
                     mesh=None):
    """One-token attention against a (possibly sequence-sharded) KV cache.

    q [B,Hq,hd]; cache_k/v [B,S,Hkv,hd]; new_k/v [B,Hkv,hd]; pos scalar i32.
    When ``seq_axis`` is set the cache S axis is sharded over that mesh axis
    and the softmax is combined with one psum (flash-decoding)."""

    def local(q_, k_, v_, nk, nv, shards, shard_idx):
        s_local = k_.shape[1]
        off = shard_idx * s_local
        lpos = pos - off
        in_rng = (lpos >= 0) & (lpos < s_local)
        li = jnp.clip(lpos, 0, s_local - 1)
        k2 = jax.lax.dynamic_update_slice(k_, nk[:, None], (0, li, 0, 0))
        v2 = jax.lax.dynamic_update_slice(v_, nv[:, None], (0, li, 0, 0))
        k_ = jnp.where(in_rng, k2, k_)
        v_ = jnp.where(in_rng, v2, v_)
        valid = (off + jnp.arange(s_local))[None, :] <= pos
        valid = jnp.broadcast_to(valid, (k_.shape[0], s_local))
        part = decode_partial(q_, k_, v_, valid)
        return k_, v_, part

    if seq_axis is None:
        k_, v_, part = local(q, cache_k, cache_v, new_k, new_v, 1, 0)
        return combine_partials(part, None).astype(q.dtype), k_, v_

    def inner(q_, k_, v_, nk, nv):
        idx = jax.lax.axis_index(seq_axis)
        k_, v_, part = local(q_, k_, v_, nk, nv,
                             jax.lax.axis_size(seq_axis), idx)
        o = combine_partials(part, seq_axis)
        return o.astype(q_.dtype), k_, v_

    # batch must divide the dp axes to be shard_map'd over them; replicate
    # the batch otherwise (e.g. long_500k's global_batch=1)
    if dp_axes and mesh is not None:
        import numpy as _np
        dp_size = int(_np.prod([mesh.shape[a] for a in dp_axes]))
        if q.shape[0] % dp_size != 0:
            dp_axes = None
    qspec = P(dp_axes if dp_axes else None, None, None)
    kvspec = P(dp_axes if dp_axes else None, seq_axis, None, None)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(qspec, kvspec, kvspec, qspec, qspec),
                   out_specs=(qspec, kvspec, kvspec), check_vma=False)
    return fn(q, cache_k, cache_v, new_k, new_v)


def block_decode(p: Dict[str, Any], x1: jnp.ndarray, cache: Dict[str, Any],
                 cfg: ModelConfig, pos, positions,
                 dp_axes=None, seq_axis=None, mesh=None):
    """One-token block step.  x1 [B, D].  Returns (x1, new_cache)."""
    new_cache = dict(cache)
    if cfg.block == "rwkv":
        h, st = rwkv_token_mix_decode(
            p["rwkv"], rms_norm(x1, p["norm1"]), cfg,
            (cache["tm_x"], cache["wkv"]))
        x1 = x1 + h
        new_cache["tm_x"], new_cache["wkv"] = st
        h, cmx = rwkv_channel_mix_decode(
            p["rwkv"], rms_norm(x1, p["norm2"]), cache["cm_x"])
        new_cache["cm_x"] = cmx
        return x1 + h, new_cache
    n1 = rms_norm(x1, p["norm1"])
    q, k, v = qkv_project(p["attn"], n1[:, None], cfg, positions)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    o, ck, cv = decode_attention(q, cache["k"], cache["v"], k, v, pos,
                                 dp_axes, seq_axis, mesh)
    new_cache["k"], new_cache["v"] = ck, cv
    b = x1.shape[0]
    ao = (o.reshape(b, -1) @ p["attn"].wo.astype(x1.dtype))
    if cfg.block == "hymba":
        so, s1 = ssm_decode(p["ssm"], n1, cfg, cache["ssm"])
        new_cache["ssm"] = s1
        ao = (ao + so) * 0.5
    x1 = x1 + ao
    n2 = rms_norm(x1, p["norm2"])
    if cfg.block == "moe":
        mo, _ = moe_apply(p["moe"], n2[:, None], cfg)
        mo = mo[:, 0]
        if cfg.dense_residual:
            mo = mo + mlp_apply(p["dense"], n2[:, None], cfg.mlp)[:, 0]
    else:
        mo = mlp_apply(p["mlp"], n2[:, None], cfg.mlp)[:, 0]
    return x1 + mo, new_cache


def init_cache(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, Any]:
    """Per-layer decode cache (stacked [L, ...])."""
    l, hk, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    dt = cfg.act_dtype()
    c: Dict[str, Any] = {}
    if cfg.block in ("attn", "moe", "hymba"):
        c["k"] = jnp.zeros((l, batch, seq, hk, hd), dt)
        c["v"] = jnp.zeros((l, batch, seq, hk, hd), dt)
    if cfg.block == "hymba":
        h, hdv = cfg.ssm_heads, cfg.hd
        c["ssm"] = jnp.zeros((l, batch, h, cfg.ssm_state, hdv), jnp.float32)
    if cfg.block == "rwkv":
        d = cfg.d_model
        h = d // cfg.rwkv_head_dim
        c["tm_x"] = jnp.zeros((l, batch, d), dt)
        c["cm_x"] = jnp.zeros((l, batch, d), dt)
        c["wkv"] = jnp.zeros((l, batch, h, cfg.rwkv_head_dim,
                              cfg.rwkv_head_dim), jnp.float32)
    if cfg.enc_dec:
        c["xk"] = jnp.zeros((l, batch, cfg.enc_frames, hk, hd), dt)
        c["xv"] = jnp.zeros((l, batch, cfg.enc_frames, hk, hd), dt)
    return c


# ------------------------------------------------------ whisper enc/dec -----

def cross_block_init(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p = block_init(ks[0], cfg)
    p["norm_x"] = jnp.ones((d,), jnp.float32)
    p["xattn"] = attn_init(ks[1], cfg)
    return p


def cross_block_apply(p, x, enc_kv, cfg: ModelConfig, positions):
    """Decoder block with cross-attention.  enc_kv = (k, v) precomputed."""
    n1 = rms_norm(x, p["norm1"])
    q, k, v = qkv_project(p["attn"], n1, cfg, positions)
    ao = sharded_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    b, s, hq, hd = ao.shape
    x = x + jnp.einsum("bsh,hd->bsd", ao.reshape(b, s, hq * hd),
                       p["attn"].wo.astype(x.dtype))
    nx = rms_norm(x, p["norm_x"])
    qx = jnp.einsum("bsd,dh->bsh", nx, p["xattn"].wq.astype(x.dtype))
    qx = qx.reshape(b, s, cfg.n_heads, hd)
    xo = block_attention(qx, enc_kv[0], enc_kv[1], causal=False,
                         chunk=cfg.attn_chunk)
    x = x + jnp.einsum("bsh,hd->bsd", xo.reshape(b, s, cfg.n_heads * hd),
                       p["xattn"].wo.astype(x.dtype))
    n2 = rms_norm(x, p["norm2"])
    return x + mlp_apply(p["mlp"], n2, cfg.mlp), jnp.zeros((), jnp.float32)


def cross_kv(p, enc_out, cfg: ModelConfig):
    b, f, d = enc_out.shape
    hk, hd = cfg.n_kv_heads, cfg.hd
    k = jnp.einsum("bfd,dh->bfh", enc_out, p["xattn"].wk.astype(enc_out.dtype))
    v = jnp.einsum("bfd,dh->bfh", enc_out, p["xattn"].wv.astype(enc_out.dtype))
    return k.reshape(b, f, hk, hd), v.reshape(b, f, hk, hd)
