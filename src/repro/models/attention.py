"""Attention for the model zoo.

Train/prefill: blockwise ("flash-style") attention as a pure-JAX
online-softmax scan over KV chunks — O(S * chunk) activation memory so
the 32k prefill and 4k train cells have credible memory_analysis, and
the remat story stays simple.  Supports causal, sliding-window and
cross attention with GQA grouping.

Decode: single-token attention against a KV cache.  At scale the cache
seq dim is sharded (over 'model', and also 'data' when global_batch=1);
``flash_decode`` is a shard_map that computes local partial softmax
(m, l, o) per shard and merges with a log-sum-exp psum — flash-decoding
mapped onto jax.lax collectives.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.common import apply_rope, dense_init

_NEG = -1e30


# ----------------------------------------------------------------- params
def init_attn(key, d_model: int, n_heads: int, n_kv: int, hd: int,
              dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, n_heads * hd), 0, dtype=dtype),
        "wk": dense_init(kk, (d_model, n_kv * hd), 0, dtype=dtype),
        "wv": dense_init(kv, (d_model, n_kv * hd), 0, dtype=dtype),
        "wo": dense_init(ko, (n_heads * hd, d_model), 0, dtype=dtype),
    }


def attn_specs(par, stacked: bool = True):
    return {"wq": par.w_col(stacked), "wk": par.w_col(stacked),
            "wv": par.w_col(stacked), "wo": par.w_row(stacked)}


# ------------------------------------------------------------- blockwise
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_pos: jax.Array, k_pos: jax.Array, *,
                        causal: bool, window: int = 0,
                        chunk_q: int = 512, chunk_k: int = 512,
                        remat_qchunk: bool = False,
                        probs_bf16: bool = False) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd) -> (B, Sq, H, hd).

    Online softmax over KV chunks; masked chunks still execute (see
    EXPERIMENTS §Perf for the chunk-skipping optimization).

    remat_qchunk: recompute the per-q-chunk KV scan in the backward
    pass instead of saving the stacked (nk, B, Hkv, G, cq, ck) softmax
    intermediates — the flash-attention trade (EXPERIMENTS §Perf i1).
    probs_bf16: run the p @ v matmul with bf16 probabilities (m/l stats
    stay f32) — halves the dominant S^2 HBM traffic (§Perf i2).
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv

    def _divisor_chunk(s, c):
        for d in range(min(c, s), 0, -1):
            if s % d == 0:
                return d
        return 1

    cq = _divisor_chunk(sq, chunk_q)
    ck = _divisor_chunk(sk, chunk_k)
    nq, nk = sq // cq, sk // ck
    scale = hd ** -0.5

    qg = q.reshape(b, nq, cq, hkv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, Hkv, G, cq, hd)
    kc = k.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(nq, cq)
    kp = k_pos.reshape(nk, ck)

    def q_chunk(args):
        qc, qpos = args                                 # (B,Hkv,G,cq,hd)
        m0 = jnp.full((b, hkv, g, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, hd), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kk, vv, kpos = inp                          # (B,Hkv,ck,hd)
            s = jnp.einsum("bngqh,bnkh->bngqk", qc, kk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            if probs_bf16:
                p = p.astype(jnp.bfloat16)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bngqk,bnkh->bngqh", p, vv,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                       # (B,Hkv,G,cq,hd)

    if remat_qchunk:
        q_chunk = jax.checkpoint(q_chunk)
    out = jax.lax.map(q_chunk, (qg, qp))                 # (nq,B,Hkv,G,cq,hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def self_attention(params, x: jax.Array, positions: jax.Array, *,
                   n_heads: int, n_kv: int, hd: int, rope_theta: float,
                   causal: bool = True, window: int = 0,
                   chunk_q: int = 512, chunk_k: int = 512,
                   memory: Optional[jax.Array] = None,
                   memory_pos: Optional[jax.Array] = None,
                   return_kv: bool = False,
                   remat_qchunk: bool = False,
                   probs_bf16: bool = False,
                   par=None):
    """Full block: project -> rope -> blockwise attention -> out-proj.

    With ``memory`` set, k/v come from it (cross attention, no rope).
    With ``return_kv``, also returns the (post-rope) k, v for KV caches.
    """
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, n_heads, hd)
    src = x if memory is None else memory
    sk = src.shape[1]
    k = (src @ params["wk"]).reshape(b, sk, n_kv, hd)
    v = (src @ params["wv"]).reshape(b, sk, n_kv, hd)
    if memory is None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
        k_pos = positions[0] if positions.ndim > 1 else positions
    else:
        k_pos = (memory_pos if memory_pos is not None
                 else jnp.arange(sk, dtype=jnp.int32))
    q_pos = positions[0] if positions.ndim > 1 else positions
    if par is not None and par.active and par.attn_head_shard:
        # One explicit seq->head reshard per layer; without this the
        # partitioner re-derives shardings per chunk of the scan and
        # emits per-chunk all-to-alls (measured in §Perf i4).  Q shards
        # over query heads; K/V are pinned REPLICATED over the model
        # axis — with GQA, n_kv is often below the model-axis size and
        # letting the partitioner "shard" them produced repeated
        # replicate-repartition cycles (§Perf i5).  GQA K/V are small
        # (one gather of (B,S,n_kv,hd) per layer).
        q = par.shard(q, par.batch(), None, par.model_axis, None)
        k = par.shard(k, par.batch(), None, None, None)
        v = par.shard(v, par.batch(), None, None, None)
    out = blockwise_attention(q, k, v, q_pos, k_pos, causal=causal,
                              window=window, chunk_q=chunk_q,
                              chunk_k=chunk_k, remat_qchunk=remat_qchunk,
                              probs_bf16=probs_bf16)
    out = out.reshape(b, s, n_heads * hd)
    if par is not None and par.active and par.attn_head_shard:
        out = par.shard(out, par.batch(), None, par.model_axis)
    out = out @ params["wo"]
    if return_kv:
        return out, k, v
    return out


# --------------------------------------------------------------- decode
def _plain_decode(q, k_cache, v_cache, lengths, seq_offset=0):
    """q: (B, Hkv, G, hd); caches (B, S, Hkv, hd); lengths (B,) tokens valid."""
    b, s, hkv, hd = k_cache.shape
    scale = hd ** -0.5
    s_ = jnp.einsum("bngh,bsnh->bngs", q, k_cache,
                    preferred_element_type=jnp.float32) * scale
    pos = seq_offset + jnp.arange(s, dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]              # (B, S)
    s_ = jnp.where(valid[:, None, None, :], s_, _NEG)
    m = jnp.max(s_, axis=-1)
    p = jnp.exp(s_ - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bngs,bsnh->bngh", p, v_cache,
                   preferred_element_type=jnp.float32)
    return m, l, o


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, par, *,
                 seq_axes: Tuple[str, ...] = ()) -> jax.Array:
    """Single-token attention vs a (possibly seq-sharded) KV cache.

    q: (B, H, hd); caches: (B, S, Hkv, hd); lengths: (B,).
    seq_axes: mesh axes sharding the cache's S dim.  Partial softmax per
    shard, log-sum-exp merge via pmax/psum (flash-decoding on ICI).
    """
    b, h, hd = q.shape
    hkv = k_cache.shape[2]
    qg = q.reshape(b, hkv, h // hkv, hd)

    if not (par is not None and par.active and seq_axes):
        m, l, o = _plain_decode(qg, k_cache, v_cache, lengths)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, h, hd).astype(q.dtype)

    mesh = par.mesh
    n_shards = par.axis_size(seq_axes)
    s_loc = k_cache.shape[1] // n_shards
    batch_axes = tuple(a for a in par.batch_axes_
                       if a not in seq_axes) if b > 1 else ()

    def local(qg_, kc, vc, ln):
        rank = jnp.int32(0)
        for a in seq_axes:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        m, l, o = _plain_decode(qg_, kc, vc, ln, seq_offset=rank * s_loc)
        mg = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - mg)
        lg = jax.lax.psum(l * corr, seq_axes)
        og = jax.lax.psum(o * corr[..., None], seq_axes)
        return og / jnp.maximum(lg, 1e-30)[..., None]

    bspec = batch_axes if batch_axes else None
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec, seq_axes), P(bspec, seq_axes),
                  P(bspec)),
        out_specs=P(bspec),
        check_vma=False)
    out = fn(qg, k_cache, v_cache, lengths)
    return out.reshape(b, h, hd).astype(q.dtype)


def decode_self_attention(params, x_tok: jax.Array, cache: dict,
                          lengths: jax.Array, *, n_heads: int, n_kv: int,
                          hd: int, rope_theta: float, par=None,
                          seq_axes: Tuple[str, ...] = (),
                          window: int = 0) -> Tuple[jax.Array, dict]:
    """One decode step.  x_tok: (B, D); cache: {"k","v"}: (B, S, Hkv, hd).

    Returns (out (B, D), updated cache).  With ``window`` the cache is a
    ring buffer of size window (slot = position % window).
    """
    b, _ = x_tok.shape
    q = (x_tok @ params["wq"]).reshape(b, 1, n_heads, hd)
    k = (x_tok @ params["wk"]).reshape(b, 1, n_kv, hd)
    v = (x_tok @ params["wv"]).reshape(b, 1, n_kv, hd)
    q = apply_rope(q, lengths[:, None], rope_theta)[:, 0]
    k = apply_rope(k, lengths[:, None], rope_theta)[:, 0]
    v = v[:, 0]

    s_cache = cache["k"].shape[1]
    slot = (lengths % s_cache) if window else lengths
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype))

    if window:
        # Ring buffer: every stored slot is within the window by
        # construction; valid slots are min(lengths+1, window).
        eff_len = jnp.minimum(lengths + 1, window)
        m, l, o = _plain_decode(q.reshape(b, n_kv, n_heads // n_kv, hd),
                                k_cache, v_cache, eff_len)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, n_heads * hd)
    elif par is not None and par.active and par.decode_kv_head_shard:
        # KV-head-sharded decode: heads are independent, so no LSE
        # merge collective at all — each model rank attends over its
        # own head group with the FULL sequence (§Perf gemma3 decode).
        kvspec = (par.batch(), None, par.model_axis, None)
        k_cache = par.shard(k_cache, *kvspec)
        v_cache = par.shard(v_cache, *kvspec)
        qg = par.shard(q.reshape(b, n_kv, n_heads // n_kv, hd),
                       par.batch(), par.model_axis, None, None)
        m, l, o = _plain_decode(qg, k_cache, v_cache, lengths + 1)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, n_heads * hd)
    else:
        out = flash_decode(q, k_cache, v_cache, lengths + 1, par,
                           seq_axes=seq_axes).reshape(b, n_heads * hd)
    out = out.astype(x_tok.dtype) @ params["wo"]
    return out, {"k": k_cache, "v": v_cache}


def decode_cross_attention(params, x_tok: jax.Array, memory_kv: dict,
                           *, n_heads: int, n_kv: int, hd: int) -> jax.Array:
    """Cross attention at decode: static precomputed memory K/V."""
    b, _ = x_tok.shape
    q = (x_tok @ params["wq"]).reshape(b, n_kv, n_heads // n_kv, hd)
    mlen = memory_kv["k"].shape[1]
    lengths = jnp.full((b,), mlen, jnp.int32)
    m, l, o = _plain_decode(q, memory_kv["k"], memory_kv["v"], lengths)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, n_heads * hd)
    return out.astype(x_tok.dtype) @ params["wo"]
