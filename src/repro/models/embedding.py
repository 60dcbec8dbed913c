"""Vocab-sharded embedding, chunked cross-entropy, greedy sampling.

Large-vocab rules (nemotron 256k, gemma3 262k, llama4 202k):
  * the (V, D) tables are sharded over the 'model' axis on V;
  * logits are NEVER materialized as (B, S, V): the loss runs over seq
    chunks inside a scan, each chunk computing LOCAL (B, C, V/m) logits
    and reducing with a log-sum-exp psum over the vocab shards;
  * decode samples greedily from local argmaxes + a pmax/pmin merge.

Without a mesh (unit tests) every function falls back to dense ops.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.common import dense_init


def init_table(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return dense_init(key, (vocab, d), 1, dtype=dtype)


# ----------------------------------------------------------------- embed
def embed(table: jax.Array, ids: jax.Array, par) -> jax.Array:
    """table: (V, D) model-sharded on V; ids: (B, S) -> (B, S, D)."""
    if not (par is not None and par.active):
        return table[ids]
    mesh = par.mesh
    ma = par.model_axis
    v_loc = table.shape[0] // par.n_model

    def local(tab, ids_):
        off = jax.lax.axis_index(ma) * v_loc
        lid = ids_ - off
        ok = (lid >= 0) & (lid < v_loc)
        emb = tab[jnp.clip(lid, 0, v_loc - 1)]
        emb = jnp.where(ok[..., None], emb, 0)
        return jax.lax.psum(emb, ma)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(ma, None), P(par.batch(), None)),
                   out_specs=P(par.batch(), None, None),
                   check_vma=False)
    return fn(table, ids)


# ------------------------------------------------------------------ loss
def softmax_xent(head: jax.Array, h: jax.Array, labels: jax.Array, par,
                 chunk: int = 2048) -> jax.Array:
    """Mean CE of h @ head.T vs labels, seq-chunked, vocab-shard-aware.

    h: (B, S, D); labels: (B, S) with -1 = ignore.  Returns scalar f32.
    """
    b, s, d = h.shape
    c = min(chunk, s)
    nc = s // c
    assert s % c == 0, (s, c)

    def chunk_loss(hc, lc):
        """hc: (B, C, D); lc: (B, C) -> (sum_loss, count)."""
        if par is not None and par.active:
            mesh, ma = par.mesh, par.model_axis
            v_loc = head.shape[0] // par.n_model

            def local(hd_, hc_, lc_):
                off = jax.lax.axis_index(ma) * v_loc
                logits = jnp.einsum("bcd,vd->bcv", hc_.astype(jnp.float32),
                                    hd_.astype(jnp.float32))
                # Global max via all_gather (differentiable, unlike
                # pmax) + stop_gradient: the max shift cancels
                # analytically in d(logsumexp).
                m_loc = jnp.max(logits, axis=-1)
                m = jax.lax.stop_gradient(jnp.max(
                    jax.lax.all_gather(m_loc, ma), axis=0))
                se = jax.lax.psum(
                    jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), ma)
                lid = lc_ - off
                ok = (lid >= 0) & (lid < v_loc)
                lab = jnp.take_along_axis(
                    logits, jnp.clip(lid, 0, v_loc - 1)[..., None],
                    axis=-1)[..., 0]
                lab = jax.lax.psum(jnp.where(ok, lab, 0.0), ma)
                return jnp.log(se) + m - lab                   # (B, C)

            fn = shard_map(
                local, mesh=mesh,
                in_specs=(P(ma, None), P(par.batch(), None, None),
                          P(par.batch(), None)),
                out_specs=P(par.batch(), None),
                check_vma=False)
            nll = fn(head, hc, lc)
        else:
            logits = jnp.einsum("bcd,vd->bcv", hc.astype(jnp.float32),
                                head.astype(jnp.float32))
            nll = (jax.nn.logsumexp(logits, axis=-1)
                   - jnp.take_along_axis(
                       logits, jnp.clip(lc, 0, None)[..., None],
                       axis=-1)[..., 0])
        valid = lc >= 0
        return (jnp.sum(jnp.where(valid, nll, 0.0)),
                jnp.sum(valid.astype(jnp.float32)))

    chunk_loss = jax.checkpoint(chunk_loss)  # recompute logits in bwd

    def body(acc, inp):
        hc, lc = inp
        sl, cnt = chunk_loss(hc, lc)
        return (acc[0] + sl, acc[1] + cnt), None

    hs = h.reshape(b, nc, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, nc, c).swapaxes(0, 1)
    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                     (hs, ls))
    return total / jnp.maximum(count, 1.0)


# --------------------------------------------------------------- decode
def greedy_sample(head: jax.Array, h_last: jax.Array, par) -> jax.Array:
    """argmax_v (h_last @ head.T).  h_last: (B, D) -> (B,) int32."""
    if not (par is not None and par.active):
        logits = h_last.astype(jnp.float32) @ head.astype(jnp.float32).T
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    mesh, ma = par.mesh, par.model_axis
    v_loc = head.shape[0] // par.n_model

    def local(hd_, hl_):
        off = jax.lax.axis_index(ma) * v_loc
        logits = hl_.astype(jnp.float32) @ hd_.astype(jnp.float32).T
        loc_max = jnp.max(logits, axis=-1)
        loc_arg = jnp.argmax(logits, axis=-1).astype(jnp.int32) + off
        g_max = jax.lax.pmax(loc_max, ma)
        cand = jnp.where(loc_max >= g_max, loc_arg, jnp.int32(2**30))
        return jax.lax.pmin(cand, ma)

    bspec = par.batch()
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(ma, None), P(bspec, None)),
                   out_specs=P(bspec),
                   check_vma=False)
    return fn(head, h_last)
