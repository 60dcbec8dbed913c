"""Mixture-of-Experts MLP with sort-based (gather/scatter) dispatch.

No (tokens, experts, capacity) one-hot tensors — at llama4 scale that
would be ~5e9 elements.  Instead: top-k routing -> argsort by expert ->
position-in-expert via running counts -> capacity clamp -> scatter into
an (E, C, D) buffer -> stacked-expert einsum -> unsort + weighted
combine.  Expert weights are sharded over the 'model' axis (expert
parallelism); the scatter/gather around the expert einsum induces XLA
all-to-alls between the token (data) and expert (model) shardings.

Tokens routed beyond capacity are dropped (standard capacity-factor
semantics); the router softmax keeps their probability mass out of the
combine, so the layer degrades gracefully.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import dense_init


def init_moe(key, d_model: int, d_ff: int, num_experts: int,
             dtype=jnp.bfloat16):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, (d_model, num_experts), 0,
                             dtype=jnp.float32),
        "wi": dense_init(k1, (num_experts, d_model, d_ff), 1, dtype=dtype),
        "wg": dense_init(k2, (num_experts, d_model, d_ff), 1, dtype=dtype),
        "wo": dense_init(k3, (num_experts, d_ff, d_model), 1, dtype=dtype),
    }


def moe_specs(par, stacked: bool = True):
    st = (None,) if stacked else ()
    ma = par.model_axis if par.active else None
    fa = par.fsdp_axis()
    return {"router": st + (None, None),
            "wi": st + (ma, fa, None),
            "wg": st + (ma, fa, None),
            "wo": st + (ma, fa, None)}


def moe_apply(params, x: jax.Array, *, top_k: int, capacity_factor: float,
              act: str = "silu", par=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    if par is not None and par.active and par.moe_local_dispatch \
            and x.shape[0] * x.shape[1] >= par.axis_size(par.batch_axes_):
        return _moe_apply_local(params, x, top_k=top_k,
                                capacity_factor=capacity_factor, act=act,
                                par=par)
    b, s, d = x.shape
    e = params["router"].shape[1]
    t = b * s
    xt = x.reshape(t, d)

    logits = xt.astype(jnp.float32) @ params["router"]       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, top_k)               # (T, K)

    # Load-balancing aux loss (Switch-style): E * sum_e f_e * p_e.
    density = jnp.mean(jax.nn.one_hot(expert[:, 0], e, dtype=jnp.float32),
                       axis=0)
    aux = e * jnp.sum(density * jnp.mean(probs, axis=0))

    flat_expert = expert.reshape(-1)                         # (T*K,)
    flat_gate = gate.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)

    capacity = int(capacity_factor * t * top_k / e) or 1
    order = jnp.argsort(flat_expert)                         # stable
    se, sg, stok = (flat_expert[order], flat_gate[order], flat_tok[order])
    # Position within expert group: index - start offset of that expert.
    counts = jnp.bincount(se, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * top_k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < capacity

    buf = jnp.zeros((e, capacity, d), x.dtype)
    buf = buf.at[jnp.where(keep, se, 0),
                 jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[stok], 0).astype(x.dtype))
    if par is not None and par.active:
        # Expert-parallel layout: all-to-all from token(data)- to
        # expert(model)-sharding happens at this boundary.
        buf = par.shard(buf, par.model_axis, None, None)

    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
    if act == "silu":
        h = jax.nn.silu(g) * h
    else:
        h = jnp.square(jax.nn.relu(g)) * h
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"])          # (E, C, D)

    expert_out = y[jnp.where(keep, se, 0), jnp.where(keep, pos, 0)]
    expert_out = jnp.where(keep[:, None], expert_out, 0)
    contrib = expert_out.astype(jnp.float32) * sg[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[stok].add(contrib)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_apply_local(params, x: jax.Array, *, top_k: int,
                     capacity_factor: float, act: str, par):
    """Per-data-shard dispatch (§Perf iteration: kill the global sort).

    The baseline path argsorts the GLOBAL (tokens x top_k) assignment
    array, which XLA partitions into a distributed sort — enormous
    collective traffic at 1M+ tokens.  Here each data shard sorts only
    its local tokens inside a shard_map (zero collectives), and the
    only cross-device movement left is the intended expert-parallel
    all-to-all of the (E, C, D) dispatch buffers.
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = params["router"].shape[1]
    mesh = par.mesh
    taxes = par.batch_axes_ or None
    n_shards = par.axis_size(par.batch_axes_)
    x = par.shard(x, par.batch(), None, None)
    xt = x.reshape(b * s, d)
    t_loc = (b * s) // n_shards
    cap = max(1, int(capacity_factor * t_loc * top_k / e))
    router = params["router"]

    def dispatch(xt_loc, router_):
        logits = xt_loc.astype(jnp.float32) @ router_
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert = jax.lax.top_k(probs, top_k)
        density = jnp.mean(jax.nn.one_hot(expert[:, 0], e,
                                          dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(density * jnp.mean(probs, axis=0))
        if taxes:
            aux = jax.lax.pmean(aux, taxes)
        fe = expert.reshape(-1)
        fg = gate.reshape(-1)
        ft = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), top_k)
        order = jnp.argsort(fe)
        se, sg, st = fe[order], fg[order], ft[order]
        counts = jnp.bincount(se, length=e)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = (jnp.arange(t_loc * top_k, dtype=jnp.int32)
               - starts[se].astype(jnp.int32))
        keep = pos < cap
        buf = jnp.zeros((e, cap, d), x.dtype)
        buf = buf.at[jnp.where(keep, se, 0),
                     jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], xt_loc[st], 0).astype(x.dtype))
        return buf, se, sg, st, pos, keep, aux

    dis = shard_map(
        dispatch, mesh=mesh,
        in_specs=(P(taxes, None), P(None, None)),
        out_specs=(P(None, taxes, None), P(taxes), P(taxes), P(taxes),
                   P(taxes), P(taxes), P()),
        check_vma=False)
    buf, se, sg, st, pos, keep, aux = dis(xt, router)

    # Expert-parallel einsum: the only collective is the E<->C all-to-all.
    buf = par.shard(buf, par.model_axis, None, None)
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
    if act == "silu":
        h = jax.nn.silu(g) * h
    else:
        h = jnp.square(jax.nn.relu(g)) * h
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"])
    y = par.shard(y, None, par.batch(), None)

    def combine(y_loc, se_, sg_, st_, pos_, keep_):
        out_ = y_loc[jnp.where(keep_, se_, 0), jnp.where(keep_, pos_, 0)]
        out_ = jnp.where(keep_[:, None], out_, 0)
        contrib = out_.astype(jnp.float32) * sg_[:, None]
        return jnp.zeros((t_loc, d), jnp.float32).at[st_].add(contrib)

    comb = shard_map(
        combine, mesh=mesh,
        in_specs=(P(None, taxes, None), P(taxes), P(taxes), P(taxes),
                  P(taxes), P(taxes)),
        out_specs=P(taxes, None),
        check_vma=False)
    out = comb(y, se, sg, st, pos, keep)
    return out.reshape(b, s, d).astype(x.dtype), aux
