"""Mesh-sharded Hybrid LSH index (beyond the paper: multi-pod scale).

The database is row-sharded over the mesh's ``data`` axis.  Each shard
builds *local* CSR tables over its rows with globally-unique ids.  At
query time (queries replicated), every shard wraps its tables in the
engine's ``TableSegment`` and the collectives merge the per-shard
``SegmentEstimate`` terms:

  * global #collisions      = psum of local live collisions
  * global candSize         = HLL estimate of pmax-merged registers —
    HLL mergeability, which the paper uses across L tables, extends
    verbatim across shards: one (Q, m) pmax is the whole estimate.
  * routing policies:
      - "global":    one decision from the global Eq.(1)/(2) costs
      - "per_shard": each shard compares ITS local costs and picks its
        own strategy.  Correct because r-NN reporting is a union over
        disjoint shards; strictly better under local density skew (the
        shard holding a dense cluster scans linearly while others use
        LSH).  This is our main distributed extension of Algorithm 2.

Estimate math and both search strategies come from ``core.engine``
(``finalize_route`` / ``TableSegment.search``); only the collectives
and the per-shard ``lax.cond`` routing live here.  All collectives are
jax.lax primitives inside shard_map; the same code lowers for the
512-chip production mesh (see launch/dryrun.py).  The streaming
(sharded dynamic) variant lives in ``streaming.sharded``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.cost_model import CostModel
from repro.core.engine import (SegmentEstimate, TableSegment,
                               compact_results, finalize_route)
from repro.core.lsh.tables import LSHTables, build_tables
from repro.core import hll as hll_lib

__all__ = ["ShardedIndexState", "build_sharded", "make_query_fn"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedIndexState:
    """Sharded leaves; first axis of every leaf is the shard axis."""

    x: jax.Array           # (n, d)          rows sharded over 'data'
    perm: jax.Array        # (S, L, n/S)     sharded over dim 0
    starts: jax.Array      # (S, L, B+1)
    registers: jax.Array   # (S, L, B, m)

    def tree_flatten(self):
        return (self.x, self.perm, self.starts, self.registers), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def local_tables(self) -> LSHTables:
        """Inside shard_map: leaves arrive with S == 1."""
        return LSHTables(self.perm[0], self.starts[0], self.registers[0])


def build_sharded(family, params, x: jax.Array, *, num_buckets: int, m: int,
                  mesh: Mesh, data_axis: str = "data") -> ShardedIndexState:
    """Build per-shard tables; ids are globally unique (offset + local)."""
    n = x.shape[0]
    shards = mesh.shape[data_axis]
    assert n % shards == 0, (n, shards)
    n_local = n // shards

    def _build(x_local):
        shard_id = jax.lax.axis_index(data_axis)
        # HLLs must hash GLOBAL ids (cross-shard distinct-union
        # semantics); the CSR perm stores LOCAL row indices so the
        # search path can gather local rows — reporting re-offsets.
        ids = shard_id * n_local + jnp.arange(n_local, dtype=jnp.int32)
        bids = family.bucket_ids(params, x_local, num_buckets)
        t = build_tables(ids, bids, num_buckets, m)
        perm_local = t.perm - shard_id * n_local
        return (perm_local[None], t.starts[None], t.registers[None])

    spec_x = P(data_axis)
    fn = shard_map(_build, mesh=mesh, in_specs=(spec_x,),
                   out_specs=(P(data_axis), P(data_axis), P(data_axis)),
                   check_vma=False)
    x = jax.device_put(x, NamedSharding(mesh, P(data_axis)))
    perm, starts, registers = jax.jit(fn)(x)
    return ShardedIndexState(x=x, perm=perm, starts=starts,
                             registers=registers)


def make_query_fn(family, *, num_buckets: int, mesh: Mesh, n_total: int,
                  cost_model: CostModel, metric: str, cap: int, max_out: int,
                  policy: str = "per_shard", data_axis: str = "data"):
    """Build the jitted distributed hybrid query function.

    Returns fn(state, params, queries, r) ->
      dict(ids (S, Q, max_out), dists, mask, collisions (Q,),
           cand_est (Q,), used_lsh (S,)).
    Queries are replicated; outputs stay sharded over the data axis
    (union of per-shard reports).
    """
    shards = mesh.shape[data_axis]
    n_local = n_total // shards

    def _query(state_leaves, params, queries, r):
        x_local, perm, starts, registers = state_leaves
        tables = LSHTables(perm[0], starts[0], registers[0])
        qb = family.bucket_ids(params, queries, num_buckets)   # (Q, L)
        seg = TableSegment(tables=tables, x=x_local, metric=metric,
                           cap=cap, q_chunk=queries.shape[0],
                           n_live=n_local, n_scan=n_local)
        est = seg.estimate_terms(qb)            # collisions + (Q, L, m) regs
        merged_local = hll_lib.merge_registers(
            est.registers.astype(jnp.int32), axis=1)           # (Q, m)
        local = dataclasses.replace(est, registers=None,
                                    merged_registers=merged_local)

        merged = SegmentEstimate(
            collisions=jax.lax.psum(est.collisions, data_axis),
            merged_registers=jax.lax.pmax(merged_local, data_axis),
            n_live=n_total, n_scan=n_total,
            over_cap=jax.lax.pmax(est.over_cap.astype(jnp.int32),
                                  data_axis) > 0)
        route_g = finalize_route([merged], cost_model)
        route_l = finalize_route([local], cost_model)

        route = route_g if policy == "global" else route_l
        lsh_cost = jnp.sum(route.lsh_cost)
        lin_cost = route.linear_cost * queries.shape[0]
        # scalar/shard; linear if any row is over the cap
        use_lsh = (lsh_cost < lin_cost) & ~jnp.any(route.over_cap)

        def branch(lsh_route):
            def fn(_):
                ids, dists, mask = seg.search(qb, queries, r,
                                              lsh_route=lsh_route)
                ids, dists, valid = compact_results(ids, dists, mask,
                                                    max_out)
                shard_id = jax.lax.axis_index(data_axis)
                return ids + shard_id * n_local, dists, valid
            return fn

        ids, dists, mask = jax.lax.cond(use_lsh, branch(True), branch(False),
                                        operand=None)
        return (ids[None], dists[None], mask[None], route_g.collisions,
                route_g.cand_est, use_lsh[None])

    rep = P()
    sharded = P(data_axis)
    fn = shard_map(
        _query, mesh=mesh,
        in_specs=((sharded, sharded, sharded, sharded), rep, rep, rep),
        out_specs=(sharded, sharded, sharded, rep, rep, sharded),
        check_vma=False)

    @jax.jit
    def query(state, params, queries, r):
        ids, dists, mask, coll, cand, used = fn(
            (state.x, state.perm, state.starts, state.registers),
            params, queries, r)
        return {"ids": ids, "dists": dists, "mask": mask,
                "collisions": coll, "cand_est": cand, "used_lsh": used}

    def query_wrapper(state: ShardedIndexState, params, queries, r):
        return query(state, params, queries, jnp.float32(r))

    return query_wrapper
