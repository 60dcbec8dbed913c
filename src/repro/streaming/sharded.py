"""ShardedDynamicHybridIndex — the streaming index over the mesh.

Every shard of the ``data`` axis owns a full level-stack worth of
segment state:

  * levels — a list of frozen segments shared *structurally* across
             shards: every shard holds its own rows for level entry k,
             padded to one common ``n_pad`` so the whole level is a
             stack of sharded leaves.  Pad rows are hashed to bucket
             ``B`` (one past the bucket space), which the CSR
             ``segment_sum`` and the HLL ``segment_max`` drop exactly:
             padding costs capacity, never correctness.  HLLs are keyed
             on per-level globally-unique internal ids
             (shard * n_pad + row), so a ``pmax`` of merged registers
             per level is the exact distinct-union sketch across
             shards; levels are disjoint document sets, so their
             estimates sum — the engine's N-segment combination.
  * tomb   — per-(shard, level) live bitmap + per-(table, bucket) dead
             counts (the engine's tombstone correction terms).
  * delta  — per-shard fixed-capacity delta segment; inserts/deletes
             are the same fused ``.at[]`` scatters as the single-host
             index, applied under ``shard_map``.

When the deltas fill, every shard's live delta rows freeze in place
into one new level-0 entry (no cross-shard movement, no rehash — the
delta carries its hashes).  A tiered ``CompactionPolicy`` merges a
level's entries into the next level; merges are staged in bounded
``compact_step(budget_rows)`` increments (host gather of at most
``budget_rows`` rows per step across shards) and the merged level
swaps in atomically — queries keep being served from the old level
list until then.

A merge is also the one point rows *move between shards*: the staged
survivors are host-side anyway, so at swap time a pluggable
``PlacementPolicy`` (``keep_local`` / ``round_robin`` /
``load_balance``; see ``streaming.compaction``) assigns each surviving
row a target shard, the staging buffers are re-partitioned
accordingly, and ``_make_level`` rewrites the ``_loc`` entry of every
placed row.  The mid-merge delete re-check runs *before* placement, so
a row deleted while staged is dropped, never moved.  Rebalancing is
what keeps a skewed insert stream (e.g. ``insert(..., shard=0)``) from
pinning one shard's row count — and with it the common per-level
``n_pad`` every shard pays for — permanently high.

Queries run one ``shard_map`` per level structure: each shard builds
its engine segments (one ``TableSegment`` per level + ``DeltaView``),
merges ``SegmentEstimate`` terms across shards (``psum`` exact terms,
``pmax`` registers, per level), finalizes global and local routes via
the shared ``finalize_route``, and picks a strategy per the routing
policy (``"global"`` or the density-adaptive ``"per_shard"``).
Reported ids are external; after any churn — including mid-merge —
the reported sets match a fresh single-host build on the surviving
corpus per route.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.cost_model import CostModel
from repro.core.engine import (QueryEngine, SegmentEstimate, TableSegment,
                               _pad_size, compact_results, finalize_route)
from repro.core.lsh.tables import LSHTables, build_tables
from repro.core import hll as hll_lib
from repro.checkpoint.manager import array_digest
from repro.obs import Observability
from repro.obs.metrics import WorkPhases, time_block
from repro.streaming import delta as delta_lib
from repro.streaming import tombstones as tomb_lib
from repro.streaming.compaction import (CompactionPolicy, CompactionStats,
                                        PlacementPolicy,
                                        make_placement_policy)

__all__ = ["ShardedDynamicHybridIndex", "ShardedQueryResult"]

_LEAVES = ("x", "ids", "bucket_ids", "perm", "starts", "registers",
           "live", "tomb_counts")


@dataclasses.dataclass
class ShardedQueryResult:
    """Union-over-shards reporting buffers + routing diagnostics."""

    ids: np.ndarray         # (S, Q, max_out) external doc ids
    dists: np.ndarray       # (S, Q, max_out)
    mask: np.ndarray        # (S, Q, max_out) reported r-near neighbors
    collisions: np.ndarray  # (Q,) global live collisions
    cand_est: np.ndarray    # (Q,) global corrected candSize estimate
    used_lsh: np.ndarray    # (S,) per-shard strategy decision
    n_queries: int

    def neighbors(self, i: int) -> np.ndarray:
        return self.ids[:, i][self.mask[:, i]]

    def reported(self, i: int):
        """(ids, dists) reported for query ``i``, flattened over shards."""
        m = self.mask[:, i]
        return self.ids[:, i][m], self.dists[:, i][m]

    def neighbor_sets(self):
        return {i: set(self.neighbors(i).tolist())
                for i in range(self.n_queries)}

    @property
    def frac_linear(self) -> float:
        return float((~self.used_lsh).mean())

    @property
    def n_linear(self) -> int:
        """Queries served by linear search, scaled by the shard vote.

        Sharded routing is per-(batch, shard), so the exact per-query
        count of the single-host index degenerates to the shard
        fraction here.
        """
        return round(self.n_queries * self.frac_linear)


@dataclasses.dataclass
class _ShardLevel:
    """One level entry: sharded leaves + host-side accounting."""

    uid: int
    level: int
    n_pad: int                      # per-shard padded rows
    leaves: Dict[str, jax.Array]    # _LEAVES, leading dim = shard axis
    rows_s: np.ndarray              # (S,) real rows (tombstoned included)
    live_s: np.ndarray              # (S,)
    # content addresses of the immutable leaves, cached lazily by
    # state_digests() — deletes rebind only live/tomb_counts, so these
    # stay valid for the level's lifetime
    digests: Optional[Dict[str, str]] = None

    @property
    def n_rows(self) -> int:
        return int(self.rows_s.sum())

    @property
    def n_live(self) -> int:
        return int(self.live_s.sum())


@dataclasses.dataclass
class _ShardMergeTask:
    """A scheduled levels merge with per-(uid, shard) staging state."""

    uids: List[int]
    target_level: int
    reason: str
    shards: int
    # staging chunks: (uid, shard, row indices), rows, ids, hashes
    src: List[Tuple[int, int, np.ndarray]] = dataclasses.field(
        default_factory=list)
    rows: List[np.ndarray] = dataclasses.field(default_factory=list)
    ids: List[np.ndarray] = dataclasses.field(default_factory=list)
    bids: List[np.ndarray] = dataclasses.field(default_factory=list)
    pair_idx: int = 0       # cursor over (uid, shard) pairs
    row_off: int = 0
    steps: int = 0
    work_seconds: float = 0.0   # sum of this task's compact_step durations
    # host copies of each input level's immutable leaves (x, ids,
    # bucket_ids), fetched once per merge: slicing a sharded device leaf
    # by shard is refused on an Explicit-axis mesh, and one fetch per
    # level is cheaper than one device gather per staging step anyway
    host: Dict[int, Tuple[np.ndarray, ...]] = dataclasses.field(
        default_factory=dict)

    @property
    def pairs(self) -> List[Tuple[int, int]]:
        return [(u, s) for u in self.uids for s in range(self.shards)]

    @property
    def staged_done(self) -> bool:
        return self.pair_idx >= len(self.uids) * self.shards


class ShardedDynamicHybridIndex:
    """Streaming Hybrid LSH index, row-sharded over a mesh axis."""

    def __init__(self, family, *, num_buckets: int, mesh: Mesh, m: int = 64,
                 cap: int = 64, delta_capacity: int = 1024,
                 cost_model: CostModel = CostModel(alpha=1.0, beta=10.0),
                 policy: CompactionPolicy = CompactionPolicy(),
                 placement: "str | PlacementPolicy" = "keep_local",
                 routing: str = "per_shard", max_out: int = 512,
                 data_axis: str = "data", key: jax.Array | int = 0,
                 impl: Optional[str] = None,
                 obs: Optional[Observability] = None,
                 engine: Optional[QueryEngine] = None):
        """Args:
          family: LSH family (``make_family``); owns metric + hashes.
          num_buckets: buckets per table B; rows hash into [0, B), pad
            rows to B (dropped exactly by the CSR/HLL segment reductions).
          mesh: jax mesh whose ``data_axis`` rows are sharded over.
          m: HLL registers per bucket.
          cap: LSH candidate verification cap per (query, table).
          delta_capacity: per-shard delta slots before a freeze.
          cost_model: Algorithm 2 cost constants (alpha, beta).
          policy: when to freeze/merge (``CompactionPolicy``).
          placement: merge-time row placement across shards —
            ``"keep_local"`` (default; rows never move),
            ``"round_robin"``, ``"load_balance"``, or any
            ``PlacementPolicy`` instance.
          routing: ``"global"`` (one strategy for the batch) or
            ``"per_shard"`` (each shard votes with its local estimate).
          max_out: reported neighbors per (shard, query).
          data_axis: mesh axis name to shard rows over.
          key: PRNG key (or int seed) for the family parameters.
          impl: kernel impl override (e.g. ``"pallas_interpret"``).
          obs: observability bundle — events + work phases only here;
            per-query tracing needs the host-side single-index path
            (routing runs inside ``shard_map`` on this index).
          engine: a shared ``QueryEngine`` (multi-tenant collections
            pass one); default builds a private one from
            ``cost_model``.
        """
        assert routing in ("global", "per_shard"), routing
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self.family = family
        self.params = family.init(key)
        self.num_buckets = int(num_buckets)
        self.m = int(m)
        self.cap = int(cap)
        self.delta_capacity = int(delta_capacity)
        self.cost_model = cost_model
        self.policy = policy
        self.placement = make_placement_policy(placement)
        self.routing = routing
        self.max_out = int(max_out)
        self.mesh = mesh
        self.data_axis = data_axis
        self.shards = int(mesh.shape[data_axis])
        self.impl = impl
        self._engine = engine if engine is not None else QueryEngine(
            cost_model, impl=impl)
        self._shard = NamedSharding(mesh, P(data_axis))
        self.stats = CompactionStats()
        self.obs = obs if obs is not None else Observability.disabled()
        self.phases = WorkPhases("stage", "build", "apply", "full")

        # Result-cache invalidation: monotonic mutation version, bumped
        # on every insert, delete, freeze, merge swap (rebalancing
        # included), full compaction, and restore.
        self._version = 0

        # device state; delta None until first use
        self._levels: List[_ShardLevel] = []
        self._delta = None    # dict: x, bucket_ids, ids, live, count
        self._tasks: List[_ShardMergeTask] = []
        self._next_uid = 0
        self._d = None        # row width
        self._dtype = None

        # host bookkeeping
        self._loc: Dict[int, tuple] = {}   # ext -> (shard, "m", uid, row)
        #                                         | (shard, "d", slot)
        self._next_id = 0
        S = self.shards
        self._delta_count_s = np.zeros(S, np.int64)
        self._delta_live_s = np.zeros(S, np.int64)
        self._inserts = 0
        self._deletes = 0
        self._fn_cache: Dict[tuple, object] = {}

    # ------------------------------------------------------------- sizes
    @property
    def n(self) -> int:
        return (sum(l.n_live for l in self._levels)
                + int(self._delta_live_s.sum()))

    @property
    def n_frozen_rows(self) -> int:
        return sum(l.n_rows for l in self._levels)

    @property
    def n_dead(self) -> int:
        return sum(l.n_rows - l.n_live for l in self._levels)

    @property
    def version(self) -> int:
        """Monotonic mutation version — the result-cache key component.

        Changes whenever a query could report differently: insert,
        delete, freeze, merge swap (placement moves included), full
        compaction, restore.
        """
        return self._version

    def _next_uid_(self) -> int:
        u = self._next_uid
        self._next_uid += 1
        return u

    # ------------------------------------------------------------- build
    def build(self, x: jax.Array,
              ids: Optional[Sequence[int]] = None
              ) -> "ShardedDynamicHybridIndex":
        """Initial batch build; returns self.

        Args: ``x`` (n, d) corpus rows, dealt round-robin over shards;
        ``ids`` optional (n,) unique external ids (default 0..n-1).
        Replaces any existing state.
        """
        x = np.asarray(x)
        n = x.shape[0]
        if ids is None:
            ids = np.arange(n, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
            assert len(set(ids.tolist())) == len(ids), "duplicate ids"
        self._d, self._dtype = int(x.shape[1]), x.dtype
        S = self.shards
        self._levels = []
        self._tasks = []
        self._loc = {}
        self._version += 1
        if n:
            parts = [(x[s::S], ids[s::S]) for s in range(S)]
            self._make_level(parts, self.policy.level_for(
                n, self.delta_capacity))
        self._reset_delta()
        self._next_id = int(ids.max()) + 1 if n else 0
        return self

    def _make_level(self, parts: List[tuple], level: int) -> _ShardLevel:
        """Per-shard (rows, ext_ids[, bucket_rows]) -> one padded level.

        With ``bucket_rows`` supplied (freezes and merges) the fused
        build skips re-hashing and runs straight from the staged hashes.
        """
        S, L, B = self.shards, self.family.L, self.num_buckets
        ks = [int(p[0].shape[0]) for p in parts]
        n_pad = _pad_size(max(max(ks), 1))
        xs = np.zeros((S, n_pad, self._d), self._dtype)
        ext = np.full((S, n_pad), -1, np.int32)
        valid = np.zeros((S, n_pad), bool)
        with_bids = len(parts[0]) == 3
        bids_p = np.full((S, n_pad, L), B, np.int32) if with_bids else None
        for s, p in enumerate(parts):
            k = ks[s]
            xs[s, :k] = p[0]
            ext[s, :k] = p[1]
            valid[s, :k] = True
            if with_bids and k:
                bids_p[s, :k] = p[2]
        put = lambda a: jax.device_put(jnp.asarray(a), self._shard)
        if with_bids:
            bids = put(bids_p)
            perm, starts, regs = self._build_from_bids_fn(n_pad)(
                bids, put(valid))
        else:
            bids, perm, starts, regs = self._build_fn(n_pad)(
                put(xs), put(valid), self.params)
        live = np.concatenate([valid, np.zeros((S, 1), bool)], axis=1)
        lvl = _ShardLevel(
            uid=self._next_uid_(), level=int(level), n_pad=n_pad,
            leaves={"x": put(xs), "ids": put(ext), "bucket_ids": bids,
                    "perm": perm, "starts": starts, "registers": regs,
                    "live": put(live),
                    "tomb_counts": put(np.zeros((S, L, B), np.int32))},
            rows_s=np.asarray(ks, np.int64),
            live_s=np.asarray(ks, np.int64))
        self._levels.append(lvl)
        self._version += 1
        for s, p in enumerate(parts):
            for i, e in enumerate(np.asarray(p[1]).tolist()):
                self._loc[int(e)] = (s, "m", lvl.uid, i)
        self._evict_stale_query_fns()
        return lvl

    def _evict_stale_query_fns(self) -> None:
        """Drop query fns compiled for level structures that no longer
        exist.  The query fn is specialized per tuple of level pad
        sizes; under streaming that tuple changes on every freeze/merge,
        so without eviction a long-running index accumulates one
        compiled executable per structure ever seen."""
        cur = tuple(l.n_pad for l in self._levels)
        self._fn_cache = {k: v for k, v in self._fn_cache.items()
                          if k[0] != "query" or k[1] == cur}

    def _build_fn(self, n_pad: int):
        """shard_map'd Algorithm 1 fusion over one padded row block."""
        key = ("build", n_pad)
        if key in self._fn_cache:
            return self._fn_cache[key]
        family, B, m = self.family, self.num_buckets, self.m
        axis = self.data_axis

        def _build(x, valid, params):
            x, valid = x[0], valid[0]
            shard = jax.lax.axis_index(axis)
            bids = family.bucket_ids(params, x, B).astype(jnp.int32)
            # pad rows hash to bucket B: dropped by the CSR segment_sum
            # and the HLL segment_max — invisible to every estimate.
            bids = jnp.where(valid[:, None], bids, B)
            gids = shard * n_pad + jnp.arange(n_pad, dtype=jnp.int32)
            t = build_tables(gids, bids, B, m)
            perm = t.perm - shard * n_pad
            return (bids[None], perm[None], t.starts[None],
                    t.registers[None])

        sh = P(axis)
        fn = jax.jit(shard_map(
            _build, mesh=self.mesh, in_specs=(sh, sh, P()),
            out_specs=(sh, sh, sh, sh), check_vma=False))
        self._fn_cache[key] = fn
        return fn

    def _build_from_bids_fn(self, n_pad: int):
        """Same fusion, from staged hashes (freeze/merge path)."""
        key = ("build_bids", n_pad)
        if key in self._fn_cache:
            return self._fn_cache[key]
        B, m = self.num_buckets, self.m
        axis = self.data_axis

        def _build(bids, valid):
            bids, valid = bids[0], valid[0]
            shard = jax.lax.axis_index(axis)
            bids = jnp.where(valid[:, None], bids.astype(jnp.int32), B)
            gids = shard * n_pad + jnp.arange(n_pad, dtype=jnp.int32)
            t = build_tables(gids, bids, B, m)
            perm = t.perm - shard * n_pad
            return perm[None], t.starts[None], t.registers[None]

        sh = P(axis)
        fn = jax.jit(shard_map(
            _build, mesh=self.mesh, in_specs=(sh, sh),
            out_specs=(sh, sh, sh), check_vma=False))
        self._fn_cache[key] = fn
        return fn

    def _reset_delta(self) -> None:
        S, C, L = self.shards, self.delta_capacity, self.family.L
        put = lambda a: jax.device_put(jnp.asarray(a), self._shard)
        self._delta = {
            "x": put(np.zeros((S, C + 1, self._d), self._dtype)),
            "bucket_ids": put(np.full((S, C + 1, L), -1, np.int32)),
            "ids": put(np.full((S, C + 1), -1, np.int32)),
            "live": put(np.zeros((S, C + 1), bool)),
            "count": put(np.zeros((S,), np.int32))}
        self._delta_count_s[:] = 0
        self._delta_live_s[:] = 0

    def _ensure_init(self, rows: np.ndarray) -> None:
        """First contact without build(): no levels, delta-only shards."""
        if self._delta is not None:
            return
        self._d, self._dtype = int(rows.shape[1]), rows.dtype
        self._levels = []
        self._reset_delta()

    # ------------------------------------------------------------ insert
    def insert(self, rows: jax.Array, ids: Optional[Sequence[int]] = None,
               shard: Optional[int] = None) -> np.ndarray:
        """Append documents to the shard deltas; returns external ids.

        Args:
          rows: (k, d) new document rows.
          ids: optional (k,) external ids (must be unused); default
            continues from the running counter.
          shard: pin the whole batch to one shard's delta (models
            key-hash placement; how skewed streams arise).  Default
            None water-fills the least-loaded deltas.

        Splits the batch by remaining per-shard delta capacity, freezing
        every shard's delta into a new level-0 entry when the target
        shard(s) fill.  A pinned skewed stream piles rows onto one
        shard; merge-time rebalancing (``placement``) is what spreads
        them back out.
        """
        rows = np.asarray(rows)
        if rows.shape[0] == 0:
            return np.zeros((0,), np.int64)
        if shard is not None and not 0 <= int(shard) < self.shards:
            raise ValueError(f"shard {shard} not in [0, {self.shards})")
        self._ensure_init(rows)
        if ids is None:
            ids = np.arange(self._next_id, self._next_id + rows.shape[0],
                            dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
            if len(set(ids.tolist())) != len(ids):
                raise KeyError("duplicate ids within insert batch")
        for e in ids.tolist():
            if e in self._loc:
                raise KeyError(f"id {e} already indexed")
        lo = 0
        while lo < rows.shape[0]:
            free = self.delta_capacity - self._delta_count_s
            if shard is not None:
                # pinned: only the target shard's capacity counts
                pin = np.zeros_like(free)
                pin[int(shard)] = free[int(shard)]
                free = pin
            if free.sum() == 0:
                self._freeze("delta_full")
                continue
            take = int(min(free.sum(), rows.shape[0] - lo))
            # round-robin water-fill over shards with free slots
            order = np.argsort(self._delta_count_s, kind="stable")
            assign: List[List[int]] = [[] for _ in range(self.shards)]
            left, cursor = take, 0
            free = free.copy()
            while left:
                s = (int(shard) if shard is not None
                     else int(order[cursor % self.shards]))
                cursor += 1
                if free[s] > len(assign[s]):
                    assign[s].append(lo + take - left)
                    left -= 1
            self._insert_chunk(rows, ids, assign)
            lo += take
        self._next_id = max(self._next_id, int(ids.max()) + 1)
        self._maybe_compact()
        return ids

    def _insert_chunk(self, rows: np.ndarray, ids: np.ndarray,
                      assign: List[List[int]]) -> None:
        S = self.shards
        pk = _pad_size(max(max(len(a) for a in assign), 1))
        rows_p = np.zeros((S, pk, self._d), self._dtype)
        ids_p = np.zeros((S, pk), np.int32)
        valid = np.zeros((S, pk), bool)
        for s, idxs in enumerate(assign):
            k = len(idxs)
            rows_p[s, :k] = rows[idxs]
            ids_p[s, :k] = ids[idxs]
            valid[s, :k] = True
            base = int(self._delta_count_s[s])
            for i, j in enumerate(idxs):
                self._loc[int(ids[j])] = (s, "d", base + i)
            self._delta_count_s[s] += k
            self._delta_live_s[s] += k
            self._inserts += k
        d = self._delta
        out = self._insert_fn(pk)(
            (d["x"], d["bucket_ids"], d["ids"], d["live"], d["count"]),
            self.params, rows_p, ids_p, valid)
        self._delta = dict(zip(("x", "bucket_ids", "ids", "live", "count"),
                               out))
        self._version += 1

    def _insert_fn(self, pk: int):
        key = ("insert", pk)
        if key in self._fn_cache:
            return self._fn_cache[key]
        family, B = self.family, self.num_buckets
        axis = self.data_axis

        def _ins(leaves, params, rows, ext, valid):
            delta = delta_lib.DeltaSegment(*(l[0] for l in leaves))
            bids = family.bucket_ids(params, rows[0], B)
            nd = delta_lib.insert(delta, rows[0], bids, ext[0], valid[0])
            return (nd.x[None], nd.bucket_ids[None], nd.ids[None],
                    nd.live[None], nd.count[None])

        sh = P(axis)
        fn = jax.jit(shard_map(
            _ins, mesh=self.mesh,
            in_specs=((sh,) * 5, P(), sh, sh, sh),
            out_specs=(sh,) * 5, check_vma=False))
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------ delete
    def delete(self, ids: Iterable[int], strict: bool = False) -> int:
        """Tombstone documents by external id; returns #removed.

        Unknown (or already-deleted) ids are skipped unless ``strict``
        (KeyError).  Deletes mark per-(shard, level) live bitmaps and
        bump per-bucket dead counts; tables are never mutated, and a
        row staged in a pending merge is dropped at swap time.
        """
        S = self.shards
        by_uid: Dict[int, List[List[int]]] = {}
        delta_slots: List[List[int]] = [[] for _ in range(S)]
        for e in ids:
            loc = self._loc.pop(int(e), None)
            if loc is None:
                if strict:
                    raise KeyError(e)
                continue
            s, kind = loc[0], loc[1]
            if kind == "d":
                delta_slots[s].append(loc[2])
            else:
                by_uid.setdefault(loc[2],
                                  [[] for _ in range(S)])[s].append(loc[3])
        removed = 0
        for uid, main_rows in by_uid.items():
            lvl = self._level_by_uid(uid)
            pk = _pad_size(max(max(len(a) for a in main_rows), 1))
            rows_p = np.zeros((S, pk), np.int32)
            valid = np.zeros((S, pk), bool)
            for s, rr in enumerate(main_rows):
                rows_p[s, :len(rr)] = rr
                valid[s, :len(rr)] = True
                lvl.live_s[s] -= len(rr)
                removed += len(rr)
            live, counts = self._delete_main_fn(pk)(
                (lvl.leaves["live"], lvl.leaves["tomb_counts"],
                 lvl.leaves["bucket_ids"]), rows_p, valid)
            lvl.leaves = {**lvl.leaves, "live": live, "tomb_counts": counts}
        if any(delta_slots):
            pk = _pad_size(max(max(len(a) for a in delta_slots), 1))
            slots_p = np.zeros((S, pk), np.int32)
            valid = np.zeros((S, pk), bool)
            for s, ss in enumerate(delta_slots):
                slots_p[s, :len(ss)] = ss
                valid[s, :len(ss)] = True
                self._delta_live_s[s] -= len(ss)
                removed += len(ss)
            dlive = self._delete_delta_fn(pk)(
                (self._delta["x"], self._delta["bucket_ids"],
                 self._delta["ids"], self._delta["live"],
                 self._delta["count"]), slots_p, valid)
            self._delta = {**self._delta, "live": dlive}
        self._deletes += removed
        if removed:
            self._version += 1
        self._maybe_compact()
        return removed

    def _level_by_uid(self, uid: int) -> _ShardLevel:
        for l in self._levels:
            if l.uid == uid:
                return l
        raise KeyError(uid)

    def _delete_main_fn(self, pk: int):
        key = ("del_main", pk)
        if key in self._fn_cache:
            return self._fn_cache[key]
        axis = self.data_axis

        def _del(leaves, rows, valid):
            live, counts, bids = (l[0] for l in leaves)
            ts = tomb_lib.Tombstones(live=live, counts=counts)
            row_buckets = bids[rows[0]]   # pad lanes: row 0, add-count 0
            nts = tomb_lib.mark_dead(ts, rows[0], row_buckets, valid[0])
            return nts.live[None], nts.counts[None]

        sh = P(axis)
        fn = jax.jit(shard_map(_del, mesh=self.mesh,
                               in_specs=((sh,) * 3, sh, sh),
                               out_specs=(sh, sh), check_vma=False))
        self._fn_cache[key] = fn
        return fn

    def _delete_delta_fn(self, pk: int):
        key = ("del_delta", pk)
        if key in self._fn_cache:
            return self._fn_cache[key]
        axis = self.data_axis

        def _del(leaves, slots, valid):
            delta = delta_lib.DeltaSegment(*(l[0] for l in leaves))
            return delta_lib.kill(delta, slots[0], valid[0]).live[None]

        sh = P(axis)
        fn = jax.jit(shard_map(_del, mesh=self.mesh,
                               in_specs=((sh,) * 5, sh, sh),
                               out_specs=sh, check_vma=False))
        self._fn_cache[key] = fn
        return fn

    # --------------------------------------------------------- compaction
    def _freeze(self, reason: str) -> None:
        """Seal every shard's live delta rows into one level-0 entry.

        Rows stay on their shard; the delta already carries its hashes,
        so the freeze is one fused from-hashes build over at most
        delta_capacity rows per shard.
        """
        if self._delta is None or self._delta_count_s.sum() == 0:
            return
        C = self.delta_capacity
        dx = np.asarray(self._delta["x"])[:, :C]
        dids = np.asarray(self._delta["ids"])[:, :C]
        dbids = np.asarray(self._delta["bucket_ids"])[:, :C]
        dlive = np.asarray(self._delta["live"])[:, :C]
        parts = []
        total = 0
        for s in range(self.shards):
            live = dlive[s]
            parts.append((dx[s][live], dids[s][live].astype(np.int64),
                          dbids[s][live]))
            total += int(live.sum())
        self._reset_delta()
        if total == 0:
            return
        self._make_level(parts, level=0)
        self.stats.record_freeze(total)
        self.obs.events.emit("freeze", rows=total, reason=reason)

    def _maybe_compact(self) -> None:
        if self._delta is not None:
            r = self.policy.freeze_reason(
                delta_count=int(self._delta_count_s.max()),
                delta_capacity=self.delta_capacity)
            if r:
                self._freeze(r)
        self._schedule_merges()
        if self.policy.step_rows is None:
            self._drain()

    def _pending_uids(self) -> set:
        return {u for t in self._tasks for u in t.uids}

    def _schedule_merges(self) -> None:
        if not self._levels:
            return
        pend = self._pending_uids()
        free = [l for l in self._levels if l.uid not in pend]
        counts: Dict[int, int] = {}
        for l in free:
            counts[l.level] = counts.get(l.level, 0) + 1
        for reason, src, target in self.policy.plan_merges(
                level_counts=counts, n_rows=self.n_frozen_rows,
                n_dead=self.n_dead,
                n_live=sum(l.n_live for l in self._levels),
                unit=self.delta_capacity, can_full=not pend):
            uids = [l.uid for l in free if src is None or l.level == src]
            if uids:
                self._tasks.append(_ShardMergeTask(
                    uids=uids, target_level=target,
                    reason=reason, shards=self.shards))
                self.obs.events.emit("merge_scheduled", uids=uids,
                                     target_level=target, reason=reason)

    @property
    def has_compaction_work(self) -> bool:
        return bool(self._tasks)

    @property
    def staged_ready(self) -> bool:
        """A fully-staged merge awaits a control-thread ``apply_staged``."""
        return bool(self._tasks) and self._tasks[0].staged_done

    @property
    def staged_rows(self) -> int:
        """Rows currently gathered into merge staging buffers."""
        return sum(sum(len(r) for r in t.rows) for t in self._tasks)

    @property
    def pending_merges(self) -> int:
        """Queued merge tasks (head may be partially staged)."""
        return len(self._tasks)

    def stage_step(self, budget_rows: Optional[int] = None) -> str:
        """Advance ONLY the staging half of the active merge.

        The worker-thread half of the ``CompactionDriver`` split: walks
        the head task's per-(segment, shard) staging cursors, gathering
        at most ``budget_rows`` live rows across shards into private
        host buffers.  The served level list is untouched, so this is
        safe concurrently with control-thread inserts/deletes/queries.
        Returns ``"idle"`` | ``"staging"`` | ``"ready"``; once
        ``"ready"``, only a control-thread ``apply_staged`` (the swap +
        placement + ``_loc`` rewrites) makes further progress.
        """
        if not self._tasks:
            return "idle"
        task = self._tasks[0]
        if task.staged_done:
            return "ready"
        budget = int(budget_rows or self.policy.step_rows
                     or max(self.delta_capacity, 1))
        task.steps += 1
        self.stats.record_step()
        with time_block(phases=self.phases, phase="stage") as tb:
            self._stage(task, budget)
        task.work_seconds += tb.elapsed
        return "ready" if task.staged_done else "staging"

    def prepare_staged(self) -> bool:
        """No-op on the sharded index (returns False).

        The single-host stack pre-builds a staged merge's output on the
        driver's worker (``DynamicHybridIndex.prepare_staged``); here
        the build cannot run early because the ``PlacementPolicy``
        partitions the staged rows using per-shard live loads *at swap
        time* — pre-building would bake in stale placement.  The swap
        (build included) therefore stays in ``apply_staged`` on the
        control thread; the staging gathers — the O(rows) churn-scaling
        half — still run on the worker.
        """
        return False

    def apply_staged(self) -> bool:
        """CONTROL-THREAD ONLY: swap a fully-staged merge in.

        Runs the mid-merge delete re-check, the ``PlacementPolicy``
        target assignment, the fused build of the new level, the atomic
        level-list swap with its ``_loc`` rewrites, and schedules
        cascaded merges.  Returns True when a merge was applied.
        """
        if not self._tasks or not self._tasks[0].staged_done:
            return False
        task = self._tasks[0]
        task.steps += 1
        self.stats.record_step()
        with time_block(phases=self.phases, phase="apply") as tb:
            total, dropped, moved = self._finalize_merge(task)
        task.work_seconds += tb.elapsed
        self.stats.record_merge(task.target_level, total, task.steps,
                                task.work_seconds, dropped,
                                reason=task.reason, moved=moved)
        self._emit_swap(task, total, dropped, moved)
        self._schedule_merges()       # cascade up the levels
        return True

    def compact_step(self, budget_rows: Optional[int] = None) -> bool:
        """Advance the active merge by one bounded step (gather + hash of
        at most ``budget_rows`` rows across shards, or — once staging is
        complete — the fused build + atomic level swap).  Returns True
        while more work remains."""
        if not self._tasks:
            return False
        budget = int(budget_rows or self.policy.step_rows
                     or max(self.delta_capacity, 1))
        task = self._tasks[0]
        task.steps += 1
        self.stats.record_step()
        if not task.staged_done:
            with time_block(phases=self.phases, phase="stage") as tb:
                self._stage(task, budget)
            task.work_seconds += tb.elapsed
            if not task.staged_done:
                return True
        with time_block(phases=self.phases, phase="apply") as tb:
            total, dropped, moved = self._finalize_merge(task)
        task.work_seconds += tb.elapsed
        self.stats.record_merge(task.target_level, total, task.steps,
                                task.work_seconds, dropped,
                                reason=task.reason, moved=moved)
        self._emit_swap(task, total, dropped, moved)
        self._schedule_merges()       # cascade up the levels
        return bool(self._tasks)

    def _emit_swap(self, task: "_ShardMergeTask", total: int, dropped: int,
                   moved: int) -> None:
        self.obs.events.emit("swap", target_level=task.target_level,
                             rows=total, dropped=dropped, steps=task.steps,
                             seconds=task.work_seconds, reason=task.reason)
        if moved:
            self.obs.events.emit("rebalance", rows_moved=moved,
                                 target_level=task.target_level,
                                 placement=self.placement.name)

    def _stage(self, task: _ShardMergeTask, budget: int) -> None:
        pairs = task.pairs
        left = max(budget, 1)
        live_h: Dict[int, np.ndarray] = {}   # this step's live bitmaps
        while left > 0 and not task.staged_done:
            uid, s = pairs[task.pair_idx]
            lvl = self._level_by_uid(uid)
            n_rows = int(lvl.rows_s[s])
            if task.row_off >= n_rows:
                task.pair_idx += 1
                task.row_off = 0
                continue
            hi = min(n_rows, task.row_off + left)
            idx = np.arange(task.row_off, hi)
            if uid not in live_h:
                live_h[uid] = np.asarray(lvl.leaves["live"])
            live = live_h[uid][s, task.row_off:hi]
            idx = idx[live]
            if len(idx):
                if uid not in task.host:
                    task.host[uid] = tuple(np.asarray(lvl.leaves[k]) for k
                                           in ("x", "ids", "bucket_ids"))
                hx, hids, hbids = task.host[uid]
                task.src.append((uid, s, idx))
                task.rows.append(hx[s, task.row_off:hi][live])
                task.ids.append(hids[s, task.row_off:hi][live])
                task.bids.append(hbids[s, task.row_off:hi][live])
            left -= hi - task.row_off
            task.row_off = hi

    def _finalize_merge(self, task: _ShardMergeTask) -> Tuple[int, int, int]:
        """Swap the staged merge in; returns (rows kept, dropped, moved).

        Order matters: (1) re-check every staged row against the
        *current* live bitmap — deletes that landed mid-merge must not
        resurrect; (2) hand the survivors (with their origin shards) to
        the placement policy; (3) re-partition the staging buffers by
        target shard and build the new level, whose ``_make_level``
        rewrites ``_loc`` for every row — moved rows included — before
        the old levels' entries are forgotten.
        """
        S = self.shards
        surv: List[tuple] = []   # (origin shard, rows, ids, bids)
        live_h = {u: np.asarray(self._level_by_uid(u).leaves["live"])
                  for u in task.uids}
        for (uid, s, idx), rows, ids, bids in zip(task.src, task.rows,
                                                  task.ids, task.bids):
            live = live_h[uid][s][idx]
            if live.any():
                surv.append((s, rows[live], ids[live].astype(np.int64),
                             bids[live]))
        total_in = sum(self._level_by_uid(u).n_rows for u in task.uids)
        self._tasks.pop(0)
        self._levels = [l for l in self._levels if l.uid not in task.uids]
        self._version += 1
        if not surv:
            self._evict_stale_query_fns()
            return 0, total_in, 0
        origins = np.concatenate(
            [np.full(len(c[2]), c[0], np.int64) for c in surv])
        xs = np.concatenate([c[1] for c in surv], axis=0)
        es = np.concatenate([c[2] for c in surv])
        bs = np.concatenate([c[3] for c in surv], axis=0)
        # base load: live rows per shard outside this merge (surviving
        # levels — other pending merges' inputs included, they keep
        # their shard until their own swap — plus the delta); the merged
        # levels are already dropped from _levels, so shard_loads() is
        # exactly this base
        base = self.shard_loads()
        targets = np.asarray(
            self.placement.assign(origins, base, S), np.int64)
        # hard-validate the public extension point: a buggy custom
        # policy must fail the merge loudly, not silently drop rows
        # whose _loc entries would then dangle
        if targets.shape != origins.shape or not (
                (0 <= targets) & (targets < S)).all():
            raise ValueError(
                f"placement policy {self.placement.name!r} returned bad "
                f"targets (shape {targets.shape}, expected "
                f"{origins.shape}, values must be in [0, {S}))")
        moved = int((targets != origins).sum())
        parts = []
        for s in range(S):
            sel = targets == s
            parts.append((xs[sel], es[sel], bs[sel]))
        self._make_level(parts, level=task.target_level)
        return len(es), total_in - len(es), moved

    def _drain(self) -> None:
        while self._tasks:
            self.compact_step(budget_rows=max(self.n_frozen_rows, 1))

    def compact(self, reason: str = "manual") -> None:
        """Blocking full compaction: fold every level + the delta into
        one level per shard (drops tombstones).  Pending merge staging
        is discarded, not drained — the fold re-gathers everything, so
        finishing a partial merge first would build a level the fold
        immediately throws away."""
        t0 = time.perf_counter()
        if self._delta is None:
            return
        self._tasks = []
        dropped = self.n_dead + int(
            (self._delta_count_s - self._delta_live_s).sum())
        S, C = self.shards, self.delta_capacity
        dx = np.asarray(self._delta["x"])[:, :C]
        dids = np.asarray(self._delta["ids"])[:, :C]
        dbids = np.asarray(self._delta["bucket_ids"])[:, :C]
        dlive = np.asarray(self._delta["live"])[:, :C]
        # one host fetch per leaf (shard slicing happens on the host)
        host = [{k: np.asarray(lvl.leaves[k])
                 for k in ("live", "x", "ids", "bucket_ids")}
                for lvl in self._levels]
        parts, total = [], 0
        for s in range(S):
            xs, es, bs = [dx[s][dlive[s]]], \
                [dids[s][dlive[s]].astype(np.int64)], [dbids[s][dlive[s]]]
            for lvl, h in zip(self._levels, host):
                live = h["live"][s, :lvl.n_pad]
                xs.append(h["x"][s][live])
                es.append(h["ids"][s][live].astype(np.int64))
                bs.append(h["bucket_ids"][s][live])
            x = np.concatenate(xs, axis=0)
            parts.append((x, np.concatenate(es), np.concatenate(bs, axis=0)))
            total += x.shape[0]
        self._levels = []
        self._version += 1
        self._reset_delta()
        if total:
            self._make_level(parts, self.policy.level_for(
                total, self.delta_capacity))
        self.stats.record(reason, t0, dropped)
        # record() measured the fold from t0; reuse its number — one
        # measurement, reported by both stats and the phase accumulator.
        self.phases.add("full", self.stats.last_seconds)
        self.obs.events.emit("full_compact", reason=reason, dropped=dropped,
                             seconds=self.stats.last_seconds)

    # ------------------------------------------------------------- query
    def query(self, queries: jax.Array, r: float,
              force: Optional[str] = None) -> ShardedQueryResult:
        """Hybrid r-NN reporting, union over shards; ids are external.

        Args:
          queries: (Q, d) rows, replicated to every shard.
          r: report radius — every returned neighbor has dist <= r.
          force: None (hybrid routing) | "lsh" | "linear" override.

        Returns a ``ShardedQueryResult`` with (S, Q, max_out) reporting
        buffers (union over the shard axis; ``neighbors(i)`` flattens
        it) plus global routing diagnostics.
        """
        assert self._delta is not None, "index is empty: build/insert first"
        queries = jnp.asarray(queries)
        d = self._delta
        n_pads = tuple(l.n_pad for l in self._levels)
        level_leaves = tuple(
            tuple(l.leaves[k] for k in _LEAVES) for l in self._levels)
        out = self._query_fn(n_pads, force)(
            level_leaves,
            (d["x"], d["bucket_ids"], d["ids"], d["live"], d["count"]),
            self.params, queries, jnp.float32(r))
        ids, dists, mask, coll, cand, used = (np.asarray(o) for o in out)
        return ShardedQueryResult(ids=ids, dists=dists, mask=mask,
                                  collisions=coll, cand_est=cand,
                                  used_lsh=used,
                                  n_queries=int(queries.shape[0]))

    def _query_fn(self, n_pads: Tuple[int, ...], force: Optional[str]):
        key = ("query", n_pads, force)
        if key in self._fn_cache:
            return self._fn_cache[key]
        family, cm, B = self.family, self.cost_model, self.num_buckets
        metric = family.metric
        cap, C = self.cap, self.delta_capacity
        # both cond branches must agree on the output width, and top_k
        # cannot widen a buffer: clamp by the narrower strategy's width
        max_out = min(self.max_out, sum(n_pads) + C + 1,
                      len(n_pads) * family.L * cap + C + 1)
        routing, axis = self.routing, self.data_axis
        engine, impl = self._engine, self.impl

        def _query(level_leaves, delta_leaves, params, queries, r):
            delta = delta_lib.DeltaSegment(*(l[0] for l in delta_leaves))
            qb = family.bucket_ids(params, queries, B)

            dview = delta_lib.DeltaView(delta, metric, impl=impl)
            d_est = dview.estimate_terms(qb)
            n_live_local = jnp.sum(delta.live, dtype=jnp.int32)
            n_scan_local = delta.count + sum(n_pads)
            segments, local_terms, global_terms = [], [], []
            for leaves, n_pad in zip(level_leaves, n_pads):
                (mx, mids, _bids, perm, starts, regs, live,
                 tcounts) = (l[0] for l in leaves)
                main = TableSegment(
                    tables=LSHTables(perm, starts, regs), x=mx,
                    metric=metric, cap=cap, live=live,
                    tomb_counts=tcounts, ext_ids=mids,
                    q_chunk=queries.shape[0], impl=impl)
                m_est = main.estimate_terms(qb)
                merged_local = hll_lib.merge_registers(
                    m_est.registers.astype(jnp.int32), axis=1)   # (Q, m)
                local_terms.append(dataclasses.replace(
                    m_est, registers=None,
                    merged_registers=merged_local))
                # cross-shard merge, per level: psum exact terms, pmax
                # the HLL registers (each level's internal ids are
                # globally unique, and levels are disjoint doc sets, so
                # pmax-per-level + sum-across-levels is exact).
                global_terms.append(SegmentEstimate(
                    collisions=jax.lax.psum(m_est.collisions, axis),
                    dead_collisions=jax.lax.psum(m_est.dead_collisions,
                                                 axis),
                    merged_registers=jax.lax.pmax(merged_local, axis),
                    over_cap=jax.lax.pmax(
                        m_est.over_cap.astype(jnp.int32), axis) > 0))
                segments.append(main)
                n_live_local = n_live_local + jnp.sum(live,
                                                      dtype=jnp.int32)
            segments.append(dview)
            local_terms.append(d_est)
            global_terms.append(SegmentEstimate(
                collisions=jax.lax.psum(d_est.collisions, axis),
                cand_exact=jax.lax.psum(
                    d_est.cand_exact.astype(jnp.float32), axis)))
            n_live_g = jax.lax.psum(n_live_local, axis)
            n_scan_g = jax.lax.psum(n_scan_local, axis)
            route_g = finalize_route(global_terms, cm, n_live=n_live_g,
                                     n_scan=n_scan_g)
            route_l = finalize_route(local_terms, cm, n_live=n_live_local,
                                     n_scan=n_scan_local)

            route = route_g if routing == "global" else route_l
            # one route a batch: linear if any row is over the cap
            use_lsh = ((jnp.sum(route.lsh_cost)
                        < route.linear_cost * queries.shape[0])
                       & ~jnp.any(route.over_cap))
            if force == "lsh":
                use_lsh = jnp.bool_(True)
            elif force == "linear":
                use_lsh = jnp.bool_(False)

            def branch(lsh_route):
                def fn(_):
                    ids, dists, mask = engine.search_group(
                        segments, qb, queries, r, lsh_route=lsh_route)
                    return compact_results(ids, dists, mask, max_out)
                return fn

            ids, dists, mask = jax.lax.cond(use_lsh, branch(True),
                                            branch(False), operand=None)
            return (ids[None], dists[None], mask[None], route_g.collisions,
                    route_g.cand_est, use_lsh[None])

        sh, rep = P(axis), P()
        fn = jax.jit(shard_map(
            _query, mesh=self.mesh,
            in_specs=(tuple((sh,) * len(_LEAVES) for _ in n_pads),
                      (sh,) * 5, rep, rep, rep),
            out_specs=(sh, sh, sh, rep, rep, sh), check_vma=False))
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------ observability
    def shard_of(self, ext_id: int) -> int:
        """Shard currently holding a live document (KeyError if absent).

        The answer is only stable until the next merge: rebalancing may
        move the row at swap time.
        """
        return self._loc[int(ext_id)][0]

    def validate_locations(self) -> int:
        """Debug invariant check: every ``_loc`` entry resolves to a live
        row whose stored external id matches, and every live device row
        is reachable.  Returns the number of live rows checked; raises
        AssertionError on any inconsistency.  Host-side and O(n) — for
        tests and debugging, not the serving path."""
        n_checked = 0
        # snapshot device arrays once: per-element jax indexing would
        # pay one device round-trip per live row
        by_uid = {l.uid: (l, np.asarray(l.leaves["live"]),
                          np.asarray(l.leaves["ids"]))
                  for l in self._levels}
        d_live = np.asarray(self._delta["live"]) if self._delta else None
        d_ids = np.asarray(self._delta["ids"]) if self._delta else None
        for e, loc in self._loc.items():
            s, kind = loc[0], loc[1]
            if kind == "m":
                uid, row = loc[2], loc[3]
                entry = by_uid.get(uid)
                assert entry is not None, (e, loc, "level gone")
                lvl, live, ids = entry
                assert row < int(lvl.rows_s[s]), (e, loc, "row out of range")
                assert bool(live[s, row]), (e, loc, "dead row")
                assert int(ids[s, row]) == e, (e, loc, "id mismatch")
            else:
                slot = loc[2]
                assert bool(d_live[s, slot]), (e, loc, "dead")
                assert int(d_ids[s, slot]) == e, (e, loc, "id mismatch")
            n_checked += 1
        total_live = (sum(l.n_live for l in self._levels)
                      + int(self._delta_live_s.sum()))
        assert n_checked == total_live, (n_checked, total_live)
        return n_checked

    def shard_loads(self) -> np.ndarray:
        """(S,) live rows per shard (levels + delta)."""
        loads = self._delta_live_s.copy()
        for l in self._levels:
            loads += l.live_s
        return loads

    def index_stats(self) -> Dict[str, object]:
        """Size/level/compaction counters snapshot (host ints/lists).

        Adds the sharded extras to the single-host set: per-shard live
        and delta loads, ``placement``, ``rows_moved`` (cumulative rows
        rebalanced at merges) and ``shard_skew`` = max/mean live load —
        1.0 is perfectly balanced; keep_local under a skewed stream
        grows it toward S.
        """
        S = self.shards
        live_per_shard = np.zeros(S, np.int64)
        for l in self._levels:
            live_per_shard += l.live_s
        loads = live_per_shard + self._delta_live_s
        skew = (float(loads.max() / loads.mean())
                if loads.sum() else 1.0)
        levels: Dict[int, int] = {}
        for l in self._levels:
            levels[l.level] = levels.get(l.level, 0) + 1
        out = {
            "n_live": self.n,
            "n_main": self.n_frozen_rows,
            "n_main_dead": self.n_dead,
            "delta_count": int(self._delta_count_s.sum()),
            "delta_live": int(self._delta_live_s.sum()),
            "delta_capacity": self.delta_capacity,
            "shards": S,
            "segments": len(self._levels),
            "levels": levels,
            "level_n_pads": [l.n_pad for l in self._levels],
            "pending_merges": len(self._tasks),
            "live_per_shard": live_per_shard.tolist(),
            "delta_per_shard": self._delta_count_s.tolist(),
            "shard_skew": skew,
            "placement": self.placement.name,
            "routing": self.routing,
            "inserts": self._inserts,
            "deletes": self._deletes,
            "work_seconds": self.compaction_work_seconds,
        }
        out.update(self.stats.as_dict())
        return out

    @property
    def compaction_work_seconds(self) -> Dict[str, float]:
        """Per-phase compaction work (stage/build/apply/full + total) —
        the same accumulator the driver's ``stats()`` reports, so the
        two surfaces can never disagree or double-count."""
        return self.phases.as_dict()

    # -------------------------------------------------------- checkpoint
    def state_dict(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Sharded level-stack leaves as a nested flat-array pytree.

        Leaves keep their leading shard axis; restore re-places them on
        the current mesh (same shard count) with ``device_put``.  The
        level list varies, so restore goes through the manifest-driven
        ``CheckpointManager.restore_index`` (no template).  Staged merge
        progress is volatile — inputs are still complete levels, so a
        restore loses no data and the policy re-schedules.  Rebalanced
        level layouts round-trip exactly (per-shard ``rows_s``/``live_s``
        ride in each level's meta), and the placement policy name rides
        in the top-level meta so a restored index keeps rebalancing.
        """
        S, L = self.shards, self.family.L
        levels: Dict[str, Dict] = {}
        for i, l in enumerate(self._levels):
            levels[f"{i:04d}"] = {
                **{k: np.asarray(v) for k, v in l.leaves.items()},
                "meta": {"uid": np.int64(l.uid),
                         "level": np.int64(l.level),
                         "rows_s": l.rows_s.astype(np.int64),
                         "live_s": l.live_s.astype(np.int64)},
            }
        if self._delta is not None:
            delta = {k: np.asarray(v) for k, v in self._delta.items()}
        else:
            C = self.delta_capacity
            delta = {"x": np.zeros((S, C + 1, 0), np.float32),
                     "bucket_ids": np.full((S, C + 1, L), -1, np.int32),
                     "ids": np.full((S, C + 1), -1, np.int32),
                     "live": np.zeros((S, C + 1), bool),
                     "count": np.zeros((S,), np.int32)}
        return {
            "params": self.params,
            "levels": levels,
            "delta": delta,
            "meta": {"next_id": np.int64(self._next_id),
                     "built": np.int64(0 if self._delta is None else 1),
                     "next_uid": np.int64(self._next_uid),
                     # 0-d unicode array: np.save round-trips it, and a
                     # restored index keeps rebalancing the same way
                     "placement": np.array(self.placement.name)},
        }

    # the six build-time leaves of a level; only live/tomb_counts
    # rebind after construction, so their digests can be cached
    _IMMUTABLE_LEAVES = ("x", "ids", "bucket_ids", "perm", "starts",
                         "registers")

    def state_digests(self) -> Dict[str, str]:
        """Content addresses for the immutable level leaves.

        Cached on each ``_ShardLevel``: deletes rebind only
        ``live``/``tomb_counts``, so the build-time leaves never change
        for the level's lifetime.  Feeding these hints to
        ``CheckpointManager.save_incremental`` makes snapshot hashing
        O(delta + tombstones) instead of O(corpus).
        """
        out: Dict[str, str] = {}
        for i, l in enumerate(self._levels):
            if l.digests is None:
                l.digests = {k: array_digest(np.asarray(l.leaves[k]))
                             for k in self._IMMUTABLE_LEAVES}
            for k, dg in l.digests.items():
                out[f"levels/{i:04d}/{k}"] = dg
        return out

    def load_state_dict(self, state) -> "ShardedDynamicHybridIndex":
        """Restore sharded level-stack state saved by ``state_dict``.

        The saved shard count may differ from the current mesh: leaves
        are mesh-agnostic host arrays with a leading shard axis, so a
        mismatch routes through ``_load_elastic`` which re-deals live
        rows onto the current shards.
        """
        self.params = jax.tree_util.tree_map(jnp.asarray, state["params"])
        # cached query fns bake in delta_capacity (the max_out clamp):
        # a restore may change it, so the cache cannot survive
        self._fn_cache = {}
        self._tasks = []
        self._version += 1
        self._next_id = int(np.asarray(state["meta"]["next_id"]))
        self._next_uid = int(np.asarray(state["meta"].get("next_uid", 0)))
        pl = state["meta"].get("placement")
        if pl is not None:      # pre-rebalancing checkpoints: keep ctor's
            try:
                self.placement = make_placement_policy(str(np.asarray(pl)))
            except ValueError:
                # custom PlacementPolicy subclass: only its name is
                # saved, so the restored index keeps the constructor's
                # policy (construct with the custom policy to restore it)
                pass
        if int(np.asarray(state["meta"]["built"])) == 0:
            self._levels, self._delta = [], None
            self._loc = {}
            return self
        ds = state["delta"]
        S = int(np.asarray(ds["live"]).shape[0])
        if S != self.shards:
            return self._load_elastic(state, ds, S)
        put = lambda a: jax.device_put(jnp.asarray(a), self._shard)
        self._delta = {k: put(v) for k, v in ds.items()}
        self.delta_capacity = int(np.asarray(ds["live"]).shape[1]) - 1
        self._d = int(np.asarray(ds["x"]).shape[2])
        self._dtype = np.asarray(ds["x"]).dtype
        self._loc = {}
        self._levels = []
        lvls = dict(state.get("levels") or {})
        ms = state.get("main")
        if ms is not None and np.asarray(ms["x"]).shape[1] > 0:
            # pre-stack checkpoint format (one sharded "main", no
            # meta): migrate to a single level — ignoring it would
            # silently restore an empty corpus
            mids = np.asarray(ms["ids"])
            n_pad = int(np.asarray(ms["x"]).shape[1])
            mlive = np.asarray(ms["live"])[:, :n_pad]
            rows_s = (mids != -1).sum(axis=1).astype(np.int64)
            lvls["main"] = {
                **ms,
                "meta": {"uid": np.int64(0),
                         "level": np.int64(self.policy.level_for(
                             int(rows_s.sum()), self.delta_capacity)),
                         "rows_s": rows_s,
                         "live_s": mlive.sum(axis=1).astype(np.int64)},
            }
        for key in sorted(lvls):
            s = dict(lvls[key])
            meta = s.pop("meta")
            leaves = {k: put(v) for k, v in s.items()}
            lvl = _ShardLevel(
                uid=int(np.asarray(meta["uid"])),
                level=int(np.asarray(meta["level"])),
                n_pad=int(np.asarray(s["x"]).shape[1]),
                leaves=leaves,
                rows_s=np.asarray(meta["rows_s"]).astype(np.int64),
                live_s=np.asarray(meta["live_s"]).astype(np.int64))
            self._levels.append(lvl)
            mids = np.asarray(s["ids"])
            mlive = np.asarray(s["live"])[:, :lvl.n_pad]
            for sh_i in range(self.shards):
                for i in np.nonzero(mlive[sh_i])[0]:
                    self._loc[int(mids[sh_i, i])] = (sh_i, "m", lvl.uid,
                                                     int(i))
        self._next_uid = max(self._next_uid,
                             max([l.uid for l in self._levels],
                                 default=-1) + 1)
        # delta host bookkeeping from segment state
        self._delta_count_s = np.asarray(ds["count"]).astype(np.int64)
        dlive = np.asarray(ds["live"])[:, :self.delta_capacity]
        self._delta_live_s = dlive.sum(axis=1).astype(np.int64)
        dids = np.asarray(ds["ids"])
        for s_i in range(self.shards):
            for i in range(int(self._delta_count_s[s_i])):
                if dlive[s_i, i]:
                    self._loc[int(dids[s_i, i])] = (s_i, "d", int(i))
        return self

    def _load_elastic(self, state, ds,
                      S_saved: int) -> "ShardedDynamicHybridIndex":
        """Restore a checkpoint saved on a different shard count.

        Live rows of each saved level are gathered host-side together
        with their staged hashes and dealt round-robin onto the current
        mesh through ``_make_level`` (no re-hash) — the same row
        movement the rebalancer performs, which preserves reported sets
        because placement never affects them.  Dead rows drop exactly
        as the next merge would have dropped them.  Delta rows re-deal
        the same way; if the new mesh's total delta capacity cannot
        hold them, they freeze into a level first, like an overflow
        flush.
        """
        S, L = self.shards, self.family.L
        self.delta_capacity = int(np.asarray(ds["live"]).shape[1]) - 1
        self._d = int(np.asarray(ds["x"]).shape[2])
        self._dtype = np.asarray(ds["x"]).dtype
        self._loc = {}
        self._levels = []
        lvls = dict(state.get("levels") or {})
        ms = state.get("main")
        if ms is not None and np.asarray(ms["x"]).shape[1] > 0:
            rows_s = (np.asarray(ms["ids"]) != -1).sum(axis=1)
            lvls["main"] = {**ms, "meta": {
                "level": np.int64(self.policy.level_for(
                    int(rows_s.sum()), self.delta_capacity))}}
        for key in sorted(lvls):
            s = dict(lvls[key])
            meta = s.pop("meta")
            n_pad = int(np.asarray(s["x"]).shape[1])
            xs, ids, bids = (np.asarray(s[k])
                             for k in ("x", "ids", "bucket_ids"))
            live = np.asarray(s["live"])[:, :n_pad]
            gx = np.concatenate([xs[sh][live[sh]]
                                 for sh in range(S_saved)])
            gi = np.concatenate([ids[sh][live[sh]]
                                 for sh in range(S_saved)])
            gb = np.concatenate([bids[sh][live[sh]]
                                 for sh in range(S_saved)])
            if gi.shape[0] == 0:
                continue        # fully-dead level: a merge drops it
            self._make_level(
                [(gx[sh::S], gi[sh::S], gb[sh::S]) for sh in range(S)],
                int(np.asarray(meta["level"])))
        # delta rows: gather live slots across saved shards, re-deal
        dx, dbid, did, dlive = (np.asarray(ds[k]) for k in
                                ("x", "bucket_ids", "ids", "live"))
        dcount = np.asarray(ds["count"]).astype(np.int64)
        masks = [dlive[sh, :int(dcount[sh])] for sh in range(S_saved)]
        rx = np.concatenate([dx[sh, :int(dcount[sh])][masks[sh]]
                             for sh in range(S_saved)])
        ri = np.concatenate([did[sh, :int(dcount[sh])][masks[sh]]
                             for sh in range(S_saved)])
        rb = np.concatenate([dbid[sh, :int(dcount[sh])][masks[sh]]
                             for sh in range(S_saved)])
        C = self.delta_capacity
        if rx.shape[0] > S * C:
            self._make_level([(rx[sh::S], ri[sh::S], rb[sh::S])
                              for sh in range(S)], 0)
            rx, ri, rb = rx[:0], ri[:0], rb[:0]
        put = lambda a: jax.device_put(jnp.asarray(a), self._shard)
        nx = np.zeros((S, C + 1, self._d), self._dtype)
        nb = np.full((S, C + 1, L), -1, np.int32)
        ni = np.full((S, C + 1), -1, np.int32)
        nl = np.zeros((S, C + 1), bool)
        nc = np.zeros((S,), np.int32)
        for sh in range(S):
            px, pi, pb = rx[sh::S], ri[sh::S], rb[sh::S]
            k = px.shape[0]
            nx[sh, :k], nb[sh, :k], ni[sh, :k] = px, pb, pi
            nl[sh, :k] = True
            nc[sh] = k
            for i, e in enumerate(pi.tolist()):
                self._loc[int(e)] = (sh, "d", int(i))
        self._delta = {"x": put(nx), "bucket_ids": put(nb),
                       "ids": put(ni), "live": put(nl), "count": put(nc)}
        self._delta_count_s = nc.astype(np.int64)
        self._delta_live_s = nc.astype(np.int64)
        return self
