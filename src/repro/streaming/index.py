"""DynamicHybridIndex — incremental inserts/deletes over the static core.

Segment architecture (LSM, multi-level):

  * delta segment  — fixed-capacity append-only buffers
    (``streaming.delta``); inserts are one fused ``.at[]`` scatter, so
    repeated same-size inserts never retrace.  Counts are exact.
  * segment stack  — immutable frozen segments arranged in levels
    (``streaming.segment.SegmentStack``).  When the delta fills it is
    *frozen* into a level-0 minor segment (CSR ``LSHTables`` +
    per-bucket HLLs over just the delta rows — O(delta_capacity), the
    older data is untouched); a tiered ``CompactionPolicy`` merges a
    level into the next when it overflows, so compaction cost
    amortizes O(log n)-style instead of O(n) per delta fill.
  * tombstones     — per-segment live bitmap + per-bucket dead counts;
    deletes never mutate tables.

Merges run *off the query path*: they are staged in bounded
``compact_step(budget_rows)`` increments (gather + hash at most
``budget_rows`` rows per step) and the merged segment swaps in
atomically; queries are served from the old level list until then.
With ``CompactionPolicy.step_rows=None`` (default) scheduled merges
drain synchronously after each mutation — the serving layer sets
``step_rows`` and ticks ``compact_step`` between query batches.

Queries hand the whole stack to the shared ``QueryEngine``
(``core.engine``): every frozen segment as a tombstone-aware
``TableSegment`` (corrected estimates, dead rows masked after search,
*external* ids reported), the delta as the exact ``DeltaView``.  A
mixed insert/delete workload therefore reports exactly the candidates
a fresh ``HybridLSHIndex.build()`` on the surviving corpus would (same
family parameters, cap permitting) — regardless of how many levels
exist or how far a pending merge has progressed.  ``num_probes > 1``
routes the multi-probe bucket set through the same path (SimHash
only).  The mesh-sharded variant lives in ``streaming.sharded``.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cost_model import CostModel
from repro.core.engine import (QueryEngine, QueryResult, RouteEstimate,
                               TableSegment, _pad_size)
from repro.core.lsh.families import bucket_fn_for
from repro.core.lsh.tables import LSHTables
from repro.obs import Observability
from repro.obs.metrics import WorkPhases
from repro.streaming import delta as delta_lib
from repro.streaming import tombstones as tomb_lib
from repro.streaming.compaction import CompactionPolicy, CompactionStats
from repro.streaming.segment import (FrozenSegment, MainSegment,
                                     SegmentStack, freeze_segment,
                                     frozen_digests, mark_rows_dead)

__all__ = ["DynamicHybridIndex"]

_pad_pow2 = _pad_size                # same pow2 padding as the router groups


class DynamicHybridIndex:
    """Streaming Hybrid LSH index: insert / delete / freeze / merge / query.

    Shape conventions: corpus rows are (n, d); external ids are int64
    host-side, stored int32 on device; per-row buckets are (n, L) in
    [0, num_buckets), with *pad rows hashed to bucket num_buckets* —
    one past the bucket space, dropped exactly by the CSR/HLL
    reductions — so padded builds and padded query groups stay exact
    (see ``streaming.segment`` / docs/architecture.md).
    """

    def __init__(self, family, *, num_buckets: int, m: int = 64,
                 cap: int = 64, delta_capacity: int = 4096,
                 cost_model: CostModel = CostModel(alpha=1.0, beta=10.0),
                 policy: CompactionPolicy = CompactionPolicy(),
                 key: jax.Array | int = 0, impl: Optional[str] = None,
                 obs: Optional[Observability] = None,
                 engine: Optional[QueryEngine] = None):
        """Args:
          family: LSH family (``make_family``); owns metric + hashes.
          num_buckets: buckets per table B.
          m: HLL registers per bucket.
          cap: LSH candidate verification cap per (query, table).
          delta_capacity: delta slots before a freeze.
          cost_model: Algorithm 2 cost constants (alpha, beta).
          policy: freeze/merge triggers (``CompactionPolicy``).
          key: PRNG key (or int seed) for the family parameters.
          impl: kernel impl override (e.g. ``"pallas_interpret"``).
          obs: observability bundle (tracer + event log + registry);
            default is a fresh disabled bundle — no cost unless asked.
          engine: a shared ``QueryEngine`` (multi-tenant collections
            pass one so every tenant routes through the same engine +
            tracer); default builds a private one from ``cost_model``.
        """
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
        self.impl = impl
        self.obs = obs if obs is not None else Observability.disabled()
        # Index-owned so the numbers survive stack resets
        # (build/compact/load_state_dict replace the SegmentStack).
        self.phases = WorkPhases("stage", "build", "apply", "full")
        self._engine = engine if engine is not None else QueryEngine(
            cost_model, impl=impl, tracer=self.obs.tracer)
        # shared across collections: bucket_fn_for is lru-cached on the
        # (hashable) family, so equal families reuse one jitted hash
        self._bucket_fn = bucket_fn_for(self.family, self.num_buckets)

        self.stack = SegmentStack(phases=self.phases)
        self.delta: Optional[delta_lib.DeltaSegment] = None
        self.stats = CompactionStats()
        # Result-cache invalidation: ``version`` must change whenever a
        # query could report differently.  Stack structure changes bump
        # ``stack.version``; delta inserts, deletes (tombstones + delta
        # kills), and wholesale stack replacements bump the base here.
        self._version_base = 0
        # Host bookkeeping: ext id -> ("m", uid, row) | ("d", slot).
        self._loc: Dict[int, tuple] = {}
        self._next_id = 0
        self._n_delta_live = 0
        self._inserts = 0
        self._deletes = 0

    # ------------------------------------------------------------- sizes
    @property
    def n(self) -> int:
        """Live document count (frozen live + delta live)."""
        return self.stack.n_live + self._n_delta_live

    @property
    def n_dead(self) -> int:
        return self.stack.n_dead

    @property
    def version(self) -> int:
        """Monotonic mutation version — the result-cache key component.

        Changes on every insert, delete, freeze, merge swap, and full
        rebuild; equal versions guarantee identical reported sets for
        the same (query, radius).  Monotone across stack replacements:
        ``_fold_version`` banks the outgoing stack's count first.
        """
        return self._version_base + self.stack.version

    def _fold_version(self) -> None:
        """Bank the current stack's version before replacing it, so the
        combined version can never run backwards when a fresh stack
        (version 0) is installed by build/compact/load_state_dict."""
        self._version_base += self.stack.version + 1

    # ------------------------------------------------- compat properties
    @property
    def main(self) -> Optional[MainSegment]:
        """The sole frozen segment, when the stack holds exactly one
        (the pre-stack "main segment" view; None otherwise)."""
        if len(self.stack.segments) == 1:
            return self.stack.segments[0].seg
        return None

    @property
    def tomb(self) -> Optional[tomb_lib.Tombstones]:
        if len(self.stack.segments) == 1:
            return self.stack.segments[0].tomb
        return None

    # ------------------------------------------------------------- build
    def build(self, x: jax.Array,
              ids: Optional[Sequence[int]] = None) -> "DynamicHybridIndex":
        """Initial batch build (Algorithm 1); returns self.

        Args: ``x`` (n, d) corpus rows; ``ids`` optional (n,) unique
        external ids (default 0..n-1).  Replaces any existing state.
        """
        x = np.asarray(x)
        if ids is None:
            ids = np.arange(x.shape[0], dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
            assert len(set(ids.tolist())) == len(ids), "duplicate ids"
        self._fold_version()
        self.stack = SegmentStack(phases=self.phases)
        self._loc = {}
        if x.shape[0] > 0:
            self._add_frozen(x, ids,
                             level=self.policy.level_for(
                                 x.shape[0], self.delta_capacity))
        self._reset_delta(x.shape[1] if x.ndim > 1 else 1, x.dtype)
        self._next_id = int(ids.max()) + 1 if len(ids) else 0
        return self

    def _add_frozen(self, x: np.ndarray, ext_ids: np.ndarray, level: int,
                    bucket_rows: Optional[np.ndarray] = None
                    ) -> FrozenSegment:
        seg = freeze_segment(x, np.asarray(ext_ids, np.int64),
                             self._bucket_fn, self.params,
                             self.num_buckets, self.m,
                             uid=self.stack.next_uid(), level=level,
                             bucket_rows=bucket_rows)
        self.stack.add(seg)
        for i, e in enumerate(np.asarray(ext_ids).tolist()):
            self._loc[int(e)] = ("m", seg.uid, i)
        return seg

    def _reset_delta(self, d: int, dtype) -> None:
        self.delta = delta_lib.make_delta(self.delta_capacity, d,
                                          self.family.L, dtype)
        self._n_delta_live = 0

    # ------------------------------------------------------------ insert
    def insert(self, rows: jax.Array,
               ids: Optional[Sequence[int]] = None) -> np.ndarray:
        """Append documents; returns their external ids as (k,) int64.

        Args: ``rows`` (k, d); ``ids`` optional (k,) unused external ids
        (KeyError on duplicates), default continues the running counter.
        Splits the batch by remaining delta capacity, freezing the delta
        into a level-0 segment between chunks when it fills — inserts
        never wait on a rebuild of older data.
        """
        rows = jnp.asarray(rows)
        if rows.shape[0] == 0:
            return np.zeros((0,), np.int64)
        if self.delta is None:  # first contact: empty index, delta-only
            self._reset_delta(rows.shape[1], rows.dtype)
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
            free = self.delta.capacity - int(self.delta.count)
            if free == 0:
                self._freeze("delta_full")
                free = self.delta.capacity
            take = min(free, rows.shape[0] - lo)
            self._insert_chunk(rows[lo:lo + take], ids[lo:lo + take])
            lo += take
        self._next_id = max(self._next_id, int(ids.max()) + 1)
        self._maybe_compact()
        return ids

    def _insert_chunk(self, rows: jax.Array, ids: np.ndarray) -> None:
        k = rows.shape[0]
        pk = _pad_pow2(k)
        pad = [(0, pk - k)] + [(0, 0)] * (rows.ndim - 1)
        rows_p = jnp.pad(rows, pad)
        bids = self._bucket_fn(self.params, rows_p)     # (pk, L)
        ids_p = np.zeros(pk, np.int32)
        ids_p[:k] = ids
        valid = np.zeros(pk, bool)
        valid[:k] = True
        base = int(self.delta.count)
        self.delta = delta_lib.insert(self.delta, rows_p, bids,
                                      jnp.asarray(ids_p),
                                      jnp.asarray(valid))
        for i, e in enumerate(ids.tolist()):
            self._loc[int(e)] = ("d", base + i)
        self._n_delta_live += k
        self._inserts += k
        self._version_base += 1

    # ------------------------------------------------------------ delete
    def delete(self, ids: Iterable[int], strict: bool = False) -> int:
        """Tombstone documents by external id; returns #removed.

        Unknown (or already-deleted) ids are skipped unless ``strict``.
        """
        by_uid: Dict[int, List[int]] = {}
        delta_slots: List[int] = []
        for e in ids:
            loc = self._loc.pop(int(e), None)
            if loc is None:
                if strict:
                    raise KeyError(e)
                continue
            if loc[0] == "d":
                delta_slots.append(loc[1])
            else:
                by_uid.setdefault(loc[1], []).append(loc[2])
        removed = 0
        for uid, rows in by_uid.items():
            mark_rows_dead(self.stack.by_uid(uid), rows)
            removed += len(rows)
        if delta_slots:
            k = len(delta_slots)
            pk = _pad_pow2(k)
            slots_p = np.zeros(pk, np.int32)
            slots_p[:k] = delta_slots
            valid = np.zeros(pk, bool)
            valid[:k] = True
            self.delta = delta_lib.kill(self.delta, jnp.asarray(slots_p),
                                        jnp.asarray(valid))
            self._n_delta_live -= k
            removed += k
        self._deletes += removed
        if removed:
            self._version_base += 1
        self._maybe_compact()
        return removed

    # --------------------------------------------------------- compaction
    def _freeze(self, reason: str) -> None:
        """Seal the delta's live rows into a level-0 minor segment.

        O(delta_capacity): the delta already carries its hashes, so the
        freeze is one fused ``build_tables`` over at most capacity rows.
        """
        if self.delta is None or int(self.delta.count) == 0:
            return
        c = self.delta.capacity
        live = np.asarray(self.delta.live[:c])
        x = np.asarray(self.delta.x[:c])[live]
        ext = np.asarray(self.delta.ids[:c])[live].astype(np.int64)
        bids = np.asarray(self.delta.bucket_ids[:c])[live]
        self._reset_delta(self.delta.x.shape[1], self.delta.x.dtype)
        if len(ext) == 0:
            return
        self._add_frozen(x, ext, level=0, bucket_rows=bids)
        self.stats.record_freeze(len(ext))
        self.obs.events.emit("freeze", rows=len(ext), reason=reason)

    def _maybe_compact(self) -> None:
        if self.delta is not None:
            r = self.policy.freeze_reason(
                delta_count=int(self.delta.count),
                delta_capacity=self.delta_capacity)
            if r:
                self._freeze(r)
        self._schedule_merges()
        if self.policy.step_rows is None:
            self._drain()

    def _schedule_merges(self) -> None:
        """Materialize the policy's merge decisions as pending tasks."""
        segs = self.stack.segments
        if not segs:
            return
        pend = self.stack.pending_uids()
        free = [s for s in segs if s.uid not in pend]
        counts: Dict[int, int] = {}
        for s in free:
            counts[s.level] = counts.get(s.level, 0) + 1
        for reason, src, target in self.policy.plan_merges(
                level_counts=counts, n_rows=self.stack.n_rows,
                n_dead=self.stack.n_dead, n_live=self.stack.n_live,
                unit=self.delta_capacity, can_full=not pend):
            uids = [s.uid for s in free if src is None or s.level == src]
            if self.stack.schedule(uids, target, reason):
                self.obs.events.emit("merge_scheduled", uids=uids,
                                     target_level=target, reason=reason)

    def compact_step(self, budget_rows: Optional[int] = None) -> bool:
        """Advance pending merge work by one bounded step (off-query-path
        tick).  Gathers + hashes at most ``budget_rows`` rows; a merge
        whose staging is complete swaps its segment in atomically.
        Returns True while more work remains."""
        if not self.stack.has_work:
            return False
        budget = int(budget_rows or self.policy.step_rows
                     or max(self.delta_capacity, 1))
        res = self.stack.compact_step(budget, self._bucket_fn, self.params,
                                      self.num_buckets, self.m)
        self.stats.record_step()
        if res is not None:
            self._absorb_merge(res)
        return self.stack.has_work

    def _absorb_merge(self, res) -> None:
        """Fold a completed ``MergeResult`` into index state (the one
        post-swap block, shared by the tick and driver paths): ``_loc``
        rewrites for every surviving row, merge stats, and the cascade
        re-schedule.  Control-thread-only."""
        if res.new is not None:
            for e, i in res.moved:
                self._loc[e] = ("m", res.new.uid, i)
        self.stats.record_merge(res.target_level, len(res.moved),
                                res.steps, res.seconds, res.dropped,
                                reason=res.reason)
        self.obs.events.emit("swap", target_level=res.target_level,
                             rows=len(res.moved), dropped=res.dropped,
                             steps=res.steps, seconds=res.seconds,
                             reason=res.reason)
        self._schedule_merges()          # cascade up the levels

    # ---------------------------------------------- driver (async) surface
    @property
    def has_compaction_work(self) -> bool:
        """True while any merge is queued (parity with the sharded index
        — the one predicate drivers and serving ticks poll)."""
        return self.stack.has_work

    @property
    def staged_ready(self) -> bool:
        """A fully-staged merge awaits a control-thread ``apply_staged``."""
        return self.stack.staged_ready

    @property
    def staged_rows(self) -> int:
        """Rows currently gathered into merge staging buffers."""
        return self.stack.staged_rows

    @property
    def pending_merges(self) -> int:
        """Queued merge tasks (head may be partially staged)."""
        return len(self.stack.tasks)

    def stage_step(self, budget_rows: Optional[int] = None) -> str:
        """Advance ONLY the staging half of the active merge.

        The worker-thread half of the ``CompactionDriver`` split: gathers
        at most ``budget_rows`` live rows into the task's private host
        buffers without touching the served level list, so it is safe to
        run concurrently with inserts/deletes/queries on the control
        thread.  Returns ``"idle"`` | ``"staging"`` | ``"ready"``; once
        ``"ready"``, only a control-thread ``apply_staged`` makes
        further progress.
        """
        if not self.stack.has_work:
            return "idle"
        if self.stack.staged_ready:
            return "ready"
        budget = int(budget_rows or self.policy.step_rows
                     or max(self.delta_capacity, 1))
        st = self.stack.stage_step(budget)
        self.stats.record_step()
        return st

    def prepare_staged(self) -> bool:
        """Speculatively build the staged merge's output off-thread.

        Worker-thread-safe (the staging buffers are immutable once
        ``stage_step`` reports ``"ready"``): runs the fused build so
        the control thread's ``apply_staged`` shrinks to the delete
        re-check + uid + list swap + ``_loc`` rewrites.  Returns True
        when a build ran.
        """
        return self.stack.prepare_staged(self._bucket_fn, self.params,
                                         self.num_buckets, self.m)

    def apply_staged(self) -> bool:
        """CONTROL-THREAD ONLY: swap a fully-staged merge in.

        Runs the mid-merge delete re-check, the atomic level swap, the
        ``_loc`` rewrites for every surviving row, and schedules
        cascaded merges — plus the fused build when no worker
        ``prepare_staged`` pre-built it.  Returns True when a merge was
        applied (False: nothing fully staged — staging stays with the
        worker's ``stage_step``).
        """
        res = self.stack.apply_staged(self._bucket_fn, self.params,
                                      self.num_buckets, self.m)
        if res is None:
            return False
        self.stats.record_step()
        self._absorb_merge(res)
        return True

    def _drain(self) -> None:
        while self.stack.has_work:
            self.compact_step(budget_rows=max(self.stack.n_rows, 1))

    def compact(self, reason: str = "manual") -> None:
        """Blocking full compaction: fold every frozen segment + the
        delta into one segment (drops tombstones).  Pending merge
        staging is discarded, not drained — its inputs are still
        complete segments and the fold re-gathers everything, so
        finishing a partial merge first would just build a segment the
        fold immediately throws away."""
        t0 = time.perf_counter()
        self.stack.tasks = []
        if not self.stack.segments and self.delta is None:
            return
        dropped = self.stack.n_dead
        parts_x, parts_id, parts_b = [], [], []
        for f in self.stack.segments:
            live = np.asarray(f.tomb.live[:f.n_rows])
            parts_x.append(np.asarray(f.seg.x[:f.n_rows])[live])
            parts_id.append(np.asarray(f.seg.ids[:f.n_rows])[live])
            parts_b.append(np.asarray(f.seg.bucket_ids[:f.n_rows])[live])
        if self.delta is not None:
            c = self.delta.capacity
            dropped += int(self.delta.count) - self._n_delta_live
            live = np.asarray(self.delta.live[:c])
            parts_x.append(np.asarray(self.delta.x[:c])[live])
            parts_id.append(np.asarray(self.delta.ids[:c])[live])
            parts_b.append(np.asarray(self.delta.bucket_ids[:c])[live])
        if not parts_x:
            return
        x = np.concatenate(parts_x, axis=0)
        ext = np.concatenate(parts_id, axis=0).astype(np.int64)
        bids = np.concatenate(parts_b, axis=0)
        d = self.delta.x.shape[1] if self.delta is not None else (
            x.shape[1] if x.ndim > 1 else 1)
        dtype = self.delta.x.dtype if self.delta is not None else x.dtype
        self._fold_version()
        self.stack = SegmentStack(phases=self.phases)
        self._loc = {}
        if len(ext):
            self._add_frozen(x, ext,
                             level=self.policy.level_for(
                                 len(ext), self.delta_capacity),
                             bucket_rows=bids)
        self._reset_delta(d, dtype)
        self.stats.record(reason, t0, dropped)
        # record() measured the fold from t0; reuse its number — one
        # measurement, reported by both stats and the phase accumulator.
        self.phases.add("full", self.stats.last_seconds)
        self.obs.events.emit("full_compact", reason=reason, dropped=dropped,
                             seconds=self.stats.last_seconds)

    # ------------------------------------------------------------- query
    def _segments(self, tidx: Optional[jax.Array] = None,
                  batch: int = 0) -> List:
        """The whole stack + delta as engine ``Segment`` adapters."""
        segs: List = []
        metric = self.family.metric
        for f in self.stack.segments:
            segs.append(TableSegment(
                tables=f.seg.tables, x=f.seg.x, metric=metric,
                cap=self.cap, impl=self.impl, live=f.tomb.live,
                tomb_counts=f.tomb.counts, ext_ids=f.seg.ids,
                n_live=f.n_live, n_scan=f.n_pad, tidx=tidx))
        with TraceAnnotation("repro.index.delta_count.sync", batch=batch,
                             reads=1):
            n_scan = int(self.delta.count)
        segs.append(delta_lib.DeltaView(
            self.delta, metric, impl=self.impl,
            n_live=self._n_delta_live, n_scan=n_scan, tidx=tidx))
        return segs

    def _qbuckets(self, queries: jax.Array, num_probes: int
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
        if num_probes <= 1:
            return self._bucket_fn(self.params, queries), None
        if not hasattr(self.family, "margins"):
            raise ValueError(
                "multi-probe needs a family with probing sequences "
                f"(SimHash); got {type(self.family).__name__}")
        from repro.core import multiprobe as mp
        qbp = mp.probe_buckets(self.family, self.params, queries,
                               num_probes, self.num_buckets)
        return mp.flatten_probes(qbp)

    def estimate(self, queries: jax.Array,
                 num_probes: int = 1) -> RouteEstimate:
        assert self.delta is not None, "index is empty: build/insert first"
        qb, tidx = self._qbuckets(jnp.asarray(queries), num_probes)
        return self._engine.estimate(self._segments(tidx), qb)

    def query(self, queries: jax.Array, r: float,
              force: Optional[str] = None,
              num_probes: int = 1) -> QueryResult:
        """Hybrid r-NN reporting over the whole stack; ids are external.

        Args:
          queries: (Q, d) rows in the corpus metric space.
          r: report radius — every returned neighbor has dist <= r.
          force: None (hybrid) | "lsh" | "linear" strategy override.
          num_probes: > 1 probes the Lv et al. perturbation buckets in
            every frozen level AND the delta (SimHash families only).

        Returns a ``QueryResult`` (see ``core.engine``): per-strategy
        sentinel-padded buffers plus the ``RouteEstimate`` diagnostics.
        """
        assert self.delta is not None, "index is empty: build/insert first"
        batch = self._engine.next_batch()
        with TraceAnnotation("repro.index.query", batch=batch,
                             rows=len(queries)):
            queries = jnp.asarray(queries)
            with TraceAnnotation("repro.index.hash", batch=batch):
                qb, tidx = self._qbuckets(queries, num_probes)
            return self._engine.query(self._segments(tidx, batch), queries,
                                      qb, float(r), force=force, batch=batch)

    # ------------------------------------------------------ observability
    @property
    def compaction_work_seconds(self) -> Dict[str, float]:
        """Per-phase compaction work (stage/build/apply/full + total) —
        the one accumulator behind ``index_stats()["work_seconds"]`` and
        the driver's ``stats()["work_seconds"]``, so the two surfaces
        can never disagree or double-count."""
        return self.phases.as_dict()

    def index_stats(self) -> Dict[str, object]:
        """Size/level/compaction counters snapshot (host ints/dicts):
        ``n_live``/``n_main``/``n_main_dead``, delta fill, segment and
        per-level counts, pending merges, per-phase ``work_seconds``,
        and every cumulative ``CompactionStats`` counter (freezes,
        merges_per_level, ...)."""
        out = {
            "n_live": self.n,
            "n_main": self.stack.n_rows,
            "n_main_dead": self.n_dead,
            "delta_count": int(self.delta.count) if self.delta else 0,
            "delta_live": self._n_delta_live,
            "delta_capacity": self.delta_capacity,
            "segments": len(self.stack.segments),
            "levels": self.stack.level_counts(),
            "pending_merges": len(self.stack.tasks),
            "inserts": self._inserts,
            "deletes": self._deletes,
            "work_seconds": self.compaction_work_seconds,
        }
        out.update(self.stats.as_dict())
        return out

    # -------------------------------------------------------- checkpoint
    def state_dict(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Stack + delta state as a nested flat-array pytree.

        Frozen segments land under ``segments/<i>`` with their level/uid
        metadata; the structure varies with the stack, so restore goes
        through ``CheckpointManager.restore_index`` (manifest-driven, no
        template needed).  Staged merge progress is volatile: a pending
        merge's inputs are still complete segments, so dropping the
        staging on restore loses no data — the policy just re-schedules.
        """
        L = self.family.L
        d = self.delta.x.shape[1] if self.delta is not None else 0
        segments: Dict[str, Dict] = {}
        for i, f in enumerate(self.stack.segments):
            t = f.seg.tables
            segments[f"{i:04d}"] = {
                "x": np.asarray(f.seg.x),
                "ids": np.asarray(f.seg.ids),
                "bucket_ids": np.asarray(f.seg.bucket_ids),
                "perm": np.asarray(t.perm),
                "starts": np.asarray(t.starts),
                "registers": np.asarray(t.registers),
                "live": np.asarray(f.tomb.live),
                "tomb_counts": np.asarray(f.tomb.counts),
                "meta": {"uid": np.int64(f.uid),
                         "level": np.int64(f.level),
                         "n_rows": np.int64(f.n_rows),
                         "n_live": np.int64(f.n_live)},
            }
        delta = (self.delta if self.delta is not None
                 else delta_lib.make_delta(self.delta_capacity, 1, L))
        return {
            "params": self.params,
            "segments": segments,
            "delta": {"x": np.asarray(delta.x),
                      "bucket_ids": np.asarray(delta.bucket_ids),
                      "ids": np.asarray(delta.ids),
                      "live": np.asarray(delta.live),
                      "count": np.asarray(delta.count)},
            # delta_d == 0 marks "never populated": the saved delta row
            # width is a placeholder and must not survive a restore.
            "meta": {"next_id": np.int64(self._next_id),
                     "delta_d": np.int64(0 if self.delta is None else d),
                     "next_uid": np.int64(self.stack._next_uid)},
        }

    def state_digests(self) -> Dict[str, str]:
        """Content-address hints matching ``state_dict`` leaf paths,
        for the leaves that are immutable once frozen.

        ``CheckpointManager.save_incremental`` uses these to reference
        unchanged level chunks without re-hashing them; the tombstone
        bitmaps, delta, params, and meta change between snapshots and
        are never hinted (they re-hash each save).
        """
        out: Dict[str, str] = {}
        for i, f in enumerate(self.stack.segments):
            for k, dg in frozen_digests(f).items():
                out[f"segments/{i:04d}/{k}"] = dg
        return out

    def load_state_dict(self, state) -> "DynamicHybridIndex":
        """Restore stack + delta state saved by ``state_dict``."""
        self.params = jax.tree_util.tree_map(jnp.asarray, state["params"])
        self._bucket_fn = bucket_fn_for(self.family, self.num_buckets)
        self._fold_version()
        self.stack = SegmentStack(phases=self.phases)
        self._loc = {}
        segs = dict(state.get("segments") or {})
        ms = state.get("main")
        if ms is not None and np.asarray(ms["x"]).shape[0] > 0:
            # pre-stack checkpoint format (one "main" segment, exact
            # rows, no meta): migrate it to a single frozen segment —
            # ignoring it would silently restore an empty index
            n = int(np.asarray(ms["x"]).shape[0])
            segs["main"] = {
                **ms,
                "meta": {"uid": np.int64(0), "level": np.int64(
                    self.policy.level_for(n, self.delta_capacity)),
                    "n_rows": np.int64(n),
                    "n_live": np.asarray(ms["live"])[:n].sum()},
            }
        for key in sorted(segs):
            s = segs[key]
            meta = s["meta"]
            f = FrozenSegment(
                uid=int(np.asarray(meta["uid"])),
                level=int(np.asarray(meta["level"])),
                seg=MainSegment(
                    x=jnp.asarray(s["x"]),
                    ids=jnp.asarray(s["ids"], jnp.int32),
                    bucket_ids=jnp.asarray(s["bucket_ids"], jnp.int32),
                    tables=LSHTables(jnp.asarray(s["perm"], jnp.int32),
                                     jnp.asarray(s["starts"], jnp.int32),
                                     jnp.asarray(s["registers"],
                                                 jnp.uint8))),
                tomb=tomb_lib.Tombstones(
                    live=jnp.asarray(s["live"], bool),
                    counts=jnp.asarray(s["tomb_counts"], jnp.int32)),
                n_rows=int(np.asarray(meta["n_rows"])),
                n_live=int(np.asarray(meta["n_live"])))
            self.stack.add(f)
            live = np.asarray(f.tomb.live[:f.n_rows])
            eids = np.asarray(f.seg.ids[:f.n_rows])
            for i in np.nonzero(live)[0]:
                self._loc[int(eids[i])] = ("m", f.uid, int(i))
        self.stack._next_uid = int(np.asarray(
            state["meta"].get("next_uid",
                              max([s.uid for s in self.stack.segments],
                                  default=-1) + 1)))
        ds = state["delta"]
        if int(np.asarray(state["meta"].get("delta_d", 1))) == 0:
            self.delta = None        # saved before first build/insert
            self._n_delta_live = 0
        else:
            self.delta = delta_lib.DeltaSegment(
                x=jnp.asarray(ds["x"]),
                bucket_ids=jnp.asarray(ds["bucket_ids"], jnp.int32),
                ids=jnp.asarray(ds["ids"], jnp.int32),
                live=jnp.asarray(ds["live"], bool),
                count=jnp.asarray(ds["count"], jnp.int32))
            self.delta_capacity = self.delta.capacity
            dl = np.asarray(self.delta.live)
            self._n_delta_live = int(dl.sum())
            d_ids = np.asarray(self.delta.ids)
            for s in range(int(self.delta.count)):
                if dl[s]:
                    self._loc[int(d_ids[s])] = ("d", s)
        self._next_id = int(np.asarray(state["meta"]["next_id"]))
        return self
