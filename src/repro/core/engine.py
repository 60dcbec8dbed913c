"""Segment engine — the one estimate→route→partition→search pipeline.

Every index in the repo (static ``HybridLSHIndex``, mesh-sharded
``core.distributed``, streaming ``DynamicHybridIndex``, and the sharded
streaming ``streaming.sharded``) is a composition over two concepts:

  * ``Segment``     — a searchable unit exposing its routing terms
                      (exact collisions, HLL registers or exact distinct
                      counts, tombstone dead counts, live/scan sizes)
                      and a fixed-shape search over its rows.
  * ``QueryEngine`` — owns Algorithm 2 once: gather per-segment terms,
                      combine them into a ``RouteEstimate``
                      (``finalize_route``), partition the query batch,
                      and run both strategies over every segment.

The old static/dynamic estimator split collapses here: a static segment
is simply one whose dead counts are zero and whose scan size equals its
live size, so ``finalize_route`` serves both.  The segment list is
arbitrary-length: the streaming index hands over its whole LSM level
stack (every frozen level + the delta) and the per-segment dead-count
correction composes term-by-term — Algorithm 2 stays a single path no
matter how many levels exist.  Multi-probe composes the same way: a
``tidx`` column→table map turns (Q, L*T) probed buckets into virtual
tables that every segment adapter understands.  The distributed indexes
reuse the traceable pieces (``Segment.estimate_terms`` +
``finalize_route`` + ``Segment.search``) inside ``shard_map``, merging
``SegmentEstimate`` fields across shards with ``psum``/``pmax`` before
finalizing — host-side partitioning only happens in the single-host
``QueryEngine.query``.

The host path carries profiler spans (``jax.profiler.TraceAnnotation``,
names ``repro.*``, on the device trace's clock, ~1 us each when no
profiler runs); a span whose body reads from the device ends in
``.sync`` and counts its ``reads`` (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Protocol, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import hll as hll_lib
from repro.core import search as search_lib
from repro.core.cost_model import CostModel
from repro.core.lsh.tables import (LSHTables, bucket_counts,
                                   gather_registers, table_index)
from repro.kernels import ops

__all__ = ["RouteEstimate", "SegmentEstimate", "Segment", "TableSegment",
           "QueryEngine", "QueryResult", "finalize_route",
           "partition_indices", "compact_results", "EXT_SENTINEL"]

Scalar = Union[int, float, jax.Array]

EXT_SENTINEL = np.int32(2**31 - 1)   # masked-out slots in reported buffers


# ---------------------------------------------------------------------------
# Route estimate (Algorithm 2 lines 1-4, vectorized over the query batch)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RouteEstimate:
    """Vectorized output of Algorithm 2 lines 1-4."""

    collisions: jax.Array   # (Q,) int32   exact live sum of bucket sizes
    cand_est: jax.Array     # (Q,) float32 HLL union estimate of candSize
    lsh_cost: jax.Array     # (Q,) float32 Eq. (1)
    linear_cost: Scalar     # scalar       Eq. (2) (traced under shard_map)
    use_lsh: jax.Array      # (Q,) bool    Algorithm 2 line 4, over_cap rows off
    over_cap: Optional[jax.Array] = None   # (Q,) bool, see SegmentEstimate


@dataclasses.dataclass
class SegmentEstimate:
    """One segment's contribution to the routing estimate.

    Exactly one of ``registers`` / ``merged_registers`` / ``cand_exact``
    normally carries the candSize term: CSR+HLL segments report raw
    ``(Q, L, m)`` registers (so the fused merge+estimate kernel applies),
    cross-shard merges report pre-merged ``(Q, m)`` registers, and
    sketch-free segments (the delta) report an exact distinct count.  A
    merged cross-shard estimate may carry both a sketch and an exact
    term; they are summed.
    """

    collisions: jax.Array                          # (Q,) exact live
    dead_collisions: Optional[jax.Array] = None    # (Q,) or None (static)
    registers: Optional[jax.Array] = None          # (Q, L, m) uint8
    merged_registers: Optional[jax.Array] = None   # (Q, m)
    cand_exact: Optional[jax.Array] = None         # (Q,) exact distinct
    n_live: Scalar = 0    # live rows this segment contributes
    n_scan: Scalar = 0    # rows its linear scan computes distances over
    # (Q,) bool: the row's probed buckets hold more rows past this
    # segment's LSH gather cap than one bucket's worth (the share past
    # the cap, summed over the buckets exceeds 1), so that route would
    # lose more than one table of the row's candidates; None for a
    # segment whose LSH route gathers every colliding row
    over_cap: Optional[jax.Array] = None


class Segment(Protocol):
    """Anything the engine can route over (duck-typed; no inheritance)."""

    def estimate_terms(self, qbuckets: jax.Array) -> SegmentEstimate:
        """(Q, L) query buckets -> this segment's routing terms."""
        ...

    def search(self, qbuckets: jax.Array, q: jax.Array, r, *,
               lsh_route: bool) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Fixed-shape search -> sentinel-padded ``(ids, dists, mask)``."""
        ...

    # Traced queries (``QueryEngine`` with a tracer) additionally call
    # ``count_candidates(qbuckets) -> (Q,)``: the distinct candidates
    # this segment's LSH route gathers (cap-truncated).  Both in-repo
    # adapters implement it; a custom segment only needs it when
    # tracing is enabled.


@jax.jit
def _route_rows(lsh_cost, linear_cost, overs):
    """(Q,) ``use_lsh`` and the segments' ``over_cap`` or-ed (None when
    no segment flags it), in one dispatch."""
    use_lsh = lsh_cost < linear_cost
    if not overs:
        return use_lsh, None
    over_cap = functools.reduce(jnp.logical_or, overs)
    return use_lsh & ~over_cap, over_cap


def finalize_route(terms: Sequence[SegmentEstimate], cost_model: CostModel,
                   *, impl: Optional[str] = None,
                   n_live: Optional[Scalar] = None,
                   n_scan: Optional[Scalar] = None) -> RouteEstimate:
    """Combine per-segment terms into the tombstone-aware RouteEstimate.

    collisions = sum of exact live collisions; candSize = sum over
    segments of (HLL estimate - dead collisions, clamped at 0) plus the
    exact distinct counts, clamped by the structural bounds (candSize is
    a distinct count, <= live #collisions and <= n_live).  Static
    segments simply have zero dead counts.  HLL registers are monotone
    (they never decrement), so the dead-count subtraction over-corrects
    slightly — a dead point colliding in several tables is subtracted
    once per table — making the churned estimate a mild under-estimate,
    biased toward the LSH route, whose verification step masks dead
    rows cheaply.  LinearCost is priced at ``n_scan``: the rows the
    linear route actually computes distances over (tombstoned or padded
    rows included — masking happens after the scan).

    A row any segment flags ``over_cap`` is sent linear whatever its
    cost: the LSH route keeps the first ``cap`` rows of each probed
    bucket, so a neighbour in a bucket of c > cap rows survives with
    probability cap / c, and a row whose buckets lose more than one
    table's worth of rows that way (its own neighbourhood denser than
    the cap) would get an arbitrary part of its answer; only the linear
    scan answers it in full.  A row that loses at most one table's
    worth (an unrelated dense bucket hashed beside it) keeps the LSH
    route: its recall at r is at least what ``L - 1`` tables give.
    """
    assert terms, "finalize_route needs at least one segment"
    collisions = terms[0].collisions
    for t in terms[1:]:
        collisions = collisions + t.collisions
    if n_live is None:
        n_live = sum(t.n_live for t in terms)
    if n_scan is None:
        n_scan = sum(t.n_scan for t in terms)

    cand = jnp.zeros_like(collisions, dtype=jnp.float32)
    for t in terms:
        if t.registers is not None:
            est = ops.hll_merge_estimate(t.registers, impl=impl)
        elif t.merged_registers is not None:
            est = hll_lib.estimate_from_registers(t.merged_registers)
        else:
            est = None
        if est is not None:
            if t.dead_collisions is not None:
                est = jnp.maximum(
                    est - t.dead_collisions.astype(jnp.float32), 0.0)
            cand = cand + est
        if t.cand_exact is not None:
            cand = cand + t.cand_exact.astype(jnp.float32)
    n_live_f = (float(n_live) if isinstance(n_live, (int, float))
                else n_live.astype(jnp.float32))
    cand = jnp.minimum(cand, jnp.minimum(
        collisions.astype(jnp.float32), n_live_f))
    lsh_cost = cost_model.lsh_cost(collisions.astype(jnp.float32), cand)
    linear_cost = cost_model.linear_cost(n_scan)
    use_lsh, over_cap = _route_rows(
        lsh_cost, linear_cost,
        tuple(t.over_cap for t in terms if t.over_cap is not None))
    return RouteEstimate(collisions=collisions, cand_est=cand,
                         lsh_cost=lsh_cost, linear_cost=linear_cost,
                         use_lsh=use_lsh, over_cap=over_cap)


# ---------------------------------------------------------------------------
# The CSR+HLL segment (static core and the streaming main segment)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cap",))
def _table_terms(starts, registers, qbuckets, tidx, tomb_counts, *, cap):
    """A table segment's estimate terms in one dispatch: (Q,) collisions
    (tombstoned rows subtracted), (Q,) dead collisions or None, the
    (Q, V, m) probed registers, and (Q,) ``over_cap`` (None when ``cap``
    is None).  It takes the tables' ``starts``/``registers`` and not
    the tables, so segments of any row count share one compile."""
    # an empty perm: the gathers below read starts and registers only
    tables = LSHTables(perm=starts[:, :0], starts=starts,
                       registers=registers)
    counts = bucket_counts(tables, qbuckets, tidx=tidx)
    regs = gather_registers(tables, qbuckets, tidx=tidx)
    if tomb_counts is None:
        collisions = jnp.sum(counts, axis=-1)
        dead = None
    else:
        lidx = table_index(tables, tidx)
        d = tomb_counts[lidx, qbuckets.astype(jnp.int32)]
        collisions = jnp.sum(counts - d, axis=-1)
        dead = jnp.sum(d, axis=-1)
    # tombstoned rows hold gather slots too: the raw sizes count
    over = None
    if cap is not None:
        lost = jnp.where(counts > cap, 1.0 - cap / jnp.maximum(counts, 1),
                         0.0)
        over = jnp.sum(lost, axis=-1) > 1.0
    return collisions, dead, regs, over


@dataclasses.dataclass
class TableSegment:
    """CSR tables + per-bucket HLLs, with optional tombstones/external ids.

    With the defaults this is the static core's segment: no dead counts,
    internal ids reported raw.  The streaming main segment supplies
    ``live``/``tomb_counts`` (tombstone-corrected estimates, dead rows
    masked after search) and ``ext_ids`` (external ids reported, with
    ``EXT_SENTINEL`` in masked slots).  A segment with rows flags the
    queries whose probed buckets lose more than one bucket's worth of
    rows to ``cap`` (``over_cap``); an estimate-only one prices
    Algorithm 2 alone.
    """

    tables: LSHTables
    x: Optional[jax.Array] = None       # (n, d) rows; None = estimate-only
    metric: str = "l2"
    cap: int = 64
    live: Optional[jax.Array] = None         # (n + 1,) bool
    tomb_counts: Optional[jax.Array] = None  # (L, B) int32
    ext_ids: Optional[jax.Array] = None      # (n,) int32
    n_live: Optional[Scalar] = None          # defaults to tables.n
    n_scan: Optional[Scalar] = None          # defaults to #rows scanned
    impl: Optional[str] = None
    q_chunk: Optional[int] = None            # None -> min(32, Q)
    tidx: Optional[jax.Array] = None         # (V,) multi-probe column->table

    def estimate_terms(self, qbuckets: jax.Array) -> SegmentEstimate:
        collisions, dead, regs, over = _table_terms(
            self.tables.starts, self.tables.registers, qbuckets, self.tidx,
            self.tomb_counts, cap=None if self.x is None else self.cap)
        n_rows = self.tables.n if self.x is None else self.x.shape[0]
        n_live = self.tables.n if self.n_live is None else self.n_live
        n_scan = n_rows if self.n_scan is None else self.n_scan
        return SegmentEstimate(collisions=collisions, dead_collisions=dead,
                               registers=regs, n_live=n_live, n_scan=n_scan,
                               over_cap=over)

    def search(self, qbuckets: jax.Array, q: jax.Array, r, *,
               lsh_route: bool):
        assert self.x is not None, "estimate-only segment has no rows"
        n = self.x.shape[0]
        if lsh_route:
            qc = self.q_chunk or min(32, q.shape[0])
            ids, dists, mask = search_lib.lsh_search(
                self.x, self.tables, qbuckets, q, r, self.metric, self.cap,
                q_chunk=qc, tidx=self.tidx, impl=self.impl)
        else:
            ids, dists, mask = search_lib.linear_search(
                self.x, q, r, self.metric, impl=self.impl)
        if self.live is not None or self.ext_ids is not None:
            safe = jnp.clip(ids, 0, n - 1)
            if self.live is not None:
                mask = mask & self.live[safe]
            if self.ext_ids is not None:
                ids = jnp.where(mask, self.ext_ids[safe], EXT_SENTINEL)
        return ids, dists, mask

    def count_candidates(self, qbuckets: jax.Array) -> jax.Array:
        """(Q,) distinct candidates the LSH route gathers (cap-truncated,
        tombstoned rows included — they cost gather + verification)."""
        return search_lib.lsh_candidate_counts(self.tables, qbuckets,
                                               self.cap, tidx=self.tidx)


# ---------------------------------------------------------------------------
# Query result + host-side partitioning helpers
# ---------------------------------------------------------------------------
PAIRS_FLOOR = 1 << 16   # shortest packed buffer, in (id, dist) pairs


def packed_length(total: int) -> int:
    """Length of a group's packed buffers for ``total`` reported pairs:
    a power of two, at least ``PAIRS_FLOOR``.  Each length is one
    compile of ``_pack``, so the set of lengths stays small."""
    return max(PAIRS_FLOOR, 1 << max(total - 1, 0).bit_length())


def _first_rows(idx: np.ndarray) -> np.ndarray:
    """(G,) bool: the group row is its query's first.  The power-of-two
    padding repeats a query already in the group; it is never read."""
    idx = np.asarray(idx)
    first = np.zeros(idx.shape, bool)
    first[np.unique(idx, return_index=True)[1]] = True
    return first


@functools.partial(jax.jit, static_argnames=("n_queries",))
def _row_counts(masks, idxs, firsts, *, n_queries: int) -> jax.Array:
    """(n_queries,) reported pairs of each query, in query order."""
    counts = jnp.zeros(n_queries, jnp.int32)
    for mask, idx, first in zip(masks, idxs, firsts):
        counts = counts.at[jnp.where(first, idx, n_queries)].set(
            jnp.sum(mask, axis=-1, dtype=jnp.int32), mode="drop")
    return counts


@functools.partial(jax.jit, static_argnames=("lengths", "impl"))
def _pack(groups, firsts, *, lengths, impl=None):
    """Each group's reported (id, dist) pairs of its first rows, in row
    order and each row's in buffer order, in flat buffers of its
    length (``ops.pack_reported``)."""
    return [ops.pack_reported(ids, dists, mask & first[:, None], length,
                              impl=impl)
            for (ids, dists, mask), first, length
            in zip(groups, firsts, lengths)]


@dataclasses.dataclass
class QueryResult:
    """Per-strategy buffers + per-query bookkeeping.

    ``reported(i)``/``neighbors(i)`` return query i's answer regardless
    of which strategy served it.  The first such call brings the whole
    result to the host at once, packed on the device (one read of the
    per-query counts, one of every group's packed pairs); every call
    after it slices the host copy.  ``batch`` is the engine's call
    number, stamped on the extraction spans so they join the query's
    spans; ``impl`` the engine's kernel override.
    """

    route: RouteEstimate
    lsh_idx: np.ndarray          # query indices served by LSH search
    lin_idx: np.ndarray          # query indices served by linear search
    lsh_out: Optional[tuple]     # (ids, dists, mask) for the LSH group
    lin_out: Optional[tuple]     # (ids, dists, mask) for the linear group
    n_queries: int
    batch: int = 0
    impl: Optional[str] = None
    # host copy: ([(ids, dists)] a group, (n_queries, 3) rows of
    # (group, start, end)); None until the first extraction
    _host: Optional[tuple] = dataclasses.field(default=None, init=False,
                                               repr=False)

    def _route(self, i: int) -> str:
        """The route that served query ``i``."""
        for route, idx, out in (("lsh", self.lsh_idx, self.lsh_out),
                                ("linear", self.lin_idx, self.lin_out)):
            if out is not None and np.any(np.asarray(idx) == i):
                return route
        raise KeyError(i)

    def _fetch(self) -> int:
        """Pack every query's answer on the device and copy the whole
        result to the host in one transfer; returns the bytes moved."""
        groups, idxs = [], []
        for idx, out in ((self.lsh_idx, self.lsh_out),
                         (self.lin_idx, self.lin_out)):
            if out is not None:
                groups.append(tuple(out))
                idxs.append(np.asarray(idx, np.int32))
        firsts = [_first_rows(idx) for idx in idxs]
        with TraceAnnotation("repro.result.copy.sync", batch=self.batch,
                             reads=2):
            with TraceAnnotation("repro.result.compact",
                                 batch=self.batch) as span:
                counts = np.asarray(_row_counts(
                    [g[2] for g in groups], idxs, firsts,
                    n_queries=self.n_queries))
                row_counts = [np.where(f, counts[idx], 0)
                              for idx, f in zip(idxs, firsts)]
                lengths = tuple(packed_length(int(c.sum()))
                                for c in row_counts)
                packed = _pack(groups, firsts, lengths=lengths,
                               impl=self.impl)
                span.set_metadata(pairs=int(counts.sum()),
                                  padded_pairs=sum(lengths))
            packed = jax.device_get(packed)
        where = np.zeros((self.n_queries, 3), np.int64)
        for g, (idx, first, c) in enumerate(zip(idxs, firsts, row_counts)):
            start = np.cumsum(c) - c
            where[idx[first]] = np.stack(
                [np.full(first.sum(), g), start[first],
                 (start + c)[first]], axis=1)
        for pair in packed:
            for a in pair:
                a.flags.writeable = False     # callers share the copy
        self._host = (packed, where)
        return counts.nbytes + sum(a.nbytes for p in packed for a in p)

    def neighbors(self, i: int) -> np.ndarray:
        return self.reported(i)[0]

    def reported(self, i: int):
        """(ids, dists) reported for query ``i`` — ``neighbors`` plus
        the distances, the pair the serving result cache stores.

        Read-only views of the host copy: ``bytes`` on the span is what
        this call moved from the device (the whole result on the first
        call, 0 after), ``reported`` the pairs query ``i`` holds."""
        with TraceAnnotation("repro.result.reported",
                             batch=self.batch) as span:
            route = self._route(i)
            moved = self._fetch() if self._host is None else 0
            packed, where = self._host
            g, lo, hi = where[i]
            ids, dists = packed[g]
            span.set_metadata(route=route, reported=int(hi - lo),
                              bytes=moved)
            return ids[lo:hi], dists[lo:hi]

    def neighbor_sets(self):
        return {i: set(self.neighbors(i).tolist())
                for i in range(self.n_queries)}

    @property
    def n_linear(self) -> int:
        """Exact count of queries served by linear search.

        ``lin_idx`` is power-of-two padded by repeating its last entry,
        so the raw length over-counts — dedup gives the true count.
        """
        return len(set(np.asarray(self.lin_idx).tolist()))

    @property
    def frac_linear(self) -> float:
        return self.n_linear / max(self.n_queries, 1)


def _pad_size(k: int, minimum: int = 8) -> int:
    """Round group sizes up to powers of two: bounded jit-cache churn."""
    if k == 0:
        return 0
    return max(minimum, 1 << (k - 1).bit_length())


def partition_indices(use_lsh: np.ndarray,
                      minimum: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """Split query indices into (lsh_idx, linear_idx), each padded to a
    power-of-two length by repeating the last index (results for padded
    slots are discarded by the caller)."""
    use_lsh = np.asarray(use_lsh)
    lsh_idx = np.nonzero(use_lsh)[0]
    lin_idx = np.nonzero(~use_lsh)[0]

    def pad(idx):
        tgt = _pad_size(len(idx), minimum)
        if tgt == 0:
            return idx.astype(np.int32)
        out = np.full(tgt, idx[-1] if len(idx) else 0, np.int32)
        out[:len(idx)] = idx
        return out

    return pad(lsh_idx), pad(lin_idx)


def compact_results(ids: jax.Array, dists: jax.Array, mask: jax.Array,
                    max_out: int):
    """Compact sentinel-padded (Q, C) results to fixed (Q, max_out).

    Keeps the ``max_out`` nearest reported neighbors per query (exact
    whenever the true output size <= max_out).
    """
    key = jnp.where(mask, dists, jnp.inf)
    neg, pos = jax.lax.top_k(-key, max_out)
    take = jnp.take_along_axis
    return (take(ids, pos, axis=-1), -neg,
            take(mask, pos, axis=-1) & jnp.isfinite(-neg))


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class QueryEngine:
    """Owns the hybrid pipeline once, for any list of segments.

    ``estimate``/``search_group`` are pure traced functions — the
    sharded indexes call them inside ``shard_map`` and merge the terms
    across shards themselves; ``query`` is the host-side single-host
    pipeline that additionally partitions the batch.
    """

    def __init__(self, cost_model: CostModel, impl: Optional[str] = None,
                 tracer=None):
        """Args: ``cost_model`` — Algorithm 2 constants (alpha, beta);
        ``impl`` — kernel impl override (e.g. ``"pallas_interpret"``);
        ``tracer`` — optional ``repro.obs.QueryTracer`` (duck-typed, the
        engine never imports obs).  ``query`` calls into it only while
        ``tracer.enabled`` is true."""
        self.cost_model = cost_model
        self.impl = impl
        self.tracer = tracer
        self._batches = 0

    # traceable pieces (also used inside shard_map by the sharded paths)
    def estimate(self, segments: Sequence[Segment],
                 qbuckets: jax.Array) -> RouteEstimate:
        """Algorithm 2 lines 1-4 over the whole segment list.

        Args:
          segments: engine segments (frozen levels + delta, any length).
          qbuckets: (Q, L) int query buckets — or (Q, V) virtual-table
            columns under multi-probe.

        Returns the vectorized ``RouteEstimate`` (all fields (Q,) except
        the scalar ``linear_cost``)."""
        return finalize_route([s.estimate_terms(qbuckets) for s in segments],
                              self.cost_model, impl=self.impl)

    def search_group(self, segments: Sequence[Segment], qbuckets: jax.Array,
                     q: jax.Array, r, *, lsh_route: bool, batch: int = 0):
        """Search every segment for one routed group; concat the buffers.

        Args:
          qbuckets/q: (G, L) buckets and (G, d) rows of the group.
          r: report radius; ``lsh_route`` picks the strategy.
          batch: the engine call number the segment spans carry (under
            ``jit``/``shard_map`` the spans mark tracing, not running).

        Returns sentinel-padded ``(ids, dists, mask)``, each (G, C) with
        C the concatenation of the per-segment output widths."""
        route = "lsh" if lsh_route else "linear"
        parts = []
        for i, s in enumerate(segments):
            with TraceAnnotation("repro.engine.segment", batch=batch,
                                 route=route, segment=i):
                parts.append(s.search(qbuckets, q, r, lsh_route=lsh_route))
        if len(parts) == 1:
            return parts[0]
        return tuple(jnp.concatenate([p[i] for p in parts], axis=-1)
                     for i in range(3))

    # host-side pipeline (single-host indexes)
    def next_batch(self) -> int:
        """Number the next ``query`` call (its spans carry it)."""
        self._batches += 1
        return self._batches

    def query(self, segments: Sequence[Segment], queries: jax.Array,
              qbuckets: jax.Array, r: float,
              force: Optional[str] = None, *,
              batch: Optional[int] = None) -> QueryResult:
        """Hybrid r-NN reporting over the segments.

        Args:
          segments: engine segments, any length.
          queries: (Q, d) rows; ``qbuckets``: (Q, L) their buckets.
          r: report radius (every returned neighbor has dist <= r).
          force: None (hybrid routing) | "lsh" | "linear" — the two
            baselines of the paper's Figure 2.
          batch: the call number from ``next_batch`` (a caller that
            opens its own spans first takes one); None takes the next.

        Returns a ``QueryResult``; ``neighbors(i)``/``neighbor_sets()``
        extract reported ids regardless of which strategy served each
        query.  With an enabled tracer every batch counts its routes and
        every ``sample_every``-th also prices its misroutes.
        """
        batch = self.next_batch() if batch is None else batch
        tracer = self.tracer
        traced = tracer is not None and tracer.enabled
        sampled = traced and tracer.sample()
        nq = queries.shape[0]
        with TraceAnnotation("repro.engine.estimate", batch=batch,
                             segments=len(segments)):
            route = self.estimate(segments, qbuckets)
        if force == "lsh":
            use = np.ones(nq, bool)
        elif force == "linear":
            use = np.zeros(nq, bool)
        else:
            with TraceAnnotation("repro.engine.route.sync", batch=batch,
                                 reads=1) as span:
                use = np.asarray(route.use_lsh)
                n_lsh = int(np.count_nonzero(use))
                span.set_metadata(lsh_rows=n_lsh, linear_rows=nq - n_lsh)
        if traced:
            tracer.count_routes(use)
        lsh_idx, lin_idx = partition_indices(use)

        def group(idx, lsh_route):
            if not len(idx):
                return None
            with TraceAnnotation(
                    "repro.engine.search", batch=batch,
                    route="lsh" if lsh_route else "linear",
                    rows=int(np.count_nonzero(use == lsh_route)),
                    padded_rows=len(idx)) as span:
                out = self.search_group(segments, qbuckets[idx],
                                        queries[idx], float(r),
                                        lsh_route=lsh_route, batch=batch)
                span.set_metadata(width=out[0].shape[-1])
            return out

        lsh_out = group(lsh_idx, True)
        lin_out = group(lin_idx, False)
        if sampled:
            self._record_misroutes(segments, qbuckets, route, use, force,
                                   batch)
        return QueryResult(route=route, lsh_idx=lsh_idx, lin_idx=lin_idx,
                           lsh_out=lsh_out, lin_out=lin_out, n_queries=nq,
                           batch=batch, impl=self.impl)

    def count_candidates(self, segments: Sequence[Segment],
                         qbuckets: jax.Array) -> jax.Array:
        """(Q,) distinct candidates the LSH route gathers, summed over
        segments (segments hold disjoint docs, so the sum is exact)."""
        total = segments[0].count_candidates(qbuckets)
        for s in segments[1:]:
            total = total + s.count_candidates(qbuckets)
        return total

    def _record_misroutes(self, segments: Sequence[Segment],
                          qbuckets: jax.Array, route: RouteEstimate,
                          use: np.ndarray, force: Optional[str],
                          batch: int) -> None:
        """A sampled batch: price the actual candidate set (real device
        work) and fold the batch into the tracer's misroute spans."""
        with TraceAnnotation("repro.engine.misroute.sync", batch=batch,
                             reads=4):
            cand_actual = np.asarray(self.count_candidates(segments,
                                                           qbuckets))
            coll = np.asarray(route.collisions).astype(np.float64)
            cand_est = np.asarray(route.cand_est).astype(np.float64)
            lsh_cost_est = np.asarray(route.lsh_cost).astype(np.float64)
        self.tracer.record_batch(
            use_lsh=use,
            collisions=coll,
            cand_est=cand_est,
            cand_actual=cand_actual,
            lsh_cost_est=lsh_cost_est,
            lsh_cost_actual=np.asarray(self.cost_model.lsh_cost(
                coll, cand_actual.astype(np.float64))),
            linear_cost=float(route.linear_cost),
            probes=int(qbuckets.shape[1]),
            forced=force,
            kernel_impl=ops.resolve_impl(self.impl))


# ---------------------------------------------------------------------------
# Compatibility wrappers (the pre-engine estimator entry points)
# ---------------------------------------------------------------------------
def estimate_routes(tables: LSHTables, qbuckets: jax.Array,
                    cost_model: CostModel, n: int,
                    impl: Optional[str] = None) -> RouteEstimate:
    """O(m*L) per query, independent of bucket sizes (the paper's point)."""
    seg = TableSegment(tables=tables, n_live=n, n_scan=n)
    return finalize_route([seg.estimate_terms(qbuckets)], cost_model,
                          impl=impl)


def estimate_routes_dynamic(tables: LSHTables, qbuckets: jax.Array,
                            cost_model: CostModel, n_live: int, *,
                            tomb_counts: jax.Array,
                            delta_collisions: jax.Array,
                            delta_distinct: jax.Array,
                            n_scan: Optional[int] = None,
                            impl: Optional[str] = None) -> RouteEstimate:
    """Tombstone-corrected Algorithm 2 for a main+delta segment pair."""
    main = TableSegment(tables=tables, tomb_counts=tomb_counts)
    delta = SegmentEstimate(collisions=delta_collisions,
                            cand_exact=delta_distinct)
    return finalize_route([main.estimate_terms(qbuckets), delta], cost_model,
                          impl=impl, n_live=n_live,
                          n_scan=n_live if n_scan is None else n_scan)
