"""The two search strategies the hybrid router chooses between.

Both are batched, fixed-shape, jittable functions (TPU execution model),
and both now run through the fused Pallas scan kernels
(``kernels/fused_scan.py``) behind the ``ops`` dispatch:

  * ``linear_search``     — fused brute-force scan (Eq. 2 cost):
                            distance + threshold + report mask + ids in
                            one kernel pass over (Q, N) tiles.
  * ``lsh_search``        — fixed-capacity bucket gather, then the fused
                            verification kernel: sorted-run dedup +
                            row gather + rowwise distance + threshold
                            over (Q, C) candidate tiles (Eq. 1 cost:
                            alpha-term = gather+dedup, beta-term =
                            verification).

On non-TPU backends (and under ``impl="ref"``) both dispatch to the
composed jnp oracles in ``kernels/ref.py`` — same results, bit-exact.

Reporting semantics: every function returns ``(ids, dists, mask)`` where
``mask[q, i]`` marks a reported r-near neighbor of query q.  Buffers are
sentinel-padded; ``mask`` already excludes padding.

Query batches are processed in fixed ``q_chunk`` slices so the
per-chunk working set stays bounded; batches that are not a chunk
multiple are padded up and the results sliced back (a 33-query batch
runs as two 32-query chunks, never as one (33, n) buffer).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.lsh.tables import LSHTables, gather_candidates
from repro.kernels import ops
from repro.kernels import ref as _ref

__all__ = ["linear_search", "lsh_search", "lsh_candidate_counts",
           "dedupe_sorted", "rowwise_dist"]


def rowwise_dist(rows: jax.Array, q: jax.Array, metric: str) -> jax.Array:
    """rows: (..., C, d) candidates vs q: (..., d) -> (..., C) distances.

    Used for candidate verification (gather-bound, so plain VPU math;
    the full-scan MXU kernel wouldn't help on already-gathered rows).
    L2 returns squared distance, consistent with ops.pairwise_dist.
    Delegates to ``kernels.ref.rowwise_dist`` — the expression the fused
    LSH-route kernel replicates tile-by-tile.
    """
    return _ref.rowwise_dist(rows, q, metric)


def dedupe_sorted(cands: jax.Array, sentinel: int) -> Tuple[jax.Array, jax.Array]:
    """Sort candidate ids and mask duplicates / sentinels.

    cands: (Q, C) int32 with sentinel padding.  Returns (sorted_ids,
    first_occurrence_mask).  This is the TPU replacement for the paper's
    hash-set duplicate removal; its cost is the alpha-term of Eq. (1).
    The fused LSH kernel applies the same run-boundary mask in-kernel
    (``ids != prev``); this helper remains the counting path
    (``lsh_candidate_counts``) and the oracle's reference.
    """
    s = jnp.sort(cands, axis=-1)
    first = jnp.concatenate(
        [jnp.ones(s.shape[:-1] + (1,), bool), s[..., 1:] != s[..., :-1]],
        axis=-1)
    return s, first & (s < sentinel)


def _chunked(chunk_fn, args, nq: int, q_chunk: int, pad_values):
    """Run ``chunk_fn`` over fixed q_chunk slices of per-query arrays.

    Pads every array in ``args`` up to the next chunk multiple (with its
    entry in ``pad_values``) so *no* batch size falls back to the
    full-materialization path, then slices the (nq, ...) results back.
    """
    padded = tuple(ops.pad_to(a, q_chunk, 0, value=v)
                   for a, v in zip(args, pad_values))
    nb = padded[0].shape[0] // q_chunk
    reshaped = tuple(a.reshape(nb, q_chunk, *a.shape[1:]) for a in padded)
    ids, dists, mask = jax.lax.map(
        chunk_fn, reshaped if len(reshaped) > 1 else reshaped[0])
    flat = lambda a: a.reshape(nb * q_chunk, -1)[:nq]
    return flat(ids), flat(dists), flat(mask)


@functools.partial(jax.jit, static_argnames=("metric", "impl", "q_chunk"))
def linear_search(x: jax.Array, q: jax.Array, r: float, metric: str,
                  impl: str | None = None, q_chunk: int = 32):
    """Brute-force scan. Returns (ids (Q,n), dists (Q,n), mask (Q,n)).

    One fused kernel per chunk: distances, threshold compare, report
    mask, and candidate ids leave the kernel together (``ops.
    fused_linear_scan``); the composed pipeline never materializes.
    Queries are processed in chunks of ``q_chunk`` (padded up to a chunk
    multiple when needed) so the kernel's working set stays bounded on
    large corpora; the (Q, n) result buffers are the reporting contract
    and are unchanged.
    """
    def chunk_fn(qq):
        return ops.fused_linear_scan(qq, x, r, metric, impl=impl)

    nq = q.shape[0]
    if q_chunk and nq > q_chunk:
        return _chunked(chunk_fn, (q,), nq, q_chunk, (0,))
    return chunk_fn(q)


@functools.partial(jax.jit, static_argnames=("cap",))
def lsh_candidate_counts(tables: LSHTables, qbuckets: jax.Array, cap: int,
                         tidx: jax.Array | None = None) -> jax.Array:
    """(Q,) distinct candidates ``lsh_search`` would gather per query.

    The observability counterpart of the alpha-term: the same
    fixed-capacity gather + sort-dedup as ``lsh_search``, counting
    instead of verifying — ids only, no row gather, no distance math —
    so a traced query batch can compare the HLL candSize *estimate*
    against the candidates actually scanned (cap-truncated, exactly
    like the search; tombstoned rows included — they are gathered and
    verified, so they are real work).  Per-route *kernel time* for the
    verification itself is in a profiler trace, under the engine's
    ``repro.engine.search`` spans.
    """
    sentinel = tables.n
    cands = gather_candidates(tables, qbuckets, cap, sentinel, tidx=tidx)
    _, uniq = dedupe_sorted(cands, sentinel)
    return jnp.sum(uniq, axis=-1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("metric", "cap", "q_chunk",
                                             "impl"))
def lsh_search(x: jax.Array, tables: LSHTables, qbuckets: jax.Array,
               q: jax.Array, r: float, metric: str, cap: int,
               q_chunk: int = 32, tidx: jax.Array | None = None,
               impl: str | None = None):
    """LSH-based search (steps S2+S3).

    x: (n, d) database rows (or (n, W) packed codes for hamming);
    qbuckets: (Q, V) bucket of each query per probed table (V = L, or
    L*T under multi-probe with ``tidx`` mapping probe columns to
    physical tables); q: (Q, d) queries.
    Returns (ids (Q, V*cap), dists, mask) — deduped, verified.

    Per chunk the candidate ids are sorted (int32, d-independent) and
    handed to the fused verification kernel (``ops.fused_lsh_scan``):
    run-dedup, row gather, rowwise distance, and threshold run in one
    pass over (Q, V*cap) candidate tiles, so the gathered (qc, C, d)
    rows stream through VMEM instead of materializing.  Queries are
    processed in chunks of ``q_chunk`` (padded up to a chunk multiple —
    pad rows carry all-sentinel candidates, so they self-mask).
    """
    n = x.shape[0]
    sentinel = n
    cands = gather_candidates(tables, qbuckets, cap, sentinel,
                              tidx=tidx)                        # (Q, C)

    def chunk_fn(args):
        c, qq = args                                   # (qc, C), (qc, d)
        ids = jnp.sort(c, axis=-1)
        return ops.fused_lsh_scan(x, ids, qq, r, metric, impl=impl)

    nq = q.shape[0]
    if q_chunk and nq > q_chunk:
        return _chunked(chunk_fn, (cands, q), nq, q_chunk, (sentinel, 0))
    return chunk_fn((cands, q))
