"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references (``assert_allclose`` targets in
tests) AND the CPU execution path: on the CPU container the ops layer
dispatches here, while on TPU it dispatches to the Pallas kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_UINT = jnp.uint32


def popcount_u32(v: jax.Array) -> jax.Array:
    """Classic SWAR popcount for uint32."""
    v = v.astype(_UINT)
    v = v - ((v >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return ((v * np.uint32(0x01010101)) >> 24).astype(jnp.int32)


def pairwise_sql2(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 distances, (Q, d) x (N, d) -> (Q, N) float32.

    MXU-friendly decomposition ||q||^2 - 2<q,x> + ||x||^2 (this is the
    exact form the Pallas kernel tiles).
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1)
    xn = jnp.sum(x * x, axis=-1)
    d = qn[:, None] + xn[None, :] - 2.0 * (q @ x.T)
    return jnp.maximum(d, 0.0)


def pairwise_l1(q: jax.Array, x: jax.Array) -> jax.Array:
    """L1 distances, (Q, d) x (N, d) -> (Q, N) float32."""
    return jnp.sum(jnp.abs(q.astype(jnp.float32)[:, None, :]
                           - x.astype(jnp.float32)[None, :, :]), axis=-1)


def pairwise_cosine(q: jax.Array, x: jax.Array) -> jax.Array:
    """Cosine distances 1 - cos(q, x), (Q, d) x (N, d) -> (Q, N)."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    return 1.0 - qn @ xn.T


def rowwise_dist(rows: jax.Array, q: jax.Array, metric: str) -> jax.Array:
    """rows: (..., C, d) candidates vs q: (..., d) -> (..., C) distances.

    The candidate-verification math (gather-bound, plain VPU ops; L2
    returns squared distance, consistent with pairwise_sql2).  This is
    the expression the fused LSH-route kernel replicates per tile —
    ``core.search.rowwise_dist`` delegates here.
    """
    if metric == "hamming":
        x = rows.astype(_UINT) ^ q[..., None, :].astype(_UINT)
        return jnp.sum(popcount_u32(x), axis=-1).astype(jnp.float32)
    rows = rows.astype(jnp.float32)
    q = q.astype(jnp.float32)[..., None, :]
    if metric == "l2":
        d = rows - q
        return jnp.sum(d * d, axis=-1)
    if metric == "l1":
        return jnp.sum(jnp.abs(rows - q), axis=-1)
    if metric == "cosine":
        rn = rows / jnp.maximum(
            jnp.linalg.norm(rows, axis=-1, keepdims=True), 1e-12)
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True),
                             1e-12)
        return 1.0 - jnp.sum(rn * qn, axis=-1)
    raise ValueError(metric)


def fused_linear_scan(q: jax.Array, x: jax.Array, thresh,
                      metric: str):
    """Oracle for the fused linear-route scan: the composed pipeline
    (pairwise distance -> threshold -> broadcast ids) as plain jnp.
    Returns (ids, dists, mask), each (Q, N); ``thresh`` is already
    radius-transformed (r^2 for l2)."""
    if metric == "hamming":
        dists = hamming(q, x).astype(jnp.float32)
    elif metric == "l2":
        dists = pairwise_sql2(q, x)
    elif metric == "l1":
        dists = pairwise_l1(q, x)
    elif metric == "cosine":
        dists = pairwise_cosine(q, x)
    else:
        raise ValueError(metric)
    mask = dists <= thresh
    ids = jnp.broadcast_to(jnp.arange(x.shape[0], dtype=jnp.int32),
                           dists.shape)
    return ids, dists, mask


def fused_lsh_scan(x: jax.Array, ids_sorted: jax.Array, prev: jax.Array,
                   q: jax.Array, thresh, metric: str):
    """Oracle for the fused LSH-route scan: sorted-run dedup -> row
    gather -> rowwise distance -> threshold, as plain jnp.

    ids_sorted: (Q, C) sorted candidate ids with sentinel = x.shape[0];
    prev: ids_sorted shifted right one slot (prev[..., 0] = -1), so
    ``ids != prev`` marks run starts — identical to
    ``core.search.dedupe_sorted``'s first-occurrence mask on sorted
    input.  Returns (ids_sorted, dists, mask), each (Q, C).
    """
    n = x.shape[0]
    uniq = (ids_sorted != prev) & (ids_sorted < n)
    rows = x[jnp.clip(ids_sorted, 0, n - 1)]             # (Q, C, d)
    dists = rowwise_dist(rows, q, metric)
    mask = uniq & (dists <= thresh)
    return ids_sorted, dists, mask


def hamming(qc: jax.Array, xc: jax.Array) -> jax.Array:
    """Hamming distances over packed codes, (Q, W) x (N, W) -> (Q, N) i32."""
    x = qc.astype(_UINT)[:, None, :] ^ xc.astype(_UINT)[None, :, :]
    return jnp.sum(popcount_u32(x), axis=-1, dtype=jnp.int32)


def simhash_fingerprint(x: jax.Array, r_padded: jax.Array, L: int,
                        words: int) -> jax.Array:
    """SimHash fingerprints, (N, d) x (d, L*words*32) -> (N, L, words) u32.

    ``r_padded`` has zero columns beyond the family's true k bits per
    table (zero projection -> bit 0, matching families._pack_bits).
    """
    proj = x.astype(jnp.float32) @ r_padded.astype(jnp.float32)
    bits = (proj > 0).reshape(x.shape[0], L, words, 32).astype(_UINT)
    powers = jnp.asarray(np.uint32(1), _UINT) << jnp.arange(32, dtype=_UINT)
    return jnp.sum(bits * powers, axis=-1, dtype=_UINT)


def hll_merge_estimate(regs: jax.Array) -> jax.Array:
    """Merge (Q, L, m) registers over L and estimate cardinality -> (Q,).

    Must match repro.core.hll exactly (merge + estimator with
    small/large-range corrections).
    """
    from repro.core import hll as hll_lib
    merged = hll_lib.merge_registers(regs.astype(jnp.int32), axis=1)
    return hll_lib.estimate_cardinality(merged, int(regs.shape[-1]))


def pack_reported(ids: jax.Array, dists: jax.Array, mask: jax.Array,
                  length: int):
    """(G, W) buffers -> (length,) ids and dists of the ``mask`` slots,
    row-major, zero past them: each slot scattered to its rank among
    the reported slots."""
    rank = jnp.cumsum(mask.ravel(), dtype=jnp.int32) - 1
    slot = jnp.where(mask.ravel(), rank, length)
    out = [jnp.zeros(length, a.dtype).at[slot].set(a.ravel(), mode="drop",
                                                   unique_indices=True)
           for a in (ids, dists)]
    return out[0], out[1]
