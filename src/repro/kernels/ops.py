"""Public jit'd wrappers around the Pallas kernels.

Handles (a) padding inputs to tile multiples and slicing outputs back,
(b) backend dispatch: on TPU -> compiled Pallas kernels, elsewhere ->
the pure-jnp oracles in ``ref.py`` (Pallas ``interpret=True`` is for
correctness tests, not speed).  Callers may force a backend with
``impl=`` ("pallas", "pallas_interpret", "ref", None = auto).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import compact as _compact
from repro.kernels import distances as _dist
from repro.kernels import fused_scan as _fs
from repro.kernels import hamming as _ham
from repro.kernels import hll_merge as _hllm
from repro.kernels import ref as _ref
from repro.kernels import simhash as _sim

__all__ = ["pairwise_dist", "hamming_dist", "simhash_fingerprint",
           "hll_merge_estimate", "pad_to", "metric_radius_transform",
           "fused_linear_scan", "fused_lsh_scan", "pack_reported",
           "resolve_impl"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    return "pallas" if _on_tpu() else "ref"


def resolve_impl(impl: Optional[str] = None) -> str:
    """The backend an ``impl=`` override actually dispatches to (public:
    the tracer labels per-route kernel timings with this)."""
    return _resolve(impl)


def pad_to(x: jax.Array, mult: int, axis: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _dot_tiles(nq: int, d: int, interpret: bool):
    """(tq, tn, td) for the MXU distance kernels.

    A small query batch (the 32-row chunks of ``linear_search``) gets a
    query tile just big enough for it, in multiples of 32 (the int8
    mask's sublane tile), instead of being padded to 256 rows; ``d``
    pads only to the next multiple of 128 lanes, so a 128-wide corpus
    is never copied to 256 columns.  Interpret mode keeps 128 tiles.
    """
    cap = 128 if interpret else _dist.DEFAULT_TQ
    tq = min(cap, _round_up(nq, 32))
    tn = min(_dist.DEFAULT_TN, cap)
    td = min(_dist.DEFAULT_TD, cap, _round_up(d, 128))
    return tq, tn, td


def metric_radius_transform(metric: str, r: float) -> float:
    """Map a user radius to the raw-kernel comparison value.

    The L2 kernels return *squared* distances, so the threshold is r^2;
    other metrics are identity.
    """
    return r * r if metric == "l2" else r


def pairwise_dist(q: jax.Array, x: jax.Array, metric: str,
                  impl: Optional[str] = None) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N) float32 distances.

    NOTE: metric "l2" returns SQUARED L2 (compare against r^2 via
    ``metric_radius_transform``) — avoids a full-matrix sqrt on the scan.
    """
    impl = _resolve(impl)
    if impl == "ref":
        if metric == "l2":
            return _ref.pairwise_sql2(q, x)
        if metric == "l1":
            return _ref.pairwise_l1(q, x)
        if metric == "cosine":
            return _ref.pairwise_cosine(q, x)
        raise ValueError(metric)

    interpret = impl == "pallas_interpret"
    nq, nn = q.shape[0], x.shape[0]
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)

    if metric in ("l2", "cosine"):
        tq, tn, td = _dot_tiles(nq, q.shape[1], interpret)
        qp = pad_to(pad_to(q, tq, 0), td, 1)
        xp = pad_to(pad_to(x, tn, 0), td, 1)
        qn = jnp.sum(qp.astype(jnp.float32) ** 2, axis=-1)
        xn = jnp.sum(xp.astype(jnp.float32) ** 2, axis=-1)
        out = _dist.pairwise_dot_pallas(
            qp, xp, qn, xn, mode="l2" if metric == "l2" else "cosine",
            tq=tq, tn=tn, td=td, interpret=interpret)
        out = out[:nq, :nn]
        return jnp.maximum(out, 0.0) if metric == "l2" else out
    if metric == "l1":
        tq = tn = td = 128
        qp = pad_to(pad_to(q, tq, 0), td, 1)
        xp = pad_to(pad_to(x, tn, 0), td, 1)
        return _dist.pairwise_l1_pallas(qp, xp, tq=tq, tn=tn, td=td,
                                        interpret=interpret)[:nq, :nn]
    raise ValueError(metric)


def hamming_dist(qc: jax.Array, xc: jax.Array,
                 impl: Optional[str] = None) -> jax.Array:
    """(Q, W) x (N, W) packed uint32 -> (Q, N) int32 Hamming distances."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.hamming(qc, xc)
    interpret = impl == "pallas_interpret"
    nq, nn = qc.shape[0], xc.shape[0]
    tq = tn = 128
    qp = pad_to(qc, tq, 0)
    xp = pad_to(xc, tn, 0)
    return _ham.hamming_pallas(qp, xp, tq=tq, tn=tn,
                               interpret=interpret)[:nq, :nn]


def pad_projection(r: jax.Array, L: int, k: int) -> jax.Array:
    """(d, L*k) projection -> (d, L*words*32) zero-padded per table."""
    d = r.shape[0]
    words = (k + 31) // 32
    r = r.reshape(d, L, k)
    r = jnp.pad(r, ((0, 0), (0, 0), (0, words * 32 - k)))
    return r.reshape(d, L * words * 32)


def simhash_fingerprint(x: jax.Array, r: jax.Array, L: int, k: int,
                        impl: Optional[str] = None) -> jax.Array:
    """(N, d) points, (d, L*k) projections -> (N, L, ceil(k/32)) u32."""
    impl = _resolve(impl)
    words = (k + 31) // 32
    rp = pad_projection(r, L, k)
    if impl == "ref":
        return _ref.simhash_fingerprint(x, rp, L, words)
    interpret = impl == "pallas_interpret"
    n = x.shape[0]
    tn = 128
    xp = pad_to(x, tn, 0)
    return _sim.simhash_pallas(xp, rp, L=L, words=words, tn=tn,
                               interpret=interpret)[:n]


def fused_linear_scan(q: jax.Array, x: jax.Array, r, metric: str,
                      impl: Optional[str] = None):
    """Fused linear-route scan: distance + threshold + report mask +
    candidate ids in ONE kernel pass over (Q, N) tiles.

    q: (Q, d) queries ((Q, W) packed u32 codes for hamming); x: (N, d)
    corpus ((N, W) for hamming); r: report radius (traced OK).
    Returns (ids (Q, N) i32, dists (Q, N) f32, mask (Q, N) bool) —
    identical to the composed ``pairwise_dist`` -> compare ->
    broadcast-ids pipeline, without materializing the intermediates.
    """
    impl = _resolve(impl)
    thresh = metric_radius_transform(metric, r)
    if impl == "ref":
        return _ref.fused_linear_scan(q, x, thresh, metric)
    interpret = impl == "pallas_interpret"
    t = jnp.full((1, 1), thresh, jnp.float32)
    nq, nn = q.shape[0], x.shape[0]
    sl = lambda a: a[:nq, :nn]
    if metric == "hamming":
        tq = tn = 128
        d_i, m, i = _fs.linear_scan_hamming_pallas(
            t, pad_to(q, tq, 0), pad_to(x, tn, 0), tq=tq, tn=tn,
            interpret=interpret)
        return sl(i), sl(d_i).astype(jnp.float32), sl(m).astype(bool)
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    if metric in ("l2", "cosine"):
        tq, tn, td = _dot_tiles(nq, q.shape[1], interpret)
        qp = pad_to(pad_to(q, tq, 0), td, 1)
        xp = pad_to(pad_to(x, tn, 0), td, 1)
        qn = jnp.sum(qp.astype(jnp.float32) ** 2, axis=-1)
        xn = jnp.sum(xp.astype(jnp.float32) ** 2, axis=-1)
        dd, m, i = _fs.linear_scan_dot_pallas(
            t, qp, xp, qn, xn, mode="l2" if metric == "l2" else "cosine",
            tq=tq, tn=tn, td=td, interpret=interpret)
        return sl(i), sl(dd), sl(m).astype(bool)
    if metric == "l1":
        tq = tn = td = 128
        qp = pad_to(pad_to(q, tq, 0), td, 1)
        xp = pad_to(pad_to(x, tn, 0), td, 1)
        dd, m, i = _fs.linear_scan_l1_pallas(t, qp, xp, tq=tq, tn=tn, td=td,
                                             interpret=interpret)
        return sl(i), sl(dd), sl(m).astype(bool)
    raise ValueError(metric)


def fused_lsh_scan(x: jax.Array, ids_sorted: jax.Array, q: jax.Array, r,
                   metric: str, impl: Optional[str] = None):
    """Fused LSH-route candidate verification: sorted-run dedup + row
    gather + rowwise distance + threshold in ONE kernel pass over the
    (Q, C) candidate tiles — the composed ``dedupe_sorted`` ->
    ``x[ids]`` -> ``rowwise_dist`` -> compare chain without the
    (Q, C, d) gathered-rows materialization.

    x: (n, d) corpus ((n, W) packed u32 for hamming); ids_sorted:
    (Q, C) *sorted* candidate ids with sentinel = n (the int32 sort is
    the caller's — it is the cheap d-independent stage); q: (Q, d).
    Returns (ids (Q, C) i32, dists (Q, C) f32, mask (Q, C) bool) with
    duplicates, sentinels, and out-of-radius rows masked.
    """
    impl = _resolve(impl)
    thresh = metric_radius_transform(metric, r)
    n = x.shape[0]
    prev = jnp.concatenate(
        [jnp.full(ids_sorted.shape[:-1] + (1,), -1, ids_sorted.dtype),
         ids_sorted[..., :-1]], axis=-1)
    if impl == "ref":
        return _ref.fused_lsh_scan(x, ids_sorted, prev, q, thresh, metric)
    interpret = impl == "pallas_interpret"
    t = jnp.full((1, 1), thresh, jnp.float32)
    nq, c = ids_sorted.shape
    tq, tc = _fs.LSH_TQ, _fs.LSH_TC
    sent = jnp.int32(n)
    ids_p = pad_to(pad_to(ids_sorted.astype(jnp.int32), tq, 0, value=sent),
                   tc, 1, value=sent)
    prev_p = pad_to(pad_to(prev.astype(jnp.int32), tq, 0, value=sent),
                    tc, 1, value=sent)
    # corpus rows 8-aligned, lanes 128-aligned (zeros: norms unaffected,
    # XOR-popcount unaffected; gathers are clipped to the real n rows)
    xp = pad_to(pad_to(x, 8, 0), 128, 1)
    qp = pad_to(pad_to(q, tq, 0), 128, 1)
    dd, m = _fs.lsh_scan_pallas(t, xp, qp, ids_p, prev_p, metric=metric,
                                n=n, tq=tq, tc=tc, interpret=interpret)
    return ids_sorted, dd[:nq, :c], m[:nq, :c].astype(bool)


def hll_merge_estimate(regs: jax.Array,
                       impl: Optional[str] = None) -> jax.Array:
    """(Q, L, m) uint8 registers -> (Q,) float32 candSize estimates."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.hll_merge_estimate(regs)
    interpret = impl == "pallas_interpret"
    q = regs.shape[0]
    tq = 8 if interpret else 64
    rp = pad_to(regs, tq, 0)
    return _hllm.hll_merge_estimate_pallas(rp, tq=tq,
                                           interpret=interpret)[:q]


def pack_reported(ids: jax.Array, dists: jax.Array, mask: jax.Array,
                  length: int, impl: Optional[str] = None):
    """Every ``mask`` slot's (id, dist) of (G, W) buffers, row-major, in
    flat (length,) buffers, zero past them: a route group's answers
    without its padding.  ``length`` >= the number of slots."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.pack_reported(ids, dists, mask, length)
    rows = 8 if impl == "pallas_interpret" else _compact.TILE_ROWS
    lanes = _compact.LANES

    def tiles(a):       # (G, W) -> (n, 128), n a multiple of ``rows``
        a = pad_to(a.astype(jnp.int32), lanes, 1).reshape(-1, lanes)
        return pad_to(a, rows, 0)

    m = tiles(mask)
    count = jnp.sum(m, axis=1)
    end = jnp.cumsum(count)
    out_i, out_d = _compact.pack_pallas(
        (end - count).reshape(-1, 1, rows), m, tiles(ids),
        tiles(jax.lax.bitcast_convert_type(dists, jnp.int32)),
        length=_round_up(length, lanes), rows=rows,
        interpret=impl == "pallas_interpret")
    # the kernel writes the output rows it filled; zero the rest
    tail = jnp.arange(length) >= end[-1]
    return (jnp.where(tail, 0, out_i[:length]),
            jax.lax.bitcast_convert_type(
                jnp.where(tail, 0, out_d[:length]), dists.dtype))
