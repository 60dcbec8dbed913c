"""Fused Pallas kernels for the two query routes (the candidate scan).

The composed hot path runs distance, threshold, report-mask (and, on
the LSH route, row gather + dedup) as separate XLA kernels, writing the
full ``(Q, N)`` / ``(Q, C, d)`` intermediates to HBM between stages.
These kernels fuse each route into one ``pallas_call`` that streams
candidate blocks through VMEM once — the blockwise Q_CHUNK/K_CHUNK
pattern: fixed-size tiles, online accumulate over the contraction axis,
no full intermediate materialization.

Linear route (``linear_scan_*_pallas``): grid ``(Q/tq, N/tn[, d/td])``
with the contraction axis innermost.  The distance tile accumulates in
the revisited output block (init at ``k == 0``, exactly like
``distances.py``); on the *last* d-block the epilogue applies the L2
clamp, compares against the threshold, and writes the report mask and
the candidate-id tile in place — the separate compare/broadcast-ids
passes of the composed path never touch HBM.

LSH route (``lsh_scan_pallas``): grid ``(Q/tq, C/tc)`` over the sorted
candidate-id tiles.  Per tile the kernel masks duplicate runs and
sentinels (``ids != prev & ids < n`` — the sorted-run half of
``dedupe_sorted``; the (Q, C) int32 sort itself stays an XLA op, it is
the d-independent cheap part), copies each candidate row from the
corpus in HBM into a VMEM scratch by DMA, and runs the rowwise
distance + threshold on the gathered tile.  The composed path's
``(Q, C, d)`` gathered-rows buffer — the dominant HBM traffic of the
route — is never materialized; only the ``(Q, C)`` distances and mask
leave the kernel.

Memory spaces: candidate ids ride twice — an SMEM copy feeding the
scalar DMA addresses and a VMEM copy for the vectorized dedup compare.
The corpus never enters VMEM whole (``memory_space=pl.ANY``): it is
read as 8-row slabs, one per candidate, so a segment of any size
compiles; VMEM holds only the slabs in flight and the ``(tc, d)``
gathered tile.  There is no fallback: on a TPU ``ops.py`` always
dispatches to these kernels.

Thresholds arrive as (1, 1) SMEM scalars (they are traced values — the
radius is a runtime argument).  Masks are written as int8 (TPU-tileable)
and cast to bool by the ``ops`` wrappers; sentinel semantics (internal
sentinel = n) match ``core.search`` bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Linear-route tiles mirror distances.py (MXU-aligned); the LSH route
# tiles the candidate axis at one VREG row of lanes per query row.
DEFAULT_TQ = 256
DEFAULT_TN = 256
DEFAULT_TD = 256
LSH_TQ = 8
LSH_TC = 128
LSH_SLAB_BYTES = 2 << 20    # VMEM for the LSH route's in-flight row slabs


def _popcount_u32(v):
    """SWAR popcount (same as ref.popcount_u32, VPU-friendly)."""
    v = v.astype(jnp.uint32)
    v = v - ((v >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return ((v * np.uint32(0x01010101)) >> 24).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Linear route: distance + threshold + report mask + candidate ids
# ---------------------------------------------------------------------------
def _linear_dot_kernel(t_ref, q_ref, x_ref, qn_ref, xn_ref,
                       dist_ref, mask_ref, ids_ref, *, mode, nk, tn):
    """Accumulate norms - 2 q.x (l2) | 1 - q.x (cosine) over d-blocks;
    epilogue on the last block: clamp, threshold, ids.  Norms arrive as
    a (tq, 1) column and a (1, tn) row: 1-D blocks do not lower."""
    k = pl.program_id(2)
    j = pl.program_id(1)     # read outside @pl.when (interpret-mode rule)

    @pl.when(k == 0)
    def _init():
        if mode == "l2":
            dist_ref[...] = qn_ref[...] + xn_ref[...]
        else:   # cosine: inputs pre-normalized, distance = 1 - dot
            dist_ref[...] = jnp.ones_like(dist_ref)

    # full f32 products: a one-pass bf16 MXU product would move points
    # near the radius across it
    acc = jnp.dot(q_ref[...], x_ref[...].T,
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    scale = 2.0 if mode == "l2" else 1.0
    dist_ref[...] = dist_ref[...] - scale * acc

    @pl.when(k == nk - 1)
    def _report():
        d = dist_ref[...]
        if mode == "l2":
            d = jnp.maximum(d, 0.0)
            dist_ref[...] = d
        mask_ref[...] = (d <= t_ref[0, 0]).astype(jnp.int8)
        ids_ref[...] = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
                        + j * tn)


def _linear_l1_kernel(t_ref, q_ref, x_ref, dist_ref, mask_ref, ids_ref,
                      *, nk, tn):
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        dist_ref[...] = jnp.zeros_like(dist_ref)

    diff = jnp.abs(q_ref[...][:, None, :] - x_ref[...][None, :, :])
    dist_ref[...] = dist_ref[...] + jnp.sum(diff, axis=-1)

    @pl.when(k == nk - 1)
    def _report():
        d = dist_ref[...]
        mask_ref[...] = (d <= t_ref[0, 0]).astype(jnp.int8)
        ids_ref[...] = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
                        + j * tn)


def _linear_hamming_kernel(t_ref, q_ref, x_ref, dist_ref, mask_ref, ids_ref,
                           *, tn):
    """Packed-code XOR + popcount, single shot per (i, j) tile (the code
    width is not blocked — W words fit a tile)."""
    x = q_ref[...][:, None, :] ^ x_ref[...][None, :, :]
    d = jnp.sum(_popcount_u32(x), axis=-1, dtype=jnp.int32)
    dist_ref[...] = d
    mask_ref[...] = (d.astype(jnp.float32) <= t_ref[0, 0]).astype(jnp.int8)
    j = pl.program_id(1)
    ids_ref[...] = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
                    + j * tn)


def _linear_out(nq, nn, tq, tn, dist_dtype):
    specs = [pl.BlockSpec((tq, tn), lambda i, j, k: (i, j))] * 3
    shapes = [jax.ShapeDtypeStruct((nq, nn), dist_dtype),
              jax.ShapeDtypeStruct((nq, nn), jnp.int8),
              jax.ShapeDtypeStruct((nq, nn), jnp.int32)]
    return specs, shapes


@functools.partial(jax.jit, static_argnames=("mode", "tq", "tn", "td",
                                             "interpret"))
def linear_scan_dot_pallas(thresh: jax.Array, q: jax.Array, x: jax.Array,
                           qn: jax.Array, xn: jax.Array, *, mode: str = "l2",
                           tq: int = DEFAULT_TQ, tn: int = DEFAULT_TN,
                           td: int = DEFAULT_TD, interpret: bool = False):
    """Fused (Q, d) x (N, d) -> (dists f32, mask i8, ids i32), all (Q, N).

    Shapes pre-padded (ops.py): Q % tq == N % tn == d % td == 0;
    ``thresh`` is a (1, 1) f32 scalar (r^2 for l2); ``qn``/``xn`` are
    the (Q,)/(N,) squared norms.
    """
    nq, d = q.shape
    nn = x.shape[0]
    assert nq % tq == 0 and nn % tn == 0 and d % td == 0, (q.shape, x.shape)
    grid = (nq // tq, nn // tn, d // td)
    out_specs, out_shape = _linear_out(nq, nn, tq, tn, jnp.float32)
    return pl.pallas_call(
        functools.partial(_linear_dot_kernel, mode=mode, nk=grid[2], tn=tn),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, td), lambda i, j, k: (j, k)),
            pl.BlockSpec((tq, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j, k: (0, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(thresh.astype(jnp.float32), q.astype(jnp.float32),
      x.astype(jnp.float32), qn.astype(jnp.float32).reshape(nq, 1),
      xn.astype(jnp.float32).reshape(1, nn))


@functools.partial(jax.jit, static_argnames=("tq", "tn", "td", "interpret"))
def linear_scan_l1_pallas(thresh: jax.Array, q: jax.Array, x: jax.Array, *,
                          tq: int = 128, tn: int = 128, td: int = 128,
                          interpret: bool = False):
    """Fused L1 scan -> (dists f32, mask i8, ids i32), all (Q, N)."""
    nq, d = q.shape
    nn = x.shape[0]
    assert nq % tq == 0 and nn % tn == 0 and d % td == 0, (q.shape, x.shape)
    grid = (nq // tq, nn // tn, d // td)
    out_specs, out_shape = _linear_out(nq, nn, tq, tn, jnp.float32)
    return pl.pallas_call(
        functools.partial(_linear_l1_kernel, nk=grid[2], tn=tn),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, td), lambda i, j, k: (j, k)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(thresh.astype(jnp.float32), q.astype(jnp.float32),
      x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("tq", "tn", "interpret"))
def linear_scan_hamming_pallas(thresh: jax.Array, qc: jax.Array,
                               xc: jax.Array, *, tq: int = 128,
                               tn: int = 128, interpret: bool = False):
    """Fused packed-code Hamming scan -> (dists i32, mask i8, ids i32)."""
    nq, w = qc.shape
    nn = xc.shape[0]
    assert nq % tq == 0 and nn % tn == 0, (qc.shape, xc.shape)
    grid = (nq // tq, nn // tn, 1)
    out_specs, out_shape = _linear_out(nq, nn, tq, tn, jnp.int32)
    return pl.pallas_call(
        functools.partial(_linear_hamming_kernel, tn=tn),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tq, w), lambda i, j, k: (i, 0)),
            pl.BlockSpec((tn, w), lambda i, j, k: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(thresh.astype(jnp.float32), qc.astype(jnp.uint32),
      xc.astype(jnp.uint32))


# ---------------------------------------------------------------------------
# LSH route: sorted-run dedup + row gather + rowwise distance + threshold
# ---------------------------------------------------------------------------
def _lsh_kernel(t_ref, ids_sm, x_hbm, q_ref, ids_ref, prev_ref,
                dist_ref, mask_ref, blk_ref, rows_ref, sem, *, metric, n,
                tq, tc, group):
    """One (tq, tc) candidate tile: dedup-mask, gather, verify.

    ``ids_sm`` is the SMEM copy of the sorted candidate tile (scalar
    reads address the row copies); ``ids_ref``/``prev_ref`` are the
    VMEM copies for the vectorized run-boundary compare.  The corpus
    stays in HBM as ``(n_rows / 8, 8, d)`` sublane-aligned slabs
    (``x_hbm``): the TPU's DMA cannot slice one row out of an
    (8, 128)-tiled array wider than 128 lanes, so each candidate's
    whole slab lands in ``blk_ref`` (``group`` candidates in flight)
    and its row is picked into the ``(tc, d)`` scratch.  The rowwise
    math is kept expression-identical to ``ref.rowwise_dist``; the
    per-row terms are reduced over the transposed ``(d, tc)`` tile so
    each query's distances come out as one lane-major ``(1, tc)`` row.
    """
    thresh = t_ref[0, 0]
    for qi in range(tq):     # static unroll: stores at static row offsets

        def row(c):
            return jnp.clip(ids_sm[qi, c], 0, n - 1)

        for g0 in range(0, tc, group):

            def copy(c, g0=g0):
                return pltpu.make_async_copy(x_hbm.at[row(g0 + c) // 8],
                                             blk_ref.at[c], sem.at[0])

            def start(c, carry, copy=copy):
                copy(c).start()
                return carry

            def wait(c, carry, copy=copy):
                copy(c).wait()
                return carry

            def pick(c, carry, g0=g0):
                rows_ref[pl.ds(g0 + c, 1), :] = \
                    blk_ref[c, pl.ds(row(g0 + c) % 8, 1), :]
                return carry

            jax.lax.fori_loop(0, group, start, 0)
            jax.lax.fori_loop(0, group, wait, 0)
            jax.lax.fori_loop(0, group, pick, 0)
        rows = rows_ref[...]                             # (tc, d) in VMEM
        qv = q_ref[pl.ds(qi, 1), :]                      # (1, d)
        if metric == "hamming":
            dist = jnp.sum(_popcount_u32(rows ^ qv).T, axis=0,
                           keepdims=True).astype(jnp.float32)
        elif metric == "l2":
            diff = rows - qv
            dist = jnp.sum((diff * diff).T, axis=0, keepdims=True)
        elif metric == "l1":
            dist = jnp.sum(jnp.abs(rows - qv).T, axis=0, keepdims=True)
        else:   # cosine (pad columns are zero: norms unaffected)
            rn = rows / jnp.maximum(
                jnp.sqrt(jnp.sum(rows * rows, -1, keepdims=True)), 1e-12)
            qn = qv / jnp.maximum(
                jnp.sqrt(jnp.sum(qv * qv, -1, keepdims=True)), 1e-12)
            dist = 1.0 - jnp.sum((rn * qn).T, axis=0, keepdims=True)
        dist_ref[pl.ds(qi, 1), :] = dist
    ids_v = ids_ref[...]
    uniq = (ids_v != prev_ref[...]) & (ids_v < n)        # sorted-run dedup
    mask_ref[...] = (uniq & (dist_ref[...] <= thresh)).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("metric", "n", "tq", "tc",
                                             "interpret"))
def lsh_scan_pallas(thresh: jax.Array, x: jax.Array, q: jax.Array,
                    ids: jax.Array, prev: jax.Array, *, metric: str, n: int,
                    tq: int = LSH_TQ, tc: int = LSH_TC,
                    interpret: bool = False):
    """Fused LSH-route verification -> (dists f32, mask i8), both (Q, C).

    x: (n_rows, d_pad) corpus, left in HBM (rows >= n are pad; never
    gathered — ids are clipped to n - 1); q: (Q, d_pad); ids/prev:
    (Q, C) sorted candidate ids and their left-shift (prev[0] = -1),
    sentinel = ``n``.  Q % tq == C % tc == 0 and n_rows % 8 == 0
    (ops.py pads; sentinel padding makes padded slots self-masking).
    """
    nq, c = ids.shape
    n_rows, d = x.shape
    assert nq % tq == 0 and c % tc == 0, (ids.shape, tq, tc)
    assert n_rows % 8 == 0 and q.shape[1] == d, (q.shape, x.shape)
    grid = (nq // tq, c // tc)
    dtype = jnp.uint32 if metric == "hamming" else jnp.float32
    # candidates in flight: as many 8-row slabs as fit LSH_SLAB_BYTES
    group = tc
    while group > 1 and group * 8 * d * 4 > LSH_SLAB_BYTES:
        group //= 2
    assert tc % group == 0, (tc, group)
    return pl.pallas_call(
        functools.partial(_lsh_kernel, metric=metric, n=n, tq=tq, tc=tc,
                          group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # thresh
            pl.BlockSpec((tq, tc), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),                 # ids tile
            pl.BlockSpec(memory_space=pl.ANY),                     # corpus
            pl.BlockSpec((tq, q.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tq, tc), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tq, tc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, c), jnp.float32),
            jax.ShapeDtypeStruct((nq, c), jnp.int8),
        ],
        scratch_shapes=[pltpu.VMEM((group, 8, d), dtype),
                        pltpu.VMEM((tc, d), dtype),
                        pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret,
    )(thresh.astype(jnp.float32), ids.astype(jnp.int32),
      x.astype(dtype).reshape(n_rows // 8, 8, d),   # a bitcast, not a copy
      q.astype(dtype), ids.astype(jnp.int32), prev.astype(jnp.int32))
