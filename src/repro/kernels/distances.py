"""Pallas TPU kernels for blocked pairwise distances (the linear-scan and
candidate-verification hot spot — step S3 of the paper's cost model).

Design (TPU-native, not a CUDA port):
  * The L2/cosine scans are decomposed so the inner loop is a
    ``(TQ, TD) @ (TD, TN)`` matmul that runs on the MXU; norms are
    precomputed (O(N·d), done once per database) and added on the first
    d-block only.
  * Tiles are 128-aligned (MXU/VREG lanes); the d (contraction) axis is
    blocked so the working set ``TQ*TD + TN*TD + TQ*TN`` floats stays
    well inside VMEM (default tiles: 256*256*3*4B = 768 KiB).
  * L1 has no matmul form; its kernel broadcasts a ``(TQ, TN, TD)``
    tile on the VPU and accumulates over d-blocks.

Grid is (Q/TQ, N/TN, D/TD) with the contraction axis innermost; the
output block for (i, j) is revisited across k, initialized at k == 0.
Inputs must be pre-padded to tile multiples (ops.py does this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TQ = 256
DEFAULT_TN = 256
DEFAULT_TD = 256


def _dot_kernel(q_ref, x_ref, qn_ref, xn_ref, out_ref, *, mode: str):
    """out[i,j] (+)= norms - 2 q.x  (l2)  |  1 - q.x (cosine, normalized)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if mode == "l2":
            out_ref[...] = qn_ref[...] + xn_ref[...]
        else:  # cosine: inputs pre-normalized, distance = 1 - dot
            out_ref[...] = jnp.ones_like(out_ref)

    acc = jnp.dot(q_ref[...], x_ref[...].T,
                  preferred_element_type=jnp.float32)
    scale = 2.0 if mode == "l2" else 1.0
    out_ref[...] = out_ref[...] - scale * acc


def _l1_kernel(q_ref, x_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    diff = jnp.abs(q_ref[...][:, None, :] - x_ref[...][None, :, :])
    out_ref[...] = out_ref[...] + jnp.sum(diff, axis=-1)


@functools.partial(jax.jit, static_argnames=("mode", "tq", "tn", "td",
                                             "interpret"))
def pairwise_dot_pallas(q: jax.Array, x: jax.Array, qn: jax.Array,
                        xn: jax.Array, *, mode: str = "l2",
                        tq: int = DEFAULT_TQ, tn: int = DEFAULT_TN,
                        td: int = DEFAULT_TD,
                        interpret: bool = False) -> jax.Array:
    """Blocked (Q, d) x (N, d) -> (Q, N) squared-L2 or cosine distances.

    Shapes must already be padded: Q % tq == N % tn == d % td == 0.
    ``qn``/``xn`` are (Q,)/(N,) squared norms (ignored for cosine but
    still tiled), passed to the kernel as a (tq, 1) column and a (1, tn)
    row: 1-D blocks do not lower on the TPU.
    """
    nq, d = q.shape
    nn = x.shape[0]
    assert nq % tq == 0 and nn % tn == 0 and d % td == 0, (q.shape, x.shape)
    grid = (nq // tq, nn // tn, d // td)
    return pl.pallas_call(
        functools.partial(_dot_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, td), lambda i, j, k: (j, k)),
            pl.BlockSpec((tq, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nn), jnp.float32),
        interpret=interpret,
    )(q.astype(jnp.float32), x.astype(jnp.float32),
      qn.astype(jnp.float32).reshape(nq, 1),
      xn.astype(jnp.float32).reshape(1, nn))


@functools.partial(jax.jit, static_argnames=("tq", "tn", "td", "interpret"))
def pairwise_l1_pallas(q: jax.Array, x: jax.Array, *, tq: int = 128,
                       tn: int = 128, td: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Blocked (Q, d) x (N, d) -> (Q, N) L1 distances (VPU broadcast)."""
    nq, d = q.shape
    nn = x.shape[0]
    assert nq % tq == 0 and nn % tn == 0 and d % td == 0, (q.shape, x.shape)
    grid = (nq // tq, nn // tn, d // td)
    return pl.pallas_call(
        _l1_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, td), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nn), jnp.float32),
        interpret=interpret,
    )(q.astype(jnp.float32), x.astype(jnp.float32))
