"""Pallas TPU kernel for the O(m*L) HLL merge + estimate (Algorithm 2,
line 2) — the step the paper adds on the query path.

Per query: max-merge the (L, m) gathered registers, then the HLL
estimator with small/large-range corrections.  Entirely VPU work on a
``(L, TQ, m)`` tile (20 * 64 * 64 B = 80 KiB of uint8 at L = 20); memory
bound, but fusing merge+estimate avoids a round trip of the merged
registers through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _alpha(m: int) -> float:
    if m <= 16:
        return 0.673
    if m <= 32:
        return 0.697
    if m <= 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


def _kernel(regs_ref, out_ref, *, m: int):
    regs = regs_ref[...].astype(jnp.int32)              # (L, TQ, m)
    merged = jnp.max(regs, axis=0)                      # (TQ, m)
    rf = merged.astype(jnp.float32)
    raw = _alpha(m) * m * m / jnp.sum(jnp.exp2(-rf), axis=-1, keepdims=True)
    zeros = jnp.sum((merged == 0).astype(jnp.float32), axis=-1,
                    keepdims=True)
    small = m * jnp.log(m / jnp.maximum(zeros, 1e-9))
    est = jnp.where((raw <= 2.5 * m) & (zeros > 0), small, raw)
    two32 = jnp.float32(2.0**32)
    est = jnp.where(est > two32 / 30.0, -two32 * jnp.log1p(-est / two32), est)
    out_ref[...] = est                                  # (TQ, 1)


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def hll_merge_estimate_pallas(regs: jax.Array, *, tq: int = 64,
                              interpret: bool = False) -> jax.Array:
    """(Q, L, m) uint8 registers -> (Q,) float32 candSize estimates.

    The kernel sees the registers table-major, ``(L, Q, m)``, so the
    merge is an elementwise max over the leading axis, and it writes a
    ``(Q, 1)`` column: 1-D blocks do not lower on the TPU.
    """
    q, L, m = regs.shape
    assert q % tq == 0, regs.shape
    grid = (q // tq,)
    out = pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid=grid,
        in_specs=[pl.BlockSpec((L, tq, m), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tq, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.float32),
        interpret=interpret,
    )(jnp.transpose(regs, (1, 0, 2)))
    return out[:, 0]
