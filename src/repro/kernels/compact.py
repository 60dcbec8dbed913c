"""Pallas TPU kernel: pack a route group's reported pairs into flat buffers.

A group's search buffers are ``(G, W)`` ids, distances and a report
mask, mostly padding (a linear row holds every corpus row, an LSH row
every candidate slot).  The kernel keeps the reported slots only, in
row-major order, so the host copies answers and not padding.

The buffers arrive as ``(rows, 128)`` tiles of 128-slot source rows,
with each source row's output position (the prefix sum of the reported
slots before it, computed outside).  Per grid step, on a
``(tile_rows, 128)`` tile:

1. *Left-pack each row on the VPU.*  A reported slot moves left by the
   number of empty slots before it, one bit of that distance a stage
   (seven lane rolls); distances never decrease along a row, so no two
   slots land on one lane.
2. *Place each row.*  Row ``r``'s ``c`` pairs belong at output position
   ``p``: rolled right by ``p % 128`` lanes, they fill lanes ``p % 128``
   onward of output row ``p // 128`` and the wrapped rest of the next.
   Positions only grow, so those two output rows are carried in
   registers from source row to source row (every other slot is zero,
   so pairs are added in) and stored to a VMEM window of output rows,
   never read back inside the loop.
3. *Flush.*  Output rows below the next tile's first position are
   complete; once ``tile_rows`` of them are, they are DMA'd to the HBM
   output (8-row aligned) and the window shifts down.  The last step
   writes the whole window.

Distances travel as their int32 bits, so the packing is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE_ROWS = 256     # source rows a grid step (32 KiB of each input)
UNROLL = 8          # source rows placed per loop iteration


def _kernel(pos_sm, m_ref, i_ref, d_ref, oi_hbm, od_hbm,
            packed_i, packed_d, win_i, win_d, base_sm, sem, *, rows, win,
            steps):
    step = pl.program_id(0)
    keep = win - rows

    @pl.when(step == 0)
    def _():
        win_i[...] = jnp.zeros_like(win_i)
        win_d[...] = jnp.zeros_like(win_d)
        base_sm[0] = 0

    def write_out(n, base):
        copies = [pltpu.make_async_copy(w.at[pl.ds(0, n)],
                                        o.at[pl.ds(base, n)], sem.at[k])
                  for k, (w, o) in enumerate(((win_i, oi_hbm),
                                              (win_d, od_hbm)))]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    @pl.when(pos_sm[0, 0] // LANES - base_sm[0] >= rows)
    def _():
        base = pl.multiple_of(base_sm[0], 8)
        write_out(rows, base)
        for w in (win_i, win_d):
            w[pl.ds(0, keep), :] = w[pl.ds(rows, keep), :]
            w[pl.ds(keep, rows), :] = jnp.zeros((rows, LANES), jnp.int32)
        base_sm[0] = base + rows

    m = m_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, m.shape, 1)
    rank = m                                   # inclusive prefix, lanes
    for b in range(7):
        rank = rank + jnp.where(lane >= (1 << b),
                                pltpu.roll(rank, 1 << b, 1), 0)
    valid = m > 0
    gap = lane + 1 - rank                      # empty slots before a slot
    vi, vd = i_ref[...], d_ref[...]
    for b in range(7):
        move = valid & (((gap >> b) & 1) == 1)
        left = LANES - (1 << b)                # a roll left by 2^b lanes
        arrive = pltpu.roll(move.astype(jnp.int32), left, 1) > 0
        vi = jnp.where(arrive, pltpu.roll(vi, left, 1), vi)
        vd = jnp.where(arrive, pltpu.roll(vd, left, 1), vd)
        gap = jnp.where(arrive, pltpu.roll(gap, left, 1), gap)
        valid = arrive | (valid & ~move)
    packed_i[...] = jnp.where(valid, vi, 0)
    packed_d[...] = jnp.where(valid, vd, 0)

    # a source row moves the output on by at most one row: the row being
    # filled and the next are carried, and stored after every source row
    base = base_sm[0]
    lane1 = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    first = pos_sm[0, 0] // LANES - base
    rows0 = tuple(w[pl.ds(first + k, 1), :]
                  for w in (win_i, win_d) for k in (0, 1))

    def place(r, carry):
        cur, i0, i1, d0, d1 = carry
        p = pos_sm[0, r]
        row, shift = p // LANES - base, p % LANES
        on = row > cur                      # row ``cur`` is complete
        head = lane1 >= shift
        out = [cur]
        for src, w, a, b in ((packed_i, win_i, i0, i1),
                             (packed_d, win_d, d0, d1)):
            a, b = jnp.where(on, b, a), jnp.where(on, 0, b)
            v = pltpu.roll(src[pl.ds(r, 1), :], shift, 1)
            a = a + jnp.where(head, v, 0)
            b = b + jnp.where(head, 0, v)
            w[pl.ds(row, 1), :] = a
            w[pl.ds(row + 1, 1), :] = b
            out += [a, b]
        out[0] = row
        return tuple(out)

    def place_some(j, carry):           # ``UNROLL`` rows an iteration
        for k in range(UNROLL):
            carry = place(j * UNROLL + k, carry)
        return carry

    lax.fori_loop(0, rows // UNROLL, place_some, (first,) + rows0)

    @pl.when(step == steps - 1)
    def _():
        write_out(win, pl.multiple_of(base_sm[0], 8))


@functools.partial(jax.jit, static_argnames=("length", "rows", "interpret"))
def pack_pallas(pos: jax.Array, mask: jax.Array, ids: jax.Array,
                dbits: jax.Array, *, length: int, rows: int = TILE_ROWS,
                interpret: bool = False):
    """Pack the reported slots of ``(n, 128)`` int32 tiles.

    pos: (n // rows, 1, rows) each source row's output position (1-D
    and (1, rows) blocks do not lower on the TPU; a leading axis does);
    mask: 0/1; ids, dbits: ids and distance bits.  n % rows == 0 and
    pos[-1] + the last row's count <= length.  Returns (ids, dbits),
    each (length + window,): the pairs, then zeros up to the last
    window written, then whatever the output buffer held.
    """
    n = mask.shape[0]
    assert n % rows == 0 and rows % UNROLL == rows % 8 == 0
    assert length % LANES == 0
    steps = n // rows
    win = 2 * rows + 8
    out_rows = length // LANES + win
    blk = pl.BlockSpec((rows, LANES), lambda t: (t, 0))
    oi, od = pl.pallas_call(
        functools.partial(_kernel, rows=rows, win=win, steps=steps),
        grid=(steps,),
        in_specs=[pl.BlockSpec((None, 1, rows), lambda t: (t, 0, 0),
                               memory_space=pltpu.SMEM), blk, blk, blk],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32),
                        pltpu.VMEM((rows, LANES), jnp.int32),
                        pltpu.VMEM((win, LANES), jnp.int32),
                        pltpu.VMEM((win, LANES), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pos, mask, ids, dbits)
    return oi.reshape(-1), od.reshape(-1)
