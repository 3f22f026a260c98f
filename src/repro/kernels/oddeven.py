"""Pallas TPU kernel: vectorised odd-even transposition over slab rows.

The paper's lock-free bubble sort, as a VPU-only kernel.  Each row tile
first gathers its counts into order position (``c[r, i] = cnt[r,
order[r, i]]``, a lane gather in VMEM), then runs the passes with a
roll-based compare-exchange — no lane-strided slicing — so every pass is a
handful of lane shifts + selects, ideal for the TPU vector unit:

  for each parity p in {even, odd}:
    take_next[i] = (i % 2 == p) and i < C-1 and c[i] < c[i+1]
    gave_prev[i] = take_next[i-1]
    c'[i] = c[i+1] if take_next else (c[i-1] if gave_prev else c[i])

VMEM tiling: a (ROWS_PER_BLOCK, C) tile of the counts in slot order and of
the permutation in, a tile of the new permutation out; grid over row
blocks.  The counts in order position live only in VMEM: per call the
kernel reads two [N, C] arrays from HBM and writes one.  C (slab capacity)
is the lane dim; a C below 128 (the serving configs use 64) fills part of
each vreg.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROWS_PER_BLOCK = 256


def _compare_exchange(c, o, idx, parity):
    cap = c.shape[-1]
    cn = jnp.roll(c, -1, axis=1)
    cp = jnp.roll(c, 1, axis=1)
    on = jnp.roll(o, -1, axis=1)
    op = jnp.roll(o, 1, axis=1)
    is_left = ((idx % 2) == parity) & (idx < cap - 1)
    # descending order target; the mask travels as int32 because Mosaic
    # cannot rotate a bool vector (wrap safe: the last lane is masked)
    take_next = (is_left & (c < cn)).astype(jnp.int32)
    gave_prev = jnp.roll(take_next, 1, axis=1) > 0
    take_next = take_next > 0
    new_c = jnp.where(take_next, cn, jnp.where(gave_prev, cp, c))
    new_o = jnp.where(take_next, on, jnp.where(gave_prev, op, o))
    return new_c, new_o


def _oddeven_kernel(cnt_ref, o_ref, o_out_ref, *, passes: int):
    o = o_ref[...]
    # order holds a permutation of 0..C-1 in every row (padding rows: 0s)
    c = jnp.take_along_axis(cnt_ref[...], o, axis=1,
                            mode="promise_in_bounds")
    cap = c.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    for _ in range(passes):
        for parity in (0, 1):
            c, o = _compare_exchange(c, o, idx, parity)
    o_out_ref[...] = o


@functools.partial(
    jax.jit, static_argnames=("passes", "rows_per_block", "interpret"))
def oddeven_pallas(cnt: jax.Array, order: jax.Array, *, passes: int = 1,
                   rows_per_block: int = DEFAULT_ROWS_PER_BLOCK,
                   interpret: bool):
    """k odd-even passes. cnt: [N, C] counts in slot order; order: [N, C]
    int32 slot permutation; N divisible by rows_per_block (ops.py pads).
    Returns order'."""
    n, cap = cnt.shape
    rb = min(rows_per_block, n)
    assert n % rb == 0, (n, rb)
    spec = pl.BlockSpec((rb, cap), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_oddeven_kernel, passes=passes),
        grid=(n // rb,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(order.shape, order.dtype),
        interpret=interpret,
    )(cnt, order)
