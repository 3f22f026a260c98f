"""Pallas TPU kernel: vectorised odd-even transposition over slab rows.

The paper's lock-free bubble sort, as a VPU-only kernel.  Roll-based
compare-exchange — no lane-strided slicing, no gathers — so every pass is a
handful of lane shifts + selects, ideal for the TPU vector unit:

  for each parity p in {even, odd}:
    take_next[i] = (i % 2 == p) and i < C-1 and c[i] < c[i+1]
    gave_prev[i] = take_next[i-1]
    c'[i] = c[i+1] if take_next else (c[i-1] if gave_prev else c[i])

VMEM tiling: a (ROWS_PER_BLOCK, C) tile of both the count-in-order array and
the permutation; grid over row blocks.  C (slab capacity) is the lane dim;
a C below 128 (the serving configs use 64) fills part of each vreg.
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


def _oddeven_kernel(c_ref, o_ref, c_out_ref, o_out_ref, *, passes: int):
    c = c_ref[...]
    o = o_ref[...]
    cap = c.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    for _ in range(passes):
        for parity in (0, 1):
            c, o = _compare_exchange(c, o, idx, parity)
    c_out_ref[...] = c
    o_out_ref[...] = o


@functools.partial(
    jax.jit, static_argnames=("passes", "rows_per_block", "interpret"))
def oddeven_pallas(c_ord: jax.Array, order: jax.Array, *, passes: int = 1,
                   rows_per_block: int = DEFAULT_ROWS_PER_BLOCK,
                   interpret: bool):
    """k odd-even passes. c_ord/order: [N, C], N divisible by rows_per_block
    (ops.py pads). Returns (c_ord', order')."""
    n, cap = c_ord.shape
    rb = min(rows_per_block, n)
    assert n % rb == 0, (n, rb)
    grid = (n // rb,)
    spec = pl.BlockSpec((rb, cap), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_oddeven_kernel, passes=passes),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(c_ord.shape, c_ord.dtype),
            jax.ShapeDtypeStruct(order.shape, order.dtype),
        ],
        interpret=interpret,
    )(c_ord, order)
