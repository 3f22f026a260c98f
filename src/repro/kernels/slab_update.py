"""Pallas TPU kernel: fused batched edge-increment (paper §II.A.2 hot path).

Fuses the paper's "O(1) dst lookup + atomic increment" for a whole update
batch.  The wrapper sorts the (pre-row-resolved) update list by row and
splits it into *segments*, one per touched (ROWS_PER_BLOCK, C) slab tile.
Grid step j owns segment j: the tile's index comes from the scalar-prefetched
segment table, so only touched tiles move between HBM and VMEM (the count
array is aliased in place; untouched tiles keep their values) and each tile
replays exactly its own items — writes are conflict-free by construction
(the TPU reading of "lock-free": determinism instead of atomics,
DESIGN.md §2).  Steps past the last segment repeat its tile index with an
empty item range, so they move no data.

The dst-slot lookup inside the tile is a single C-lane vector compare per
item — the paper's §II.2 observation that a linear scan can rival a hash
table is literal here: on TPU the scan is one VPU op.  The first hit is a
lane-min reduction, so a degenerate row holding a dst twice still counts
each item once.

Layout: items live in SMEM (scalar prefetch); the per-item row access is a
dynamic sublane slice of the VMEM tile.  Row totals take the weight each
item actually applied (an SMEM output) through one scatter-add outside the
kernel, so no 1-D VMEM array is indexed dynamically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ROWS_PER_BLOCK = 8   # one int32 sublane tile


def _slab_update_kernel(seg_blk_ref, seg_lo_ref, seg_hi_ref, rows_ref,
                        dsts_ref, w_ref, cnt_ref, dst_ref,
                        cnt_out_ref, applied_ref, *, rows_per_block: int):
    j = pl.program_id(0)
    blk = seg_blk_ref[j]
    cap = cnt_ref.shape[-1]

    # the output tile is fresh VMEM whenever its index changes; a repeated
    # index (padding steps) keeps the tile and must not reload stale input
    first_visit = (j == 0) | (blk != seg_blk_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first_visit)
    def _load():
        cnt_out_ref[...] = cnt_ref[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    r0 = blk * rows_per_block

    def body(i, _):
        r = rows_ref[i] - r0
        hit = dst_ref[pl.ds(r, 1), :] == dsts_ref[i]          # (1, C)
        first = jnp.min(jnp.where(hit, lane, cap))
        w = jnp.where(first < cap, w_ref[i], 0)
        row = cnt_out_ref[pl.ds(r, 1), :]
        cnt_out_ref[pl.ds(r, 1), :] = row + jnp.where(lane == first, w, 0)
        applied_ref[i] = w
        return 0

    jax.lax.fori_loop(seg_lo_ref[j], seg_hi_ref[j], body, 0)


@functools.partial(
    jax.jit, static_argnames=("rows_per_block", "interpret"))
def slab_update_pallas(rows: jax.Array, dsts: jax.Array, w: jax.Array,
                       dst_slab: jax.Array, cnt: jax.Array, tot: jax.Array,
                       *, rows_per_block: int = DEFAULT_ROWS_PER_BLOCK,
                       interpret: bool):
    """Apply fast-path increments. rows[B] (< 0 = padding), dsts[B], w[B];
    dst_slab/cnt[N, C], tot[N]. Returns (cnt', tot')."""
    n, cap = cnt.shape
    b = rows.shape[0]
    rb = min(rows_per_block, n)
    assert n % rb == 0, (n, rb)
    # sort items by row (padding last) and cut one segment per touched tile
    valid = rows >= 0
    key = jnp.where(valid, rows, n)
    key_s, rows_s, dsts_s, w_s = jax.lax.sort(
        (key, rows, dsts, w.astype(jnp.int32)), num_keys=1, is_stable=True)
    valid_s = key_s < n
    blk_s = jnp.where(valid_s, key_s // rb, -1)
    idx = jnp.arange(b, dtype=jnp.int32)
    head = valid_s & jnp.concatenate(
        [jnp.ones((1,), bool), blk_s[1:] != blk_s[:-1]])
    n_seg = jnp.sum(head.astype(jnp.int32))
    n_valid = jnp.sum(valid_s.astype(jnp.int32))
    seg_id = jnp.where(head, jnp.cumsum(head.astype(jnp.int32)) - 1, b)
    seg_lo = jnp.zeros((b,), jnp.int32).at[seg_id].set(idx, mode="drop")
    seg_blk = jnp.zeros((b,), jnp.int32).at[seg_id].set(blk_s, mode="drop")
    seg_hi = jnp.concatenate([seg_lo[1:], jnp.zeros((1,), jnp.int32)])
    live = idx < n_seg
    seg_hi = jnp.where(idx == n_seg - 1, n_valid, seg_hi)
    seg_lo = jnp.where(live, seg_lo, 0)
    seg_hi = jnp.where(live, seg_hi, 0)
    seg_blk = jnp.where(live, seg_blk, seg_blk[jnp.maximum(n_seg - 1, 0)])

    tile = pl.BlockSpec((rb, cap), lambda j, blk, *_: (blk[j], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[tile, tile],
        out_specs=[tile, pl.BlockSpec(memory_space=pltpu.SMEM)],
    )
    cnt_out, applied = pl.pallas_call(
        functools.partial(_slab_update_kernel, rows_per_block=rb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cnt.shape, cnt.dtype),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ],
        # count tiles no segment touches keep their input values
        input_output_aliases={6: 0},
        interpret=interpret,
    )(seg_blk, seg_lo, seg_hi, rows_s, dsts_s, w_s, cnt, dst_slab)
    applied = jnp.where(valid_s, applied, 0)
    tot_out = tot.at[jnp.maximum(rows_s, 0)].add(applied.astype(tot.dtype))
    return cnt_out, tot_out
