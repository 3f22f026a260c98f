"""Pallas TPU kernel: fused row-gather + CDF threshold walk (paper §II.B).

The unfused inference path materialises every queried row's counts/dsts in
priority order on the host side (``mcprioq._ordered_rows``: three O(B*C)
``take_along_axis`` gathers) before ``cdf_query`` ever launches — O(B*C)
memory traffic regardless of the threshold.  This kernel makes the read side
honor the paper's O(CDF^-1(t)) bound at the traffic level: the queried row
indices arrive via **scalar prefetch** (``pltpu.PrefetchScalarGridSpec``), so
each grid instance's BlockSpec index map points the DMA engine at the 8-row
tile of ``cnt/dst/order`` holding ``rows[i]`` (the smallest block Mosaic
tiles) — only tiles of queried rows ever move.  The order-gather (slot
permutation -> priority order) runs on the VMEM-resident tile, whose
query row is then selected; each query's answer row is merged into its
8-row output block with a select.

The walk itself is ``cdf_query.walk_chunks`` — same integer-exact cumulative
semantics, same ``@pl.when`` chunk predication, but with a **one-query
block** the early exit is per-row exact, not block-granular: each query
stops touching lanes the moment its own cumulative count crosses the
threshold.

Semantics oracle: ``ref.cdf_query_fused_ref`` (single fused advanced-index
gather + the shared ref walk); bit-identical to the unfused path by the
integer-walk contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cdf_query import walk_chunks

SUBLANES = 8   # int32 sublane tile: rows DMA'd per query, outputs per block


def _fused_kernel(rows_ref, found_ref, tot_ref, t_ref, cnt_ref, dst_ref,
                  ord_ref, dst_out_ref, prob_out_ref, n_out_ref, dst_scr,
                  prob_scr, n_scr, carry_ref, *, max_items: int, chunks: int,
                  topk: bool):
    # cnt/dst/ord_ref are the (8, C) tiles holding THIS query's row, DMA'd
    # via the scalar-prefetched row index; the order gather runs on the
    # whole tile (Mosaic gathers lanes of full sublane tiles) and the
    # query's row is then selected out of it.
    i = pl.program_id(0)
    tile_rows, cap = cnt_ref.shape
    chunk = cap // chunks
    sub = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, cap), 0)
    pick = sub == rows_ref[i] % tile_rows
    ords = ord_ref[...]

    def row(tile):
        tile = jnp.take_along_axis(tile, ords, axis=1)
        return jnp.sum(jnp.where(pick, tile, 0), axis=0, keepdims=True)

    c_row = jnp.where(found_ref[i] > 0, row(cnt_ref[...]), 0)  # unknown -> 0
    d_row = row(dst_ref[...])
    totf = jnp.full((1, 1), jnp.maximum(tot_ref[i], 1)).astype(jnp.float32)

    def load(k):
        return (c_row[:, k * chunk:(k + 1) * chunk],
                d_row[:, k * chunk:(k + 1) * chunk])

    walk_chunks(load, totf, t_ref[0], dst_scr, prob_scr, n_scr, carry_ref,
                cap=cap, max_items=max_items, chunks=chunks, topk=topk)
    # merge this query's row into the output block with a select: Mosaic
    # refuses a sub-128-lane slice at a row picked at run time
    out_rows = dst_out_ref.shape[0]
    mine = jax.lax.broadcasted_iota(jnp.int32, (out_rows, 1), 0) == (
        i % out_rows)
    for out_ref, scr in ((dst_out_ref, dst_scr), (prob_out_ref, prob_scr),
                         (n_out_ref, n_scr)):
        out_ref[...] = jnp.where(mine, scr[...], out_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("max_items", "chunks", "topk", "interpret"))
def cdf_query_fused_pallas(rows: jax.Array, found: jax.Array,
                           cnt: jax.Array, dst: jax.Array, order: jax.Array,
                           tot: jax.Array, threshold=0.0, *,
                           max_items: int = 16, chunks: int = 1,
                           topk: bool = False, interpret: bool):
    """rows[B] (pre-resolved, 0 where missing), found[B] int32 mask,
    cnt/dst/order: [N, C] slab arrays, tot: [N].  Returns
    (dsts[B, max_items], probs[B, max_items], n_needed[B]).
    """
    b = rows.shape[0]
    n, cap = cnt.shape
    assert cap % chunks == 0, (cap, chunks)
    tile_rows = min(SUBLANES, n)
    assert n % tile_rows == 0, (n, tile_rows)
    out_rows = min(SUBLANES, b)
    pad = (-b) % out_rows
    rows_p = jnp.pad(rows.astype(jnp.int32), (0, pad))
    found_p = jnp.pad(found.astype(jnp.int32), (0, pad))
    tot_q = tot[rows_p].astype(jnp.int32)
    bp = b + pad
    t_arr = jnp.asarray([threshold], jnp.float32)
    row_tile = pl.BlockSpec(
        (tile_rows, cap), lambda i, rows, *_: (rows[i] // tile_rows, 0))
    out_tile = pl.BlockSpec(
        (out_rows, max_items), lambda i, *_: (i // out_rows, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bp,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  row_tile, row_tile, row_tile],
        out_specs=[out_tile, out_tile,
                   pl.BlockSpec((out_rows, 1),
                                lambda i, *_: (i // out_rows, 0))],
        scratch_shapes=[pltpu.VMEM((1, max_items), jnp.int32),
                        pltpu.VMEM((1, max_items), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.int32),
                        pltpu.VMEM((1, 1), jnp.int32)],
    )
    dk, pk, nn = pl.pallas_call(
        functools.partial(_fused_kernel, max_items=max_items, chunks=chunks,
                          topk=topk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bp, max_items), jnp.int32),
            jax.ShapeDtypeStruct((bp, max_items), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(rows_p, found_p, tot_q, t_arr, cnt, dst, order)
    return dk[:b], pk[:b], nn[:b, 0]
