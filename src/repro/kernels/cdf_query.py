"""Pallas TPU kernel: cumulative-probability threshold query (paper §II.B).

Fuses the whole inference path — probability normalisation (two-counter
scheme), prefix-sum, threshold test, and masked top-item emission — into one
VPU kernel over a (QUERIES_PER_BLOCK, C) VMEM tile.  The paper's
O(CDF^-1(t)) bound shows up twice:

  * ``n_needed`` reports CDF^-1(t) per query, and
  * with ``chunks`` > 1 the walk over C runs in lane-width chunks whose
    bodies are predicated off with ``@pl.when`` once **every** row of the
    block has crossed the threshold — the block-granular analogue of the
    paper's per-reader early exit.  Work done then tracks ``mean_items``
    (CDF^-1), not C.

Exactness contract (shared with ``ref.cdf_query_ref`` and the fused-gather
variant in ``cdf_gather.py``): the cumulative walk runs in **integer count
space** — ``needed[j] = (sum(cnt[<j]) < t * tot) & (cnt[j] > 0)`` with the
prefix sums exact int32 — so any chunking of the walk is bit-identical to
any other (float prefix sums would make the result depend on association
order).  The only float ops, ``t * tot`` and ``p = cnt / tot``, are
per-row/per-item and association-free.

``threshold=None`` selects **top-k mode** (keep every live item, emit the
first ``max_items``): the mode is a static kernel flag, not an unreachable
sentinel threshold, so the contract never relies on a float that cannot be
crossed.  The early-exit carry state lives in a scratch ref because values
cannot thread through ``@pl.when`` bodies.  Per-row values (totals, counts
needed, the carry) are (Q, 1) columns and the threshold an SMEM scalar:
Mosaic indexes no 1-D VMEM array at run time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashtable import EMPTY

DEFAULT_QUERIES_PER_BLOCK = 128
LANE_WIDTH = 128  # VPU lane dim; auto-chunking targets one chunk per lane tile


def auto_chunks(capacity: int, chunks: int) -> int:
    """Resolve ``chunks=0`` (auto) from C and the lane width: one chunk per
    128-lane tile when C is a lane multiple, else a single chunk.  Explicit
    chunk counts are validated here — once, for every backend — so a bad
    ``MCConfig.query_chunks`` fails identically on ref and pallas instead
    of crashing only at TPU trace time."""
    if chunks:
        if capacity % chunks:
            raise ValueError(
                f"chunks={chunks} must divide capacity={capacity} "
                f"(MCConfig.query_chunks)")
        return chunks
    if capacity % LANE_WIDTH == 0 and capacity > LANE_WIDTH:
        return capacity // LANE_WIDTH
    return 1


def lane_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum along the lane (last) axis.

    Mosaic has no cumsum lowering, so this is the log-step shifted-add
    scan (Hillis-Steele): ``log2(C)`` lane rotations, selects and adds.
    Integer addition wraps identically in any association order, so the
    result is bit-identical to ``jnp.cumsum`` (contract A9)."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, jnp.roll(x, shift, axis=-1), 0)
        shift *= 2
    return x


def walk_chunks(load, totf, t, dst_out_ref, prob_out_ref, n_out_ref,
                carry_ref, *, cap: int, max_items: int, chunks: int,
                topk: bool):
    """The chunked CDF walk shared by the pre-gathered and fused kernels.

    ``load(k) -> (ck, dk)`` yields chunk ``k`` of the counts/dsts in
    priority order (reads happen inside the predicated body, so a skipped
    chunk costs nothing).  ``totf`` is the (Q, 1) float row total and
    ``carry_ref`` an int32 (Q, 1) scratch holding each row's exact
    cumulative count; ``n_out_ref`` is (Q, 1).  Outputs are initialised
    here and written per chunk.  ``topk=True`` keeps every live item and
    disables the early exit (there is no threshold to cross).
    """
    chunk = cap // chunks
    dst_out_ref[...] = jnp.full_like(dst_out_ref[...], EMPTY)
    prob_out_ref[...] = jnp.zeros_like(prob_out_ref[...])
    n_out_ref[...] = jnp.zeros_like(n_out_ref[...])
    carry_ref[...] = jnp.zeros_like(carry_ref[...])
    tcnt = t * totf                                   # (Q, 1) float32

    for k in range(chunks):

        def body(k=k):
            ck, dk = load(k)                          # (Q, chunk) int32
            cum = carry_ref[...] + lane_cumsum(ck)    # exact int32 prefix
            if topk:
                needed = ck > 0
            else:
                before = (cum - ck).astype(jnp.float32)
                needed = (before < tcnt) & (ck > 0)
            n_out_ref[...] = n_out_ref[...] + jnp.sum(
                needed.astype(jnp.int32), axis=1, keepdims=True)
            lo = k * chunk
            if lo < max_items:
                hi = min(lo + chunk, max_items)
                w = hi - lo
                p = ck.astype(jnp.float32) / totf
                keep = needed[:, :w]
                # a whole-row store needs no lane slice, which Mosaic
                # refuses below 128 lanes on a row picked at run time
                cols = (slice(None) if w == dst_out_ref.shape[1]
                        else slice(lo, hi))
                dst_out_ref[:, cols] = jnp.where(keep, dk[:, :w], EMPTY)
                prob_out_ref[:, cols] = jnp.where(keep, p[:, :w], 0.0)
            carry_ref[...] = cum[:, chunk - 1:]

        if topk or chunks == 1:
            body()
        else:
            # real early exit: once every row's cumulative count crossed the
            # threshold no later item can be needed (prefix counts are
            # monotone), so the whole chunk is predicated off.  Skipping
            # leaves carry stale, which keeps the block skipped — exact.
            open_rows = (carry_ref[...].astype(jnp.float32)
                         < tcnt).astype(jnp.int32)
            pl.when((k == 0) | (jnp.max(open_rows) > 0))(body)


def _cdf_kernel(t_ref, c_ref, d_ref, tot_ref, dst_out_ref, prob_out_ref,
                n_out_ref, carry_ref, *, max_items: int, chunks: int,
                topk: bool):
    cap = c_ref.shape[-1]
    chunk = cap // chunks
    totf = jnp.maximum(tot_ref[...], 1).astype(jnp.float32)  # (Qb, 1)

    def load(k):
        return (c_ref[:, k * chunk:(k + 1) * chunk],
                d_ref[:, k * chunk:(k + 1) * chunk])

    walk_chunks(load, totf, t_ref[0], dst_out_ref, prob_out_ref, n_out_ref,
                carry_ref, cap=cap, max_items=max_items, chunks=chunks,
                topk=topk)


@functools.partial(
    jax.jit,
    static_argnames=("max_items", "queries_per_block", "chunks", "topk",
                     "interpret"))
def cdf_query_pallas(c_ord: jax.Array, d_ord: jax.Array, tot: jax.Array,
                     threshold=0.0, *, max_items: int = 16,
                     queries_per_block: int = DEFAULT_QUERIES_PER_BLOCK,
                     chunks: int = 1, topk: bool = False,
                     interpret: bool):
    """c_ord/d_ord: [B, C] counts/dsts in priority order (0 where missing),
    tot: [B]. Returns (dsts[B, max_items], probs[B, max_items], n_needed[B]).
    ``topk=True`` ignores the threshold and keeps every live item.
    """
    b, cap = c_ord.shape
    qb = min(queries_per_block, b)
    assert b % qb == 0, (b, qb)
    assert cap % chunks == 0, (cap, chunks)
    grid = (b // qb,)
    t_arr = jnp.asarray([threshold], jnp.float32)
    tile2d = pl.BlockSpec((qb, cap), lambda i: (i, 0))
    col = pl.BlockSpec((qb, 1), lambda i: (i, 0))
    tilek = pl.BlockSpec((qb, max_items), lambda i: (i, 0))
    dk, pk, nn = pl.pallas_call(
        functools.partial(_cdf_kernel, max_items=max_items, chunks=chunks,
                          topk=topk),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile2d, tile2d,
                  col],
        out_specs=[tilek, tilek, col],
        out_shape=[
            jax.ShapeDtypeStruct((b, max_items), jnp.int32),
            jax.ShapeDtypeStruct((b, max_items), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((qb, 1), jnp.int32)],
        interpret=interpret,
    )(t_arr, c_ord, d_ord, tot.reshape(b, 1))
    return dk, pk, nn[:, 0]
