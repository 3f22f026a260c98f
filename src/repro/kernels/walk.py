"""Pallas TPU kernel: one-shot k-step greedy draft walk (speculative.draft).

Drafting k tokens from the n-gram chain is k sequential iterations of
(rolling ctx hash -> src-table probe -> top-1 slab gather).  As a
``lax.scan`` over ``query_topk`` that is k separate kernel dispatches plus k
host round trips through lookup+gather+cdf_query — but the chain snapshot is
immutable for the duration of a draft (RCU/EpochStore contract), so the
whole walk collapses into ONE kernel: the src hash table and the slabs sit
in VMEM once, and each step is a handful of VPU ops.

Each query walks in the scalar unit; the vector unit does the probes:

  * rolling hash of the ctx window — same recurrence as
    ``speculative.context_ids`` (newest token first), on SMEM scalars;
  * src probe — the same lane-parallel linear-probe reductions as
    ``kernels/probe.py`` (key_p/empty_p min over probe positions), over
    the two 128-slot rows of the VMEM-resident table that hold the probe
    window (``max_probes <= 128``);
  * top-1 lookup — the order head ``order[row, 0]`` IS the approximate
    argmax (paper §II.2), so top-1 needs no CDF walk.  The wrapper gathers
    each row's head count and dst once (``cnt/dst[r, ord0[r]]``) into
    lane-dense tables, and the step reads one lane of them with a dynamic
    row load (``pl.ds``).

Dead lanes stop walking: once a step finds no transition the query's later
steps are predicated off with ``@pl.when`` and emit token 0 / ok False.
The window and the alive flag live in SMEM scratch because values cannot
thread through ``@pl.when`` bodies.  Semantics match ``ref.draft_walk_ref``
(the lax.scan oracle) token for token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashtable import EMPTY, ctx_hash_fold, hash_u32

DEFAULT_QUERIES_PER_BLOCK = 128
LANES = 128


def _lane_pick(ref, idx: jax.Array) -> jax.Array:
    """Element ``idx`` of a lane-dense (R, L) table as a scalar."""
    lanes = ref.shape[1]
    row = ref[pl.ds(idx // lanes, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == idx % lanes, row, 0))


def _walk_kernel(win_ref, hk_ref, hv_ref, c0_ref, d0_ref, tok_ref, ok_ref,
                 win_scr, alive_scr, *, steps: int, max_probes: int,
                 valid: int, t_size: int, n_rows: int,
                 queries_per_block: int):
    order = win_scr.shape[0]
    lanes = hk_ref.shape[1]
    n_tab_rows = hk_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    big = jnp.int32(t_size)

    def probe(src):
        h0 = (hash_u32(src) & jnp.uint32(t_size - 1)).astype(jnp.int32)
        r0 = h0 // lanes
        key_p, empty_p, hits = big, big, []
        for r in (r0, (r0 + 1) % n_tab_rows):
            keys = hk_ref[pl.ds(r, 1), :]
            p = (r * lanes + lane - h0) & (t_size - 1)
            in_win = p < max_probes
            is_key = in_win & (keys == src)
            key_p = jnp.minimum(key_p, jnp.min(jnp.where(is_key, p, big)))
            empty_p = jnp.minimum(empty_p, jnp.min(
                jnp.where(in_win & (keys == EMPTY), p, big)))
            hits.append((is_key, p, hv_ref[pl.ds(r, 1), :]))
        row = EMPTY
        for is_key, p, vals in hits:
            row = jnp.maximum(row, jnp.max(
                jnp.where(is_key & (p == key_p), vals, EMPTY)))
        return key_p < empty_p, row

    q0 = pl.program_id(0) * queries_per_block

    def query(q, carry):
        gq = q0 + q
        for j in range(order):
            win_scr[j] = win_ref[gq * order + j]
        for s in range(steps):
            tok_ref[gq * steps + s] = 0
            ok_ref[gq * steps + s] = 0
        # batch-padding queries (>= valid) start dead: no probe work
        alive_scr[0] = (gq < valid).astype(jnp.int32)

        for s in range(steps):

            @pl.when(alive_scr[0] > 0)
            def _step(s=s):
                # rolling ctx hash, newest token first (ctx_window_hash)
                h = jnp.uint32(0)
                for j in range(order):
                    h = ctx_hash_fold(h, win_scr[order - 1 - j])
                src = (h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
                found, row = probe(src)
                rowm = jnp.clip(jnp.where(found, row, 0), 0, n_rows - 1)
                cnt0 = _lane_pick(c0_ref, rowm)
                dst0 = _lane_pick(d0_ref, rowm)
                ok = found & (cnt0 > 0) & (dst0 != EMPTY)
                nxt = jnp.where(ok, dst0, 0)
                tok_ref[gq * steps + s] = nxt
                ok_ref[gq * steps + s] = ok.astype(jnp.int32)
                alive_scr[0] = ok.astype(jnp.int32)
                for j in range(order - 1):
                    win_scr[j] = win_scr[j + 1]
                win_scr[order - 1] = nxt

        return carry

    jax.lax.fori_loop(0, queries_per_block, query, 0)


def _lane_dense(x: jax.Array, fill: int) -> jax.Array:
    """Lay a 1-D table out as (rows, 128) lanes (one row when shorter)."""
    lanes = min(LANES, x.shape[0])
    x = jnp.pad(x, (0, (-x.shape[0]) % lanes), constant_values=fill)
    return x.reshape(-1, lanes)


@functools.partial(
    jax.jit,
    static_argnames=("k", "max_probes", "queries_per_block", "valid",
                     "interpret"))
def draft_walk_pallas(window: jax.Array, ht_keys: jax.Array,
                      ht_vals: jax.Array, cnt: jax.Array, dst: jax.Array,
                      ord0: jax.Array, *, k: int = 4, max_probes: int = 64,
                      queries_per_block: int = DEFAULT_QUERIES_PER_BLOCK,
                      valid: int = 0, interpret: bool):
    """window: [B, order] recent tokens per sequence; ht_keys/ht_vals: [T]
    flat src table; cnt/dst: [N, C] slabs; ord0: [N] order head per row
    (``slabs.order[:, 0]``).  ``valid`` marks the real (pre-padding) batch
    size; lanes past it never walk (0 = all lanes real).  Returns
    ``(toks[B, k], ok[B, k] int32)``.
    """
    b, order = window.shape
    t_size = ht_keys.shape[0]
    n_rows = cnt.shape[0]
    qb = min(queries_per_block, b)
    assert b % qb == 0, (b, qb)
    if max_probes > LANES and t_size > LANES:
        # (a table of at most 128 slots is one row: the window is all of it)
        raise ValueError(f"max_probes={max_probes} exceeds the "
                         f"{LANES}-slot probe window")
    valid = valid or b
    head = ord0[:, None]
    cnt0 = jnp.take_along_axis(cnt, head, axis=1)[:, 0]
    dst0 = jnp.take_along_axis(dst, head, axis=1)[:, 0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    toks, oks = pl.pallas_call(
        functools.partial(_walk_kernel, steps=k, max_probes=max_probes,
                          valid=valid, t_size=t_size, n_rows=n_rows,
                          queries_per_block=qb),
        grid=(b // qb,),
        in_specs=[smem, vmem, vmem, vmem, vmem],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((b * k,), jnp.int32),
            jax.ShapeDtypeStruct((b * k,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((order,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(window.reshape(-1).astype(jnp.int32),
      _lane_dense(ht_keys, EMPTY), _lane_dense(ht_vals, EMPTY),
      _lane_dense(cnt0, 0), _lane_dense(dst0, EMPTY))
    return toks.reshape(b, k), oks.reshape(b, k)
