"""jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples and backend dispatch: ``impl='pallas'``
(compiled by Mosaic on a TPU, run by the Pallas interpreter on any other
backend — how the CPU tests check kernel parity), ``impl='ref'`` (pure-jnp
oracle), ``impl='auto'`` (pallas on TPU, ref otherwise — the ref *is* the
XLA fast path on CPU).  On a TPU nothing falls back: a kernel that fails
to compile raises to the caller.  Layout transforms a kernel needs (the
odd-even sort's counts in order position) happen inside the kernel, in
VMEM, not as an XLA pass over the state.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.analysis.invariants import kernel_op
from repro.kernels import cdf_gather as _cg
from repro.kernels import cdf_query as _cdf
from repro.kernels import oddeven as _oe
from repro.kernels import probe as _pr
from repro.kernels import ref as _ref
from repro.kernels import slab_update as _su
from repro.kernels import walk as _wk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_IMPLS = ("auto", "ref", "pallas")


def _use_ref(impl: str) -> bool:
    """Validate ``impl`` and decide the dispatch (trace time, static arg)."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    return impl == "ref" or (impl == "auto" and not _on_tpu())


def _pad_rows(x: jax.Array, mult: int, fill) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        pad_block = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
        x = jnp.concatenate([x, pad_block], axis=0)
    return x, n


def _annotate(fn):
    """Trace a jitted dispatcher under ``jax.named_scope("mcq.<op>")``
    (DESIGN.md §13), so the op name lands in the HLO metadata every
    profiler timeline shows.  A scope is metadata only: the persistent
    compile cache's key strips it, and the compiled program is the same."""
    scope = f"mcq.{fn.__name__}"

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return dispatch


# ---------------------------------------------------------------------------


@_annotate
@functools.partial(jax.jit, static_argnames=("passes", "impl"))
@kernel_op(ref="oddeven_ref", pallas="oddeven_pallas")
def oddeven_sort(cnt: jax.Array, order: jax.Array, *, passes: int = 1,
                 impl: str = "auto") -> jax.Array:
    """k odd-even passes over every slab row; returns the new order
    permutation (slabs themselves never move — DESIGN.md §2)."""
    if _use_ref(impl):
        # oracle layout: gather counts into order position ONCE and carry
        # them through the swaps, instead of re-gathering every half-pass
        # (same semantics; see test_oddeven_ref_equals_slab_semantics)
        c_ord = jnp.take_along_axis(cnt, order, axis=1)
        _, new_order = _ref.oddeven_ref(c_ord, order, passes)
        return new_order
    # the kernel makes that gather itself, per row tile in VMEM
    rb = min(_oe.DEFAULT_ROWS_PER_BLOCK, cnt.shape[0])
    cnt_p, n = _pad_rows(cnt, rb, 0)
    order_p, _ = _pad_rows(order, rb, 0)
    new_order = _oe.oddeven_pallas(
        cnt_p, order_p, passes=passes, rows_per_block=rb,
        interpret=not _on_tpu())
    return new_order[:n]


@_annotate
@functools.partial(jax.jit, static_argnames=("impl",))
@kernel_op(ref="slab_update_ref", pallas="slab_update_pallas")
def slab_update(rows: jax.Array, dsts: jax.Array, w: jax.Array,
                dst_slab: jax.Array, cnt: jax.Array, tot: jax.Array,
                *, impl: str = "auto"):
    """Fast-path batched increments; returns (cnt', tot').
    rows < 0 = padding/inactive items."""
    if _use_ref(impl):
        _, cnt2, tot2, _ = _ref.slab_update_ref(rows, dsts, w, dst_slab, cnt, tot)
        return cnt2, tot2
    rb = min(_su.DEFAULT_ROWS_PER_BLOCK, cnt.shape[0])
    dst_p, n = _pad_rows(dst_slab, rb, -1)
    cnt_p, _ = _pad_rows(cnt, rb, 0)
    tot_p, _ = _pad_rows(tot, rb, 0)
    cnt2, tot2 = _su.slab_update_pallas(
        rows, dsts, w, dst_p, cnt_p, tot_p, rows_per_block=rb,
        interpret=not _on_tpu())
    return cnt2[:n], tot2[:n]


@_annotate
@functools.partial(jax.jit, static_argnames=("impl",))
@kernel_op(ref="oddeven_ref", composes=("oddeven_sort",))
def decay_sort(cnt: jax.Array, dst: jax.Array, order: jax.Array,
               *, impl: str = "auto"):
    """Fused §II.C decay: halve counters, evict dead edges, fully re-sort.

    The compaction sort composes the odd-even kernel with C/2+1 passes (a
    full odd-even transposition network sorts any input), so the whole decay
    runs as VPU sweeps over the slab tiles.  Returns (cnt', dst', order',
    tot') with evicted slots at the order tail.
    """
    new_cnt = cnt >> 1
    new_dst = jnp.where(new_cnt == 0, -1, dst)
    new_tot = jnp.sum(new_cnt, axis=1).astype(jnp.int32)
    passes = cnt.shape[1] // 2 + 1
    new_order = oddeven_sort(new_cnt, order, passes=passes, impl=impl)
    return new_cnt, new_dst, new_order, new_tot


@_annotate
@functools.partial(jax.jit, static_argnames=("max_probes", "impl"))
@kernel_op(ref="dh_find_ref", pallas="probe_find_pallas")
def dh_find(rows: jax.Array, dsts: jax.Array,
            dh_keys: jax.Array, dh_vals: jax.Array,
            *, max_probes: int = 64, impl: str = "auto"):
    """Batched per-row dst-hash lookup: ``(slots[B], found[B] bool)``.

    The paper's §II.2 dst -> slot tables as one fused dispatch through the
    shared probe kernel (``kernels/probe.py``); rows < 0 are padding.
    Semantics are the core linear probe (``hashtable.lookup``).
    """
    if _use_ref(impl):
        slots, found = _ref.dh_find_ref(rows, dsts, dh_keys, dh_vals,
                                        max_probes)
        return slots, found
    slots, found = _pr.probe_find_pallas(
        rows, dsts, dh_keys, dh_vals, max_probes=max_probes,
        interpret=not _on_tpu())
    return slots, found.astype(bool)


@_annotate
@functools.partial(jax.jit, static_argnames=("max_probes", "impl"))
@kernel_op(ref="probe_find_ref", pallas="probe_find_pallas")
def ht_find(keys_q: jax.Array, tab_keys: jax.Array, tab_vals: jax.Array,
            *, max_probes: int = 64, impl: str = "auto"):
    """Batched flat-table lookup: ``(vals[B], found[B] bool)``.

    The src node-id -> row probe at the head of every query (paper §II.1),
    kernelized: the flat table is the N = 1 case of the shared probe kernel.
    ``hashtable.lookup_batch`` routes here when an impl is requested.
    """
    rows = jnp.zeros_like(keys_q)
    if _use_ref(impl):
        slots, found = _ref.probe_find_ref(
            rows, keys_q, tab_keys[None], tab_vals[None], max_probes)
        return slots, found
    slots, found = _pr.probe_find_pallas(
        rows, keys_q, tab_keys[None], tab_vals[None],
        max_probes=max_probes, interpret=not _on_tpu())
    return slots, found.astype(bool)


@_annotate
@functools.partial(jax.jit,
                   static_argnames=("max_items", "chunks", "topk", "impl"))
@kernel_op(ref="cdf_query_ref", pallas="cdf_query_pallas")
def cdf_query(c_ord: jax.Array, d_ord: jax.Array, tot: jax.Array,
              threshold, *, max_items: int = 16, chunks: int = 0,
              topk: bool = False, impl: str = "auto"):
    """Threshold inference over pre-ordered rows; see cdf_query.py.

    ``threshold`` is required; passing ``None`` explicitly selects top-k
    mode (keep every live item — the explicit contract, not an unreachable
    threshold).  ``chunks=0`` auto-picks the chunked early-exit walk from C
    and the lane width.
    """
    topk = topk or threshold is None
    chunks = _cdf.auto_chunks(c_ord.shape[1], chunks)
    if _use_ref(impl):
        return _ref.cdf_query_ref(c_ord, d_ord, tot,
                                  None if topk else threshold, max_items)
    qb = min(_cdf.DEFAULT_QUERIES_PER_BLOCK, c_ord.shape[0])
    c_p, b = _pad_rows(c_ord, qb, 0)
    d_p, _ = _pad_rows(d_ord, qb, 0)
    t_p, _ = _pad_rows(tot, qb, 0)
    dk, pk, nn = _cdf.cdf_query_pallas(
        c_p, d_p, t_p, 0.0 if topk else threshold, max_items=max_items,
        queries_per_block=qb, chunks=chunks, topk=topk,
        interpret=not _on_tpu())
    return dk[:b], pk[:b], nn[:b]


@_annotate
@functools.partial(jax.jit,
                   static_argnames=("max_items", "chunks", "topk", "impl"))
@kernel_op(ref="cdf_query_fused_ref", pallas="cdf_query_fused_pallas")
def cdf_query_fused(rows: jax.Array, found: jax.Array,
                    cnt: jax.Array, dst: jax.Array, order: jax.Array,
                    tot: jax.Array, threshold, *, max_items: int = 16,
                    chunks: int = 0, topk: bool = False, impl: str = "auto"):
    """Fused inference: in-kernel row gather + CDF walk (cdf_gather.py).

    Takes pre-resolved rows[B] (0 where missing) + found[B] and the raw slab
    arrays; only queried rows are touched (scalar-prefetch DMA on TPU, one
    combined gather in the ref path).  Bit-identical to ``cdf_query`` over
    ``_ordered_rows`` output by the integer-walk contract.
    """
    topk = topk or threshold is None
    chunks = _cdf.auto_chunks(cnt.shape[1], chunks)
    if _use_ref(impl):
        return _ref.cdf_query_fused_ref(rows, found, cnt, dst, order, tot,
                                        None if topk else threshold,
                                        max_items)
    tile = min(_cg.SUBLANES, cnt.shape[0])
    cnt, _ = _pad_rows(cnt, tile, 0)
    dst, _ = _pad_rows(dst, tile, -1)
    order, _ = _pad_rows(order, tile, 0)
    return _cg.cdf_query_fused_pallas(
        rows, found, cnt, dst, order, tot, 0.0 if topk else threshold,
        max_items=max_items, chunks=chunks, topk=topk,
        interpret=not _on_tpu())


@_annotate
@functools.partial(jax.jit, static_argnames=("n", "impl"))
@kernel_op(ref="topn_merge_ref", pallas=None)
def topn_merge(probs: jax.Array, dsts: jax.Array, srcs: jax.Array,
               *, n: int, impl: str = "auto"):
    """Cross-shard top-n merge: ``(srcs[n], dsts[n], probs[n])`` descending.

    Merges S per-shard descending top lists (``probs/dsts/srcs[S, M]``) into
    one globally descending n-list — the reduce step of the sharded headline
    query (``core/sharded.py`` all_gathers local answers, then merges).
    A fixed-shape scalar head-pointer merge over an (S, M) tile is branch-
    serial by nature and tiny (S = shards, M <= n), so every backend runs
    the ref merge; ``impl`` is still validated so dispatch stays uniform
    with the other ops.
    """
    _use_ref(impl)
    return _ref.topn_merge_ref(probs, dsts, srcs, n)


@_annotate
@functools.partial(jax.jit,
                   static_argnames=("k", "max_probes", "impl"))
@kernel_op(ref="draft_walk_ref", pallas="draft_walk_pallas")
def draft_walk(window: jax.Array, ht_keys: jax.Array, ht_vals: jax.Array,
               cnt: jax.Array, dst: jax.Array, ord0: jax.Array,
               *, k: int = 4, max_probes: int = 64, impl: str = "auto"):
    """One-shot k-step greedy draft walk (kernels/walk.py).

    window[B, order] recent tokens; the chain snapshot (src table + slabs +
    order heads) is immutable during a draft, so the whole k-step scan runs
    as one dispatch.  Returns ``(toks[B, k], ok[B, k] bool)``.
    """
    if _use_ref(impl):
        toks, oks = _ref.draft_walk_ref(window, ht_keys, ht_vals, cnt, dst,
                                        ord0, k=k, max_probes=max_probes)
        return toks, oks.astype(bool)
    qb = min(_wk.DEFAULT_QUERIES_PER_BLOCK, window.shape[0])
    win_p, b = _pad_rows(window, qb, 0)
    toks, oks = _wk.draft_walk_pallas(
        win_p, ht_keys, ht_vals, cnt, dst, ord0, k=k, max_probes=max_probes,
        queries_per_block=qb, valid=b, interpret=not _on_tpu())
    return toks[:b], oks[:b].astype(bool)
