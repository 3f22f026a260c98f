"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; kernels must match exactly
(integer ops) or to float tolerance (probability ops).  The oracles reuse the
core library where it defines the semantics (slab.py odd-even passes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashtable as ht
from repro.core import slab as sl
from repro.core.hashtable import EMPTY


def oddeven_ref(c_ord: jax.Array, order: jax.Array, passes: int):
    """k odd-even passes over counts-in-order + the order permutation.

    c_ord[N, C] are the counts *already gathered into order position* (the
    layout the kernel builds per row tile in VMEM); order[N, C] the slot
    permutation. Returns the pair
    after ``passes`` full (even+odd) sweeps, descending target.
    """
    for _ in range(passes):
        for start in (0, 1):
            left_c = c_ord[:, start:-1:2]
            right_c = c_ord[:, start + 1 :: 2]
            m = min(left_c.shape[1], right_c.shape[1])
            left_c, right_c = left_c[:, :m], right_c[:, :m]
            left_o = order[:, start:-1:2][:, :m]
            right_o = order[:, start + 1 :: 2][:, :m]
            swap = left_c < right_c
            nl_c = jnp.where(swap, right_c, left_c)
            nr_c = jnp.where(swap, left_c, right_c)
            nl_o = jnp.where(swap, right_o, left_o)
            nr_o = jnp.where(swap, left_o, right_o)
            c_ord = c_ord.at[:, start : start + 2 * m : 2].set(nl_c)
            c_ord = c_ord.at[:, start + 1 : start + 1 + 2 * m : 2].set(nr_c)
            order = order.at[:, start : start + 2 * m : 2].set(nl_o)
            order = order.at[:, start + 1 : start + 1 + 2 * m : 2].set(nr_o)
    return c_ord, order


def oddeven_on_slabs_ref(cnt: jax.Array, order: jax.Array, passes: int):
    """Same semantics as slab.oddeven_passes (permutation-only view)."""
    return sl.oddeven_passes(cnt, order, passes)


def slab_update_ref(rows: jax.Array, dsts: jax.Array, w: jax.Array,
                    dst: jax.Array, cnt: jax.Array, tot: jax.Array):
    """Fast-path batched edge increment (paper §II.A.2, existing edges only).

    For each item i: find slot of dsts[i] in row rows[i]; if present add w[i]
    to cnt and tot.  Items whose edge is absent are no-ops (the caller sends
    them down the slow path).  rows < 0 marks padding.
    """
    active = rows >= 0
    safe_rows = jnp.maximum(rows, 0)
    hit = dst[safe_rows] == dsts[:, None]          # [B, C]
    found = jnp.any(hit, axis=1) & active
    slot = jnp.argmax(hit, axis=1)
    addw = jnp.where(found, w, 0)
    cnt = cnt.at[safe_rows, slot].add(addw)
    tot = tot.at[safe_rows].add(addw)
    return dst, cnt, tot, found


def probe_find_ref(rows: jax.Array, keys_q: jax.Array,
                   keys: jax.Array, vals: jax.Array, max_probes: int):
    """Batched open-addressing probe (the shared lookup oracle).

    rows[B] select a table out of keys/vals[N, H]; rows < 0 marks padding.
    Covers both the per-row dst hash (paper §II.2, N = slab rows) and the
    flat src table (paper §II.1, N = 1).  Returns ``(slots[B], found[B])``
    with slot EMPTY when missing.

    Semantics are the core scalar probe (:func:`repro.core.hashtable.lookup`
    — scan from the home slot, stop at the key or the first EMPTY, give up
    after ``max_probes``) but vectorised the same way the Pallas kernel is:
    one (B, max_probes) window gather + min-reductions over probe positions,
    instead of a vmapped fori_loop (which XLA:CPU lowers to per-item scalar
    chains — the old O(B) probe loop this PR's read path removes).  First-
    occurrence equivalence holds even when the window wraps a small table:
    a slot's first visit time IS its probe position mod H.
    """
    h = keys.shape[1]
    safe_rows = jnp.maximum(rows, 0)
    h0 = (ht.hash_u32(keys_q) & jnp.uint32(h - 1)).astype(jnp.int32)
    p = jnp.arange(max_probes, dtype=jnp.int32)[None, :]       # (1, P)
    idx = (h0[:, None] + p) & (h - 1)                          # (B, P)
    win = keys[safe_rows[:, None], idx]                        # (B, P)
    big = jnp.int32(max_probes)
    key_p = jnp.min(jnp.where(win == keys_q[:, None], p, big), axis=1)
    empty_p = jnp.min(jnp.where(win == EMPTY, p, big), axis=1)
    found = (key_p < empty_p) & (rows >= 0)
    slot_idx = (h0 + jnp.minimum(key_p, big - 1)) & (h - 1)
    slots = vals[safe_rows, slot_idx]
    return jnp.where(found, slots, EMPTY), found


# the dst-hash entry point is the same probe; kept under its §II.2 name
dh_find_ref = probe_find_ref


def _needed_walk(c_ord: jax.Array, totf: jax.Array, threshold):
    """The A9 integer walk shared by every CDF oracle: which priority
    positions a reader needs, and how many (CDF^-1).  ``threshold=None`` is
    top-k mode (every live item)."""
    if threshold is None:
        needed = c_ord > 0
    else:
        cum = jnp.cumsum(c_ord, axis=1)
        before = (cum - c_ord).astype(jnp.float32)
        needed = (before < threshold * totf[:, None]) & (c_ord > 0)
    return needed, jnp.sum(needed.astype(jnp.int32), axis=1)


def _pad_items(dk: jax.Array, pk: jax.Array, max_items: int):
    """Pad the emission window out to ``max_items`` when it exceeds C, so
    the ref path returns the same (B, max_items) shape the kernels allocate
    (entries past C are always EMPTY/0 — a row has at most C items)."""
    pad = max_items - dk.shape[1]
    if pad > 0:
        dk = jnp.pad(dk, ((0, 0), (0, pad)), constant_values=EMPTY)
        pk = jnp.pad(pk, ((0, 0), (0, pad)))
    return dk, pk


def cdf_query_ref(c_ord: jax.Array, d_ord: jax.Array, tot: jax.Array,
                  threshold, max_items: int):
    """Cumulative-probability threshold query (paper §II.B).

    c_ord/d_ord[B, C]: counts/dsts gathered in descending-priority order
    (zeros for missing rows). Returns (dsts[B,k], probs[B,k], n_needed[B]).

    ``threshold=None`` is top-k mode: keep every live item (no threshold
    test).  The cumulative walk runs in exact integer count space —
    ``needed[j] = (sum(cnt[<j]) < t * tot) & (cnt[j] > 0)`` — so the result
    is independent of how a kernel chunks the walk (int prefix sums are
    association-free; float ones are not).  The only float ops, ``t * tot``
    and ``p = cnt / tot``, are per-row/per-item.
    """
    totf = jnp.maximum(tot, 1).astype(jnp.float32)
    needed, n_needed = _needed_walk(c_ord, totf, threshold)
    k = min(max_items, c_ord.shape[1])
    keep = needed[:, :k]
    pk_raw = c_ord[:, :k].astype(jnp.float32) / totf[:, None]
    dk = jnp.where(keep, d_ord[:, :k], EMPTY)
    pk = jnp.where(keep, pk_raw, 0.0)
    dk, pk = _pad_items(dk, pk, max_items)
    return dk, pk, n_needed


def cdf_query_fused_ref(rows: jax.Array, found: jax.Array,
                        cnt: jax.Array, dst: jax.Array, order: jax.Array,
                        tot: jax.Array, threshold, max_items: int):
    """Fused row-gather + CDF walk (oracle of ``cdf_gather.py``).

    rows[B] are pre-resolved row indices (0 where missing), found[B] the
    src-lookup mask; cnt/dst/order[N, C], tot[N] are the raw slab arrays.
    One combined linear-index gather pulls counts straight into priority
    order (no intermediate ``cnt[rows]`` materialisation), and — because the
    gather is fused into the query — dsts/probs are only gathered for the
    ``max_items`` emission window instead of all C (``n_needed`` still walks
    every count).  Bit-identical to ``_ordered_rows`` + ``cdf_query_ref``:
    same integer walk, same per-item float ops.
    """
    r = jnp.maximum(rows, 0)
    cap = cnt.shape[1]
    flat = r[:, None] * cap + order[r]                 # [B, C] linear slots
    c_ord = jnp.where(found[:, None], cnt.reshape(-1)[flat], 0)
    totf = jnp.maximum(tot[r], 1).astype(jnp.float32)
    needed, n_needed = _needed_walk(c_ord, totf, threshold)
    k = min(max_items, cap)
    keep = needed[:, :k]
    d_k = dst.reshape(-1)[flat[:, :k]]                 # emission window only
    p_k = c_ord[:, :k].astype(jnp.float32) / totf[:, None]
    dk = jnp.where(keep, d_k, EMPTY)
    pk = jnp.where(keep, p_k, 0.0)
    dk, pk = _pad_items(dk, pk, max_items)
    return dk, pk, n_needed


def topn_merge_ref(probs: jax.Array, dsts: jax.Array, srcs: jax.Array,
                   n: int):
    """Fixed-shape k-way merge of per-shard descending top lists.

    probs/dsts/srcs[S, M]: each shard's local answer, descending by prob
    (dead entries carry prob 0 / EMPTY ids at the tail).  Classic k-way
    head-pointer merge as a lax.scan of n steps: every step reads the S list
    heads, emits the max (ties break toward the lowest shard id — argmax
    first occurrence — so the merge is deterministic), and advances that
    shard's pointer.  Because each input list is descending, the emitted
    stream is globally descending.  Exhausted or dead heads emit
    EMPTY/EMPTY/0.0; output is always (srcs[n], dsts[n], probs[n]).
    """
    s, m = probs.shape

    def step(ptr, _):
        j = jnp.minimum(ptr, m - 1)
        head = probs[jnp.arange(s), j]
        head = jnp.where(ptr < m, head, 0.0)
        best = jnp.argmax(head)
        p = head[best]
        live = p > 0
        src = jnp.where(live, srcs[best, j[best]], EMPTY)
        dst = jnp.where(live, dsts[best, j[best]], EMPTY)
        ptr = ptr.at[best].add(1)
        return ptr, (src, dst, jnp.where(live, p, 0.0))

    _, (ms, md, mp) = jax.lax.scan(
        step, jnp.zeros((s,), jnp.int32), None, length=n)
    return ms, md, mp


def draft_walk_ref(window: jax.Array, ht_keys: jax.Array, ht_vals: jax.Array,
                   cnt: jax.Array, dst: jax.Array, ord0: jax.Array,
                   *, k: int, max_probes: int):
    """k-step greedy draft walk (oracle of ``kernels/walk.py``).

    A lax.scan of (rolling ctx hash -> src probe -> top-1 gather) with a
    dead-lane stop: once a step finds no transition the lane emits token 0 /
    ok False for every later step and does no further lookups' worth of
    state changes.  window[B, order] int32; returns (toks[B, k], ok[B, k]).
    """
    n = cnt.shape[0]

    def step(carry, _):
        win, alive = carry
        src = ht.ctx_window_hash(win)
        rows, found = probe_find_ref(jnp.zeros_like(src), src,
                                     ht_keys[None], ht_vals[None], max_probes)
        rowm = jnp.clip(jnp.where(found, rows, 0), 0, n - 1)
        slot0 = ord0[rowm]
        cnt0 = cnt[rowm, slot0]
        dst0 = dst[rowm, slot0]
        ok = alive & found & (cnt0 > 0) & (dst0 != EMPTY)
        nxt = jnp.where(ok, dst0, 0)
        win = jnp.concatenate([win[:, 1:], nxt[:, None]], axis=1)
        return (win, ok), (nxt, ok)

    alive0 = jnp.ones((window.shape[0],), bool)
    _, (toks, oks) = jax.lax.scan(step, (window, alive0), None, length=k)
    return toks.T, oks.T.astype(jnp.int32)
