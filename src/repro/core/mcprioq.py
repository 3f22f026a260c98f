"""MCPrioQ: online sparse Markov chain with priority-ordered edge queries.

This is the paper's contribution as a composable JAX module (DESIGN.md §1-2).

Data layout
-----------
  * src hash table  : node-id -> row index into the slabs (open addressing)
  * slabs           : per-row stable edge slots (dst, cnt) + ``order`` perm
  * two counters    : per-edge ``cnt`` and per-row ``tot``; probability is
                      ``cnt/tot`` computed at query time (paper §II.3)
  * optional dst hash: per-row open-addressing table dst -> slot ("optional
                      optimization", paper §II.2); slots are stable so the
                      hash survives reordering, like the paper's pointers.

Update semantics (paper §II.A, TPU-batched)
-------------------------------------------
A batch of B transitions runs through a three-stage pipeline:
  * **pre-aggregation**: the batch is sorted by (src, dst) and duplicate
    edges are segment-summed into one item each, so B raw transitions
    collapse to U unique edges before either path runs — the batched
    analogue of contended atomics coalescing on one cache line (and the
    relaxed-batching insight of the MultiQueues line of work).
  * **update of edge** (normal case): the edge already exists — a fused
    batched increment via :func:`repro.kernels.ops.slab_update` (the
    paper's "O(1) lookup + atomic increment" as one kernel dispatch).
  * **new edge** (rare case): new-edge items are stable-partitioned to a
    static ``max_new_per_batch`` prefix and handled by a deterministic
    sequential pass (lax.scan) that allocates rows/slots and applies
    Space-Saving tail replacement when a row is full (DESIGN.md assumption
    log).  The scan is wrapped in ``lax.cond`` so a batch with zero new
    edges skips it entirely: slow-path cost is O(new edges), not O(B).
    Edges past the prefix are counted in ``deferred_new`` (the caller may
    resubmit; DESIGN.md §2 observability).
Afterwards ``sort_passes`` odd-even passes (``ops.oddeven_sort``) restore
approximate order — the paper's lock-free bubble sort.

Kernel dispatch is selected by ``MCConfig.impl`` (``auto``/``ref``/
``pallas``); ``core``, ``sharded`` and ``serve`` all inherit the fused paths
through this module.  ``update_batch_reference`` keeps the pre-kernel
O(B)-scan semantics as an oracle for equivalence tests and benchmarks.

Inference (paper §II.B, DESIGN.md §8)
-------------------------------------
``query_threshold`` walks the order permutation accumulating probability until
the cumulative sum crosses ``t``: complexity O(CDF^-1(t)) items touched.  By
default (``MCConfig.fused_query``) the kernel layer owns the whole read:
:func:`repro.kernels.ops.cdf_query_fused` gathers only the queried rows
(scalar-prefetch DMA on TPU) and runs the chunked early-exit walk in-kernel;
``fused_query=False`` keeps the unfused ``_ordered_rows`` +
:func:`repro.kernels.ops.cdf_query` baseline, bit-identical by the
integer-walk contract.  ``query_topk`` is the kernel's ``threshold=None``
mode.

Maintenance (paper §II.C, DESIGN.md §6)
---------------------------------------
``decay`` dispatches through :func:`repro.kernels.ops.decay_sort` (halve,
evict, odd-even compaction).  ``MCConfig.decay_block_rows`` selects rolling
mode: each call halves one row block and repairs that block's dst hashes
incrementally (tombstones, not rebuilds), so per-call maintenance cost is
bounded by the block size; a full rebuild runs only when accumulated
tombstones cross ``dh_rebuild_fraction`` of the hash capacity.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hashtable as ht
from repro.core import slab as sl
from repro.core.hashtable import EMPTY, TOMB, HashTable
from repro.core.slab import Slabs
from repro.kernels import ops


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class MCConfig:
    """Static configuration (hashable; safe as a jit static arg)."""

    num_rows: int = 1024          # max distinct src nodes tracked
    capacity: int = 128           # max out-degree tracked per src (C)
    table_size: int = 0           # src hash slots; 0 -> 4 * num_rows pow2
    max_probes: int = 64
    sort_passes: int = 1          # odd-even passes per update batch
    use_dst_hash: bool = False    # paper's optional dst->slot hash table
    dst_table_size: int = 0       # per-row; 0 -> 4 * capacity pow2
    max_new_per_batch: int = 0    # slow-path prefix; 0 = unbounded (batch)
    impl: str = "auto"            # kernel dispatch: auto | ref | pallas
    # inference path (DESIGN.md §8): fused in-kernel row gather vs the
    # unfused _ordered_rows host-side gather; 0 = auto-pick early-exit
    # chunks from capacity and the lane width
    fused_query: bool = True
    query_chunks: int = 0
    # maintenance (DESIGN.md §6): 0 = stop-the-world decay; R > 0 = rolling
    # decay that halves one R-row block per call (bounded per-call work)
    decay_block_rows: int = 0
    # full dst-hash rebuild once decay tombstones exceed this fraction of
    # the total dst-hash capacity (num_rows * dst_table_size)
    dh_rebuild_fraction: float = 0.25

    def resolved_table_size(self) -> int:
        return self.table_size or _next_pow2(4 * self.num_rows)

    def resolved_dst_table_size(self) -> int:
        return self.dst_table_size or _next_pow2(4 * self.capacity)

    def resolved_max_new(self, batch: int) -> int:
        if self.max_new_per_batch <= 0:
            return batch
        return min(self.max_new_per_batch, batch)

    def resolved_decay_rows(self) -> int:
        """Rows decayed per call: the block size, clamped to the table."""
        if self.decay_block_rows <= 0:
            return self.num_rows
        return min(self.decay_block_rows, self.num_rows)


class MCState(NamedTuple):
    src_table: HashTable   # node-id -> row
    slabs: Slabs
    n_rows: jax.Array      # int32[]   allocated rows
    # optional per-row dst hash (zero-size arrays when disabled)
    dh_keys: jax.Array     # int32[N, H]
    dh_vals: jax.Array     # int32[N, H]
    # observability counters (drops are the price of fixed shapes; DESIGN §2)
    dropped_rows: jax.Array    # srcs dropped because num_rows exhausted
    dropped_probes: jax.Array  # items dropped on probe-window overflow
    evictions: jax.Array       # Space-Saving tail replacements
    deferred_new: jax.Array    # new edges past the max_new_per_batch prefix
    route_dropped: jax.Array   # items dropped on all_to_all bucket overflow
    # maintenance state + observability (DESIGN.md §6)
    decay_cursor: jax.Array    # next row block for rolling decay
    decay_steps: jax.Array     # decay calls applied (blocks, not full sweeps)
    dh_rebuilds: jax.Array     # full dst-hash rebuilds triggered
    dh_tombstones: jax.Array   # live decay tombstones across all row hashes


def init(cfg: MCConfig) -> MCState:
    n, c = cfg.num_rows, cfg.capacity
    h = cfg.resolved_dst_table_size() if cfg.use_dst_hash else 1
    return MCState(
        src_table=ht.make(cfg.resolved_table_size()),
        slabs=sl.make(n, c),
        n_rows=jnp.int32(0),
        dh_keys=jnp.full((n, h), EMPTY, dtype=jnp.int32),
        dh_vals=jnp.full((n, h), EMPTY, dtype=jnp.int32),
        dropped_rows=jnp.int32(0),
        dropped_probes=jnp.int32(0),
        evictions=jnp.int32(0),
        deferred_new=jnp.int32(0),
        route_dropped=jnp.int32(0),
        decay_cursor=jnp.int32(0),
        decay_steps=jnp.int32(0),
        dh_rebuilds=jnp.int32(0),
        dh_tombstones=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# per-row dst hash helpers (optional optimisation path)
# ---------------------------------------------------------------------------


def _dh_set(state: MCState, row: jax.Array, key: jax.Array, slot: jax.Array,
            active: jax.Array, cfg: MCConfig) -> MCState:
    tab = HashTable(state.dh_keys[row], state.dh_vals[row])
    new_tab, _, _ = ht.insert(tab, key, slot, cfg.max_probes)
    dh_keys = state.dh_keys.at[row].set(
        jnp.where(active, new_tab.keys, state.dh_keys[row]))
    dh_vals = state.dh_vals.at[row].set(
        jnp.where(active, new_tab.vals, state.dh_vals[row]))
    return state._replace(dh_keys=dh_keys, dh_vals=dh_vals)


def _dh_del(state: MCState, row: jax.Array, key: jax.Array,
            active: jax.Array, cfg: MCConfig) -> MCState:
    tab = HashTable(state.dh_keys[row], state.dh_vals[row])
    new_tab, _ = ht.delete(tab, key, cfg.max_probes)
    dh_keys = state.dh_keys.at[row].set(
        jnp.where(active, new_tab.keys, state.dh_keys[row]))
    return state._replace(dh_keys=dh_keys)


def _dh_rebuild_all(state: MCState, cfg: MCConfig) -> MCState:
    """Vectorised rebuild of every row hash from the slabs (used after decay).

    Rows are independent, so a vmap over rows of a sequential slot-insert loop
    is conflict-free.
    """
    if not cfg.use_dst_hash:
        return state
    h = cfg.resolved_dst_table_size()

    def rebuild_row(dsts, cnts):
        tab = ht.make(h)

        def body(i, tab):
            new_tab, _, _ = ht.insert(tab, dsts[i], jnp.int32(i), cfg.max_probes)
            live = cnts[i] > 0
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(live, n, o), new_tab, tab)

        return jax.lax.fori_loop(0, dsts.shape[0], body, tab)

    tabs = jax.vmap(rebuild_row)(state.slabs.dst, state.slabs.cnt)
    return state._replace(dh_keys=tabs.keys, dh_vals=tabs.vals)


# ---------------------------------------------------------------------------
# lookups
# ---------------------------------------------------------------------------


def lookup_rows(state: MCState, src: jax.Array, cfg: MCConfig):
    """Batched src -> row. Returns ``(rows[B], found[B])``; row 0 when missing.

    Routed through the shared open-addressing probe kernel (``ops.ht_find``
    via ``lookup_batch``) so the src lookup at the head of every query and
    update is one fused dispatch on the selected backend.
    """
    rows, found = ht.lookup_batch(state.src_table, src, cfg.max_probes,
                                  impl=cfg.impl)
    return jnp.where(found, rows, 0), found


def _find_slots(state: MCState, rows: jax.Array, dst: jax.Array, cfg: MCConfig):
    """Batched (row, dst) -> slot via dst-hash or row scan (paper §II.2).

    The hash path is a fused kernel dispatch (``ops.dh_find``): one grid
    over row-blocks instead of a vmapped scalar probe loop per item.
    """
    if cfg.use_dst_hash:
        slots, found = ops.dh_find(rows, dst, state.dh_keys, state.dh_vals,
                                   max_probes=cfg.max_probes, impl=cfg.impl)
        return jnp.where(found, slots, 0), found
    slots, found = jax.vmap(
        lambda r, d: sl.find_slot(state.slabs, r, d))(rows, dst)
    return slots, found


# ---------------------------------------------------------------------------
# update
# ---------------------------------------------------------------------------


def _aggregate_batch(src, dst, w, active):
    """Collapse in-batch duplicates: B items -> U unique (src, dst) edges.

    Sorts the batch by (inactive, src, dst) — inactive items sink to the
    tail — and segment-sums weights into the first occurrence (*head*) of
    each unique edge.  Returns ``(src, dst, w, head, pos)`` in sorted order
    where ``head`` marks the unique-edge representatives, ``pos`` is each
    head edge's first-occurrence position in the original batch (for
    arrival-order tie-breaks downstream); non-head slots carry
    ``src = dst = -1`` and ``w = 0``.
    """
    b = src.shape[0]
    inactive = (~active).astype(jnp.int32)
    idx = jnp.arange(b, dtype=jnp.int32)
    inact_s, src_s, dst_s, w_s, idx_s = jax.lax.sort(
        (inactive, src, dst, w, idx), num_keys=3, is_stable=True)
    act_s = inact_s == 0
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (src_s[1:] != src_s[:-1]) | (dst_s[1:] != dst_s[:-1])])
    head = act_s & first
    # segment id of each item = index of its head; heads are in ascending
    # order so the cumsum is sorted (segment_sum fast path)
    seg = jnp.clip(jnp.cumsum(head.astype(jnp.int32)) - 1, 0, b - 1)
    sums = jax.ops.segment_sum(jnp.where(act_s, w_s, 0), seg,
                               num_segments=b, indices_are_sorted=True)
    mins = jax.ops.segment_min(jnp.where(act_s, idx_s, b), seg,
                               num_segments=b, indices_are_sorted=True)
    u_w = jnp.where(head, sums[seg], 0).astype(w.dtype)
    u_src = jnp.where(head, src_s, -1)
    u_dst = jnp.where(head, dst_s, -1)
    u_pos = jnp.where(head, mins[seg], b).astype(jnp.int32)
    return u_src, u_dst, u_w, head, u_pos


def _take_new_prefix(src, dst, w, pos, new_mask, limit: int):
    """Stable-partition new-edge items to the front, truncated to ``limit``.

    Ties inside the partition break by ``pos`` (original arrival order), so
    a tight ``max_new_per_batch`` admits the earliest-arriving new edges
    instead of starving high node-ids (the seed's "batch order wins" rule).
    Returns ``(src[limit], dst[limit], w[limit], mask[limit], overflow)``
    where ``overflow`` counts new edges that did not fit in the prefix.
    """
    key = (~new_mask).astype(jnp.int32)
    key_s, _, p_src, p_dst, p_w = jax.lax.sort(
        (key, pos, src, dst, w), num_keys=2, is_stable=True)
    p_mask = key_s[:limit] == 0
    overflow = (jnp.sum(new_mask.astype(jnp.int32))
                - jnp.sum(p_mask.astype(jnp.int32)))
    return p_src[:limit], p_dst[:limit], p_w[:limit], p_mask, overflow


def _slow_path(state: MCState, src, dst, w, active, cfg: MCConfig) -> MCState:
    """Sequential insert pass for new edges / new rows (the paper's rare case).

    Deterministic (batch order), fully masked — inactive items are no-ops.
    """
    n_cap = cfg.num_rows

    def step(state: MCState, item):
        s, d, wi, a = item
        # --- src row (lookup or allocate) -------------------------------
        row0, found_src = ht.lookup(state.src_table, s, cfg.max_probes)
        can_alloc = state.n_rows < n_cap
        do_alloc = a & ~found_src & can_alloc
        row = jnp.where(found_src, row0, state.n_rows)
        new_tab, _, ins_ok = ht.insert(state.src_table, s, row, cfg.max_probes)
        take_ins = do_alloc & ins_ok
        src_table = jax.tree_util.tree_map(
            lambda n, o: jnp.where(take_ins, n, o), new_tab, state.src_table)
        n_rows = state.n_rows + jnp.where(take_ins, 1, 0)
        dropped_rows = state.dropped_rows + jnp.where(a & ~found_src & ~can_alloc, 1, 0)
        dropped_probes = state.dropped_probes + jnp.where(do_alloc & ~ins_ok, 1, 0)
        have_row = found_src | take_ins
        act = a & have_row
        row = jnp.where(have_row, row, 0)

        # --- dst slot (find / free / Space-Saving tail replace) ---------
        slabs = state.slabs
        slot_eq, found_d = sl.find_slot(slabs, row, d)
        slot_free, has_free = sl.free_slot(slabs, row)
        victim = sl.tail_slot(slabs, row)
        slot = jnp.where(found_d, slot_eq, jnp.where(has_free, slot_free, victim))
        replace = act & ~found_d & ~has_free
        evicted_dst = slabs.dst[row, slot]
        # Space-Saving: the newcomer inherits the evicted count (overestimate)
        base = jnp.where(found_d, slabs.cnt[row, slot],
                         jnp.where(has_free, 0, slabs.cnt[row, slot]))
        new_c = base + wi
        cnt = slabs.cnt.at[row, slot].set(jnp.where(act, new_c, slabs.cnt[row, slot]))
        dstv = slabs.dst.at[row, slot].set(jnp.where(act, d, slabs.dst[row, slot]))
        tot = slabs.tot.at[row].add(jnp.where(act, wi, 0))
        slabs = Slabs(dst=dstv, cnt=cnt, tot=tot, order=slabs.order)
        state = state._replace(
            src_table=src_table, slabs=slabs, n_rows=n_rows,
            dropped_rows=dropped_rows, dropped_probes=dropped_probes,
            evictions=state.evictions + jnp.where(replace, 1, 0))
        if cfg.use_dst_hash:
            state = _dh_del(state, row, evicted_dst, replace, cfg)
            state = _dh_set(state, row, d, slot, act & ~found_d, cfg)
        return state, None

    state, _ = jax.lax.scan(step, state, (src, dst, w, active))
    return state


def update_batch_impl(
    state: MCState,
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    *,
    cfg: MCConfig,
) -> MCState:
    """Traced body of :func:`update_batch` — the full kernel-routed pipeline
    with no jit boundary of its own.

    Call this (not ``update_batch``) from inside another traced context such
    as the shard_map bodies in ``core/sharded.py``: the kernel dispatches
    (``ops.slab_update`` / ``ops.ht_find`` / ``ops.oddeven_sort``) then inline
    directly into the caller's program instead of nesting a jit call.
    """
    b = src.shape[0]
    w = jnp.ones((b,), jnp.int32) if weights is None else weights.astype(jnp.int32)
    m = jnp.ones((b,), bool) if mask is None else mask
    m = m & (src >= 0) & (dst >= 0)

    # (1) pre-aggregate: B items -> U unique edges (duplicates never pay a
    # slow-path step again)
    u_src, u_dst, u_w, u_act, u_pos = _aggregate_batch(src, dst, w, m)

    # (2) classify against the pre-state: edge exists <=> fast
    rows0, found_src0 = lookup_rows(state, u_src, cfg)
    _, found_d0 = _find_slots(state, rows0, u_dst, cfg)
    fast = u_act & found_src0 & found_d0

    # (3) fast path: fused batched increment through the kernel layer (the
    # batched equivalent of the paper's atomic fetch-add)
    slabs = state.slabs
    cnt, tot = ops.slab_update(
        jnp.where(fast, rows0, -1), u_dst, u_w,
        slabs.dst, slabs.cnt, slabs.tot, impl=cfg.impl)
    state = state._replace(slabs=Slabs(slabs.dst, cnt, tot, slabs.order))

    # (4) slow path: new edges only, partitioned to a static prefix so the
    # sequential scan is O(max_new), and skipped entirely when empty.  A
    # second, 4x-shorter scan tier handles the common "a few new edges"
    # case so the cost tracks the actual new-edge count, not the bound.
    new_mask = u_act & ~fast
    limit = cfg.resolved_max_new(b)
    p_src, p_dst, p_w, p_mask, overflow = _take_new_prefix(
        u_src, u_dst, u_w, u_pos, new_mask, limit)
    state = state._replace(deferred_new=state.deferred_new + overflow)
    n_new = jnp.sum(p_mask.astype(jnp.int32))
    small = max(limit // 4, 1)

    def run_prefix(n):
        return lambda st: _slow_path(
            st, p_src[:n], p_dst[:n], p_w[:n], p_mask[:n], cfg)

    with jax.named_scope("mcq.new_edge_scan"):
        if small < limit:
            state = jax.lax.cond(
                n_new == 0, lambda st: st,
                lambda st: jax.lax.cond(
                    n_new <= small, run_prefix(small), run_prefix(limit),
                    st),
                state)
        else:
            state = jax.lax.cond(
                n_new == 0, lambda st: st, run_prefix(limit), state)

    # (5) lock-free bubble sort, through the kernel layer
    if cfg.sort_passes:
        slabs = state.slabs
        order = ops.oddeven_sort(slabs.cnt, slabs.order,
                                 passes=cfg.sort_passes, impl=cfg.impl)
        state = state._replace(
            slabs=Slabs(slabs.dst, slabs.cnt, slabs.tot, order))
    return state


@functools.partial(jax.jit, static_argnames=("cfg",))
def update_batch(
    state: MCState,
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    *,
    cfg: MCConfig,
) -> MCState:
    """Apply a batch of transitions ``src[i] -> dst[i]`` (paper §II.A).

    Pipeline: pre-aggregate duplicates, fused fast-path increment
    (``ops.slab_update``), bounded sequential slow path for new edges
    (skipped via ``lax.cond`` when the batch has none), then
    ``cfg.sort_passes`` odd-even passes (``ops.oddeven_sort``).
    jit wrapper over :func:`update_batch_impl`.
    """
    return update_batch_impl(state, src, dst, weights, mask, cfg=cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def update_batch_reference(
    state: MCState,
    src: jax.Array,
    dst: jax.Array,
    weights: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    *,
    cfg: MCConfig,
) -> MCState:
    """Pre-kernel oracle for :func:`update_batch` (the seed implementation).

    Inline scatter-add fast path + an O(B) sequential slow path that walks
    every batch item.  Kept as the semantic ground truth for equivalence
    tests and as the benchmark baseline; ``max_new_per_batch``/``impl`` are
    deliberately ignored here.
    """
    b = src.shape[0]
    w = jnp.ones((b,), jnp.int32) if weights is None else weights.astype(jnp.int32)
    m = jnp.ones((b,), bool) if mask is None else mask
    m = m & (src >= 0) & (dst >= 0)

    # classify against the pre-state: edge exists <=> fast
    rows0, found_src0 = lookup_rows(state, src, cfg)
    slots0, found_d0 = _find_slots(state, rows0, dst, cfg)
    fast = m & found_src0 & found_d0

    # fast path: scatter-add (duplicates aggregate, like contended atomics)
    add_w = jnp.where(fast, w, 0)
    slabs = state.slabs
    cnt = slabs.cnt.at[rows0, slots0].add(add_w)
    tot = slabs.tot.at[rows0].add(add_w)
    state = state._replace(slabs=Slabs(slabs.dst, cnt, tot, slabs.order))

    # slow path: everything else, sequential + masked
    state = _slow_path(state, src, dst, w, m & ~fast, cfg)

    # lock-free bubble sort, vectorised
    slabs = state.slabs
    order = sl.oddeven_passes(slabs.cnt, slabs.order, cfg.sort_passes)
    return state._replace(slabs=Slabs(slabs.dst, slabs.cnt, slabs.tot, order))


# ---------------------------------------------------------------------------
# inference
# ---------------------------------------------------------------------------


def _ordered_rows(state: MCState, src: jax.Array, cfg: MCConfig):
    """Gather counts/dsts of each queried row in priority order.

    The **unfused** layout transform (three O(B*C) host-side gathers) kept
    as the baseline the fused path must match bit-for-bit
    (``cfg.fused_query=False``; DESIGN.md §8): counts of unknown srcs are
    zeroed so downstream liveness tests (``c > 0``) subsume the ``found``
    mask.
    """
    rows, found = lookup_rows(state, src, cfg)
    order = state.slabs.order[rows]                       # [B, C]
    c = jnp.take_along_axis(state.slabs.cnt[rows], order, axis=1)
    d = jnp.take_along_axis(state.slabs.dst[rows], order, axis=1)
    c = jnp.where(found[:, None], c, 0)
    return c, d, state.slabs.tot[rows], found


def query_impl(state: MCState, src: jax.Array, threshold, cfg: MCConfig,
               max_items: int):
    """Shared inference dispatch: fused in-kernel row gather by default
    (``ops.ht_find`` probe + ``ops.cdf_query_fused``), the unfused
    ``_ordered_rows`` + ``cdf_query`` pipeline otherwise.  ``threshold=None``
    is top-k mode (every live item).  Un-jitted traced body — the shard_map
    bodies in ``core/sharded.py`` call it directly."""
    if cfg.fused_query:
        rows, found = lookup_rows(state, src, cfg)
        return ops.cdf_query_fused(
            rows, found, state.slabs.cnt, state.slabs.dst, state.slabs.order,
            state.slabs.tot, threshold, max_items=max_items,
            chunks=cfg.query_chunks, impl=cfg.impl)
    c, d, tot, _ = _ordered_rows(state, src, cfg)
    return ops.cdf_query(c, d, tot, threshold, max_items=max_items,
                         chunks=cfg.query_chunks, impl=cfg.impl)


@functools.partial(jax.jit, static_argnames=("cfg", "max_items"))
def query_threshold(
    state: MCState,
    src: jax.Array,
    threshold: float,
    *,
    cfg: MCConfig,
    max_items: int = 16,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Items in descending probability until cumulative prob >= threshold.

    Returns ``(dsts[B, max_items], probs[B, max_items], n_needed[B])`` where
    entries past ``n_needed`` are EMPTY/0.  ``n_needed`` is the paper's
    CDF^-1(t): how many items a reader must touch.  Unknown srcs yield 0.
    Runs through the kernel layer (``ops.cdf_query_fused`` /
    ``ops.cdf_query`` per ``cfg.fused_query``; DESIGN.md §8).
    """
    return query_impl(state, src, threshold, cfg, max_items)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def query_topk(state: MCState, src: jax.Array, *, cfg: MCConfig, k: int = 8):
    """Top-k edges by (approximate) probability. ``(dsts[B,k], probs[B,k])``.

    Top-k is the kernel's explicit ``threshold=None`` mode (keep every live
    item), sharing the fused CDF walk.
    """
    dk, pk, _ = query_impl(state, src, None, cfg, k)
    return dk, pk


# ---------------------------------------------------------------------------
# decay (paper §II.C) — incremental maintenance subsystem (DESIGN.md §6)
# ---------------------------------------------------------------------------


def _dh_repair_rows(state: MCState, row0: jax.Array, block_rows: int,
                    cfg: MCConfig) -> MCState:
    """Incremental dst-hash repair after a block decay.

    Every dst-hash entry stores the slot it points at, so repair is one
    vectorised gather over the touched block: tombstone each occupied lane
    whose slot died (cnt == 0).  No probe loops, no per-row rebuild —
    O(block_rows * H) VPU work.  Tombstones accumulate in ``dh_tombstones``
    (decay-side only; probes walk through TOMB so lookups stay correct, just
    gradually slower) and a full rebuild runs once they cross
    ``dh_rebuild_fraction`` of the total hash capacity.
    """
    if not cfg.use_dst_hash:
        return state
    h = cfg.resolved_dst_table_size()
    keys_b = jax.lax.dynamic_slice(state.dh_keys, (row0, 0), (block_rows, h))
    vals_b = jax.lax.dynamic_slice(state.dh_vals, (row0, 0), (block_rows, h))
    cnt_b = jax.lax.dynamic_slice(state.slabs.cnt, (row0, 0),
                                  (block_rows, cfg.capacity))
    occupied = keys_b >= 0
    pointed_cnt = jnp.take_along_axis(
        cnt_b, jnp.clip(vals_b, 0, cfg.capacity - 1), axis=1)
    dead = occupied & (pointed_cnt == 0)
    keys_b = jnp.where(dead, TOMB, keys_b)
    state = state._replace(
        dh_keys=jax.lax.dynamic_update_slice(state.dh_keys, keys_b, (row0, 0)),
        dh_tombstones=state.dh_tombstones + jnp.sum(dead.astype(jnp.int32)))

    threshold = jnp.int32(cfg.dh_rebuild_fraction * cfg.num_rows * h)

    def rebuild(s):
        s = _dh_rebuild_all(s, cfg)
        return s._replace(dh_tombstones=jnp.int32(0),
                          dh_rebuilds=s.dh_rebuilds + 1)

    return jax.lax.cond(state.dh_tombstones > threshold,
                        rebuild, lambda s: s, state)


def decay_impl(state: MCState, *, cfg: MCConfig) -> MCState:
    """Traced body of :func:`decay` (no jit boundary — shard bodies call it
    directly, so every shard keeps its own rolling ``decay_cursor``)."""
    n, c = cfg.num_rows, cfg.capacity
    r = cfg.resolved_decay_rows()
    slabs = state.slabs
    if r >= n:  # stop-the-world: one fused full-table dispatch
        cnt, dst, order, tot = ops.decay_sort(
            slabs.cnt, slabs.dst, slabs.order, impl=cfg.impl)
        state = state._replace(
            slabs=Slabs(dst, cnt, tot, order),
            decay_steps=state.decay_steps + 1)
        return _dh_repair_rows(state, jnp.int32(0), n, cfg)

    n_blocks = -(-n // r)
    cur = jnp.remainder(state.decay_cursor, n_blocks)
    # last block is clamped so slices stay static-shaped (it overlaps the
    # previous block when r does not divide n; halving is not idempotent per
    # row but each call still touches exactly r rows — bounded work wins)
    row0 = jnp.minimum(cur * r, n - r).astype(jnp.int32)
    cnt_b = jax.lax.dynamic_slice(slabs.cnt, (row0, 0), (r, c))
    dst_b = jax.lax.dynamic_slice(slabs.dst, (row0, 0), (r, c))
    ord_b = jax.lax.dynamic_slice(slabs.order, (row0, 0), (r, c))
    cnt2, dst2, ord2, tot2 = ops.decay_sort(cnt_b, dst_b, ord_b, impl=cfg.impl)
    state = state._replace(
        slabs=Slabs(
            dst=jax.lax.dynamic_update_slice(slabs.dst, dst2, (row0, 0)),
            cnt=jax.lax.dynamic_update_slice(slabs.cnt, cnt2, (row0, 0)),
            tot=jax.lax.dynamic_update_slice(slabs.tot, tot2, (row0,)),
            order=jax.lax.dynamic_update_slice(slabs.order, ord2, (row0, 0))),
        decay_cursor=cur + 1,
        decay_steps=state.decay_steps + 1)
    return _dh_repair_rows(state, row0, r, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decay(state: MCState, *, cfg: MCConfig) -> MCState:
    """§II.C decay through the kernel layer (``ops.decay_sort``).

    Stop-the-world (``decay_block_rows == 0``): halve every counter, evict
    dead edges and compact in one fused dispatch.  Rolling mode
    (``decay_block_rows == R``): halve only the cursor's R-row block and
    advance the cursor, so a serving system amortises maintenance across
    steps — per-call cost scales with R, not ``num_rows``, and readers see
    the paper's approximately-correct mid-maintenance state (some rows
    decayed, some not) instead of a stop-the-world stall.  The dst hash is
    repaired incrementally for the touched block only (``_dh_repair_rows``).
    jit wrapper over :func:`decay_impl`.
    """
    return decay_impl(state, cfg=cfg)


def maybe_decay_impl(state: MCState, *, cfg: MCConfig,
                     total_threshold: int) -> MCState:
    """Traced body of :func:`maybe_decay` (the per-shard maintenance step of
    ``core/sharded.py`` runs this under shard_map)."""
    should = jnp.any(state.slabs.tot > total_threshold)
    return jax.lax.cond(
        should, lambda s: decay_impl(s, cfg=cfg), lambda s: s, state)


def maybe_decay(state: MCState, *, cfg: MCConfig, total_threshold: int) -> MCState:
    """Decay when any row total exceeds ``total_threshold`` (paper §II.C
    suggests decaying "at some threshold over the number of total
    transitions").  In rolling mode each trigger halves one block; the
    threshold keeps firing until the offending row's block comes around, so
    pressure drains over a few calls instead of one stall."""
    return maybe_decay_impl(state, cfg=cfg, total_threshold=total_threshold)


# ---------------------------------------------------------------------------
# invariant checks (used by tests and the property suite)
# ---------------------------------------------------------------------------


def _dh_consistent(state: MCState, cfg: MCConfig) -> jax.Array:
    """Dst-hash invariant: every live slot is reachable through the hash and
    every occupied hash lane points at a live slot holding its key (no stale
    entries after decay/repair)."""
    n, c = state.slabs.dst.shape
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32), c)
    dsts = state.slabs.dst.reshape(-1)
    live = state.slabs.cnt.reshape(-1) > 0
    slots, found = ops.dh_find(
        jnp.where(live, rows, -1), jnp.maximum(dsts, 0),
        state.dh_keys, state.dh_vals,
        max_probes=cfg.max_probes, impl=cfg.impl)
    expect = jnp.tile(jnp.arange(c, dtype=jnp.int32), n)
    live_ok = jnp.all(jnp.where(live, found & (slots == expect), True))
    occupied = state.dh_keys >= 0
    v = jnp.clip(state.dh_vals, 0, c - 1)
    pointed_dst = jnp.take_along_axis(state.slabs.dst, v, axis=1)
    pointed_cnt = jnp.take_along_axis(state.slabs.cnt, v, axis=1)
    stale_ok = jnp.all(jnp.where(
        occupied, (pointed_dst == state.dh_keys) & (pointed_cnt > 0), True))
    return live_ok & stale_ok


def check_invariants(state: MCState, cfg: Optional[MCConfig] = None) -> dict:
    slabs = state.slabs
    order_ok = jnp.all(
        jnp.sort(slabs.order, axis=1)
        == jnp.arange(slabs.order.shape[1], dtype=jnp.int32)[None, :])
    tot_ok = jnp.all(slabs.tot == jnp.sum(slabs.cnt, axis=1))
    free_ok = jnp.all((slabs.cnt == 0) == (slabs.dst == EMPTY))
    nonneg = jnp.all(slabs.cnt >= 0)
    out = {
        "order_is_permutation": bool(order_ok),
        "tot_matches_cnt_sum": bool(tot_ok),
        "free_slots_consistent": bool(free_ok),
        "counts_nonnegative": bool(nonneg),
        "sorted_fraction": float(sl.sorted_fraction(slabs.cnt, slabs.order)),
    }
    if cfg is not None and cfg.use_dst_hash:
        out["dst_hash_consistent"] = bool(_dh_consistent(state, cfg))
    return out


def maintenance_stats(state: MCState) -> dict:
    """Maintenance observability counters (DESIGN.md §6), host-side ints."""
    return {
        "decay_steps": int(state.decay_steps),
        "decay_cursor": int(state.decay_cursor),
        "dh_rebuilds": int(state.dh_rebuilds),
        "dh_tombstones": int(state.dh_tombstones),
    }


_COUNTER_FIELDS = ("n_rows", "dropped_rows", "dropped_probes", "evictions",
                   "deferred_new", "route_dropped", "decay_steps",
                   "dh_rebuilds", "dh_tombstones")


@jax.jit
def _counter_stack(state: MCState) -> jax.Array:
    return jnp.stack([jnp.sum(getattr(state, f)) for f in _COUNTER_FIELDS])


def counter_stats(state: MCState) -> dict:
    """Every additive observability counter as a host-side int.

    Counters are summed over any leading dims, so the same helper reads a
    local ``MCState`` and the stacked per-shard state of ``core/sharded.py``
    (where each counter is ``int32[num_shards]``).  ``decay_cursor`` is a
    position, not a count, and is deliberately excluded.  The sums are one
    fused dispatch and ONE device->host transfer — callers sit on serving
    hot paths (``ShardedEngine.observe`` reads this per batch, inside its
    writer lock).
    """
    vals = jax.device_get(_counter_stack(state))
    return {f: int(v) for f, v in zip(_COUNTER_FIELDS, vals)}
