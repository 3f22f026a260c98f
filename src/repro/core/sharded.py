"""Mesh-sharded MCPrioQ: node-space partitioning with all_to_all routing.

The paper scales by lock-free concurrency on one cache-coherent host.  On a
TPU pod the equivalent scale-out axis is *node-space sharding*: every shard
owns a slice of the graph under the two-level ownership map (hash ->
virtual bucket -> shard, :class:`repro.sharding.ownership.Ownership`;
DESIGN.md §10), a global update batch is routed to owner shards with a
fixed-capacity ``all_to_all`` (the same dispatch shape as MoE
expert-parallel routing), and each shard applies its local update.  Queries
route the same way and the answers are routed back.  The bucket indirection
is what makes the chain *elastic*: reassigning a bucket (rebalancing) or
re-deriving the table at M shards (reshard-on-restore, ``persist/``) moves
keys without touching the routing machinery.

Every per-shard body dispatches the kernel layer directly (DESIGN.md §9):
``_update_local`` runs the pre-aggregated ``ops.slab_update`` pipeline via
:func:`repro.core.mcprioq.update_batch_impl`, ``_query_local`` the fused
``ops.ht_find`` probe + ``ops.cdf_query_fused`` walk via
:func:`repro.core.mcprioq.query_impl`, and ``_maintain_local`` the rolling
``ops.decay_sort`` block decay via :func:`repro.core.mcprioq.decay_impl` —
each shard keeps its own ``decay_cursor``, so maintenance stays O(block) per
call on every shard independently.  The impl bodies carry no jit boundary of
their own: the kernels inline straight into the shard_map program.

Fixed per-destination bucket capacity keeps shapes static (overflowed items
are dropped and counted in ``route_dropped`` / the query drop output, like
the paper's "approximately correct" reads — the observability counter makes
the approximation measurable).

Cross-shard reads: :func:`make_topn_fn` answers the paper's headline query
*globally* — each shard emits its local top-n (per-row priority windows +
one ``lax.top_k``), the answers are all_gathered and k-way merged by
probability (``ops.topn_merge``), returning globally descending n items.
A shard can contribute at most n items to a global top-n, so truncating each
local answer to n is exact relative to each shard's priority order; the
``dropped`` output counts live edges a shard could not expose to the merge
(the fixed-capacity drop model's observability).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import mcprioq as mc
from repro.core.hashtable import EMPTY
from repro.kernels import ops
from repro.sharding.ownership import Ownership


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    base: mc.MCConfig
    num_shards: int
    axis: str = "shard"
    bucket_factor: float = 2.0  # capacity = factor * fair share
    # two-level hash -> virtual bucket -> shard map (DESIGN.md §10); None =
    # the default assignment, which reproduces the legacy static hash for
    # power-of-two shard counts
    ownership: Optional[Ownership] = None

    def bucket_capacity(self, local_batch: int) -> int:
        fair = max(1, local_batch // self.num_shards)
        # never 0: zero-width buckets can route nothing (and break gathers)
        return max(1, int(self.bucket_factor * fair))

    def resolved_ownership(self) -> Ownership:
        own = self.ownership or Ownership(num_shards=self.num_shards)
        if own.num_shards != self.num_shards:
            raise ValueError(
                f"ownership maps {own.num_shards} shards but config has "
                f"{self.num_shards}")
        return own


def owner_of(src: jax.Array, num_shards: int) -> jax.Array:
    """Owner shard of a node id under the *default* two-level map (kept as
    the module-level convenience; routed configs use
    ``ShardedConfig.resolved_ownership().owner_of``)."""
    return Ownership(num_shards=num_shards).owner_of(src)


def init_sharded(cfg: ShardedConfig, mesh: jax.sharding.Mesh) -> mc.MCState:
    """Global state: every array gains a leading ``num_shards`` dim, sharded
    over ``cfg.axis``. Inside shard_map each shard sees its own MCState.

    Built by one program whose outputs are sharded, so each device
    materialises only its own shard (no device ever holds the stack)."""
    def build():
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (cfg.num_shards,) + x.shape),
            mc.init(cfg.base))

    sharding = jax.sharding.NamedSharding(mesh, P(cfg.axis))
    return jax.jit(build, out_shardings=sharding)()


def _state_spec(scfg: ShardedConfig):
    return jax.tree_util.tree_map(lambda _: P(scfg.axis), mc.init(scfg.base))


# ---------------------------------------------------------------------------
# bucket building (per-shard local work)
# ---------------------------------------------------------------------------


def _build_buckets(vals_list, owner: jax.Array, num_shards: int, cap: int,
                   active: jax.Array = None):
    """Scatter items into [num_shards, cap] send buckets grouped by owner.

    Returns (buckets..., pos, dropped) where ``pos[i]`` is the in-bucket slot
    of item i (>= cap means dropped). Deterministic: stable sort by owner.
    Inactive items (``active`` False — batch padding) are routed to a
    nonexistent shard: they consume no bucket capacity, never displace real
    items, and are excluded from the drop count (their ``pos`` is garbage;
    callers must mask on ``active``).
    """
    b = owner.shape[0]
    if active is not None:
        owner = jnp.where(active, owner, num_shards)
    sort_idx = jnp.argsort(owner, stable=True)
    owner_s = owner[sort_idx]
    starts = jnp.searchsorted(owner_s, jnp.arange(num_shards, dtype=owner.dtype))
    pos_s = (jnp.arange(b, dtype=jnp.int32)
             - starts[jnp.minimum(owner_s, num_shards - 1)])
    outs = []
    for v in vals_list:
        buf = jnp.full((num_shards, cap) + v.shape[1:], EMPTY, v.dtype)
        # out-of-capacity positions (and inactive items) fall off via "drop"
        buf = buf.at[owner_s, pos_s].set(v[sort_idx], mode="drop")
        outs.append(buf)
    # per-item position in original order
    pos = jnp.zeros((b,), jnp.int32).at[sort_idx].set(pos_s)
    real = owner_s < num_shards
    dropped = jnp.sum(((pos_s >= cap) & real).astype(jnp.int32))
    return outs, pos, dropped


def predict_route_overflow(scfg: ShardedConfig, src) -> "np.ndarray":
    """Host-side mirror of :func:`_build_buckets`'s capacity drop decision.

    ``src`` must already be padded to a multiple of ``num_shards`` (the
    engine's ``_pad`` contract): the sharded batch splits into
    ``num_shards`` contiguous sender slices of length ``B/num_shards``,
    and each slice independently drops the items ranked ``>= cap`` within
    their owner group (stable order).  Returns a bool mask, True exactly
    where the device update/query path would drop the item — the overflow
    retry tier masks those items out *before* dispatch and resubmits them
    next step, so ``route_dropped`` stays 0 while the tier is on.

    Must stay bit-faithful to ``_build_buckets`` (same stable sort, same
    searchsorted starts, same ``bucket_capacity``); the fault-matrix test
    asserts prediction == device behaviour over random skewed batches.
    """
    import numpy as np  # host-only helper; keep the module's jnp surface

    src = np.asarray(src)
    n = scfg.num_shards
    if src.size % n:
        raise ValueError(f"batch of {src.size} not padded to a multiple "
                         f"of num_shards={n}")
    local = src.size // n
    cap = scfg.bucket_capacity(local)
    owner = np.asarray(scfg.resolved_ownership().owner_of(
        jnp.asarray(src, jnp.int32)))
    active = src >= 0
    owner = np.where(active, owner, n)
    out = np.zeros(src.size, dtype=bool)
    for s in range(n):
        sl = slice(s * local, (s + 1) * local)
        own_s = owner[sl]
        sort_idx = np.argsort(own_s, kind="stable")
        owner_sorted = own_s[sort_idx]
        starts = np.searchsorted(owner_sorted, np.arange(n))
        pos_s = (np.arange(local)
                 - starts[np.minimum(owner_sorted, n - 1)])
        drop_sorted = (pos_s >= cap) & (owner_sorted < n)
        drop = np.zeros(local, dtype=bool)
        drop[sort_idx] = drop_sorted
        out[sl] = drop
    return out


def _src_of_row(state: mc.MCState, num_rows: int) -> jax.Array:
    """Reverse map row -> src node id, rebuilt from the src hash table by one
    scatter (invalid table lanes fall off via an out-of-range index)."""
    tab = state.src_table
    valid = (tab.keys >= 0) & (tab.vals >= 0)
    idx = jnp.where(valid, tab.vals, num_rows)
    return jnp.full((num_rows,), EMPTY, jnp.int32).at[idx].set(
        tab.keys, mode="drop")


# ---------------------------------------------------------------------------
# per-shard bodies (call under shard_map; wrappers below)
# ---------------------------------------------------------------------------


def _update_local(state, src, dst, w, scfg: ShardedConfig):
    """Per-shard body: route then apply the kernel-routed update pipeline
    (pre-aggregation + ``ops.slab_update`` + bounded slow path +
    ``ops.oddeven_sort`` via ``update_batch_impl``).  ``state`` leading dim
    is 1; bucket-overflow drops land in ``route_dropped``."""
    state = jax.tree_util.tree_map(lambda x: x[0], state)
    n, cap = scfg.num_shards, scfg.bucket_capacity(src.shape[0])
    (bsrc, bdst, bw), _, dropped = _build_buckets(
        [src, dst, w], scfg.resolved_ownership().owner_of(src), n, cap,
        active=src >= 0)
    rsrc = jax.lax.all_to_all(bsrc, scfg.axis, 0, 0, tiled=True)
    rdst = jax.lax.all_to_all(bdst, scfg.axis, 0, 0, tiled=True)
    rw = jax.lax.all_to_all(bw, scfg.axis, 0, 0, tiled=True)
    rsrc, rdst, rw = (x.reshape(-1) for x in (rsrc, rdst, rw))
    state = mc.update_batch_impl(state, rsrc, rdst, weights=rw,
                                 mask=rsrc != EMPTY, cfg=scfg.base)
    state = state._replace(route_dropped=state.route_dropped + dropped)
    return jax.tree_util.tree_map(lambda x: x[None], state)


def _query_local(state, src, threshold, max_items, scfg: ShardedConfig):
    """Per-shard body: route queries to owners, answer through the fused
    kernel read path (``query_impl``), route answers back.  Returns
    ``(dsts, probs, n_needed, dropped[1])`` — ``dropped`` counts queries
    this shard could not route (bucket overflow; answers are EMPTY/0)."""
    state = jax.tree_util.tree_map(lambda x: x[0], state)
    n, cap = scfg.num_shards, scfg.bucket_capacity(src.shape[0])
    act = src >= 0
    (bsrc,), pos, dropped = _build_buckets(
        [src], scfg.resolved_ownership().owner_of(src), n, cap, active=act)
    rsrc = jax.lax.all_to_all(bsrc, scfg.axis, 0, 0, tiled=True)
    d, p, need = mc.query_impl(
        state, rsrc.reshape(-1), threshold, scfg.base, max_items)
    d = d.reshape(n, cap, max_items)
    p = p.reshape(n, cap, max_items)
    need = need.reshape(n, cap)
    # route answers back to the requesting shard
    d = jax.lax.all_to_all(d, scfg.axis, 0, 0, tiled=True)
    p = jax.lax.all_to_all(p, scfg.axis, 0, 0, tiled=True)
    need = jax.lax.all_to_all(need, scfg.axis, 0, 0, tiled=True)
    # un-permute: item i sits at [owner[i], pos[i]]
    own = scfg.resolved_ownership().owner_of(src)
    ok = (pos < cap) & (pos >= 0) & act
    gi = jnp.clip(pos, 0, cap - 1)
    di = d[own, gi]
    pi = p[own, gi]
    ni = need[own, gi]
    di = jnp.where(ok[:, None], di, EMPTY)
    pi = jnp.where(ok[:, None], pi, 0.0)
    ni = jnp.where(ok, ni, 0)
    return di, pi, ni, dropped[None]


def _maintain_local(state, scfg: ShardedConfig, total_threshold: int):
    """Per-shard §II.C maintenance: rolling ``ops.decay_sort`` block decay
    behind the row-total trigger.  Each shard carries its own
    ``decay_cursor``, so per-call cost is O(decay_block_rows) everywhere."""
    state = jax.tree_util.tree_map(lambda x: x[0], state)
    state = mc.maybe_decay_impl(state, cfg=scfg.base,
                                total_threshold=total_threshold)
    return jax.tree_util.tree_map(lambda x: x[None], state)


def _decay_local(state, scfg: ShardedConfig):
    """Per-shard unconditional decay step (one rolling block per shard)."""
    state = jax.tree_util.tree_map(lambda x: x[0], state)
    state = mc.decay_impl(state, cfg=scfg.base)
    return jax.tree_util.tree_map(lambda x: x[None], state)


def _topn_local(state, n: int, scfg: ShardedConfig):
    """Per-shard body of the global top-n read (DESIGN.md §9).

    Local answer: each row exposes its ``min(n, C)``-item priority window
    (one order gather), a single ``lax.top_k`` over the flattened windows
    picks the shard's n best edges, and the row -> src reverse map labels
    them.  Cross-shard: all_gather the S local answers and k-way merge by
    probability (``ops.topn_merge``).  ``dropped`` counts live edges not
    exposed to the merge — exactness is bounded by the approximate order,
    not by the truncation (a shard contributes at most n items globally).
    """
    cfg = scfg.base
    state = jax.tree_util.tree_map(lambda x: x[0], state)
    slabs = state.slabs
    k = min(n, cfg.capacity)
    ord_k = slabs.order[:, :k]                           # [N, k] heads
    cnt_k = jnp.take_along_axis(slabs.cnt, ord_k, axis=1)
    dst_k = jnp.take_along_axis(slabs.dst, ord_k, axis=1)
    totf = jnp.maximum(slabs.tot, 1).astype(jnp.float32)
    prob_k = jnp.where(cnt_k > 0,
                       cnt_k.astype(jnp.float32) / totf[:, None], 0.0)
    src_of_row = _src_of_row(state, cfg.num_rows)        # [N]
    top_p, top_i = jax.lax.top_k(prob_k.reshape(-1), n)
    live_top = top_p > 0
    top_dst = jnp.where(live_top, dst_k.reshape(-1)[top_i], EMPTY)
    top_src = jnp.where(live_top, src_of_row[top_i // k], EMPTY)
    live = jnp.sum((slabs.cnt > 0).astype(jnp.int32))
    dropped = live - jnp.sum(live_top.astype(jnp.int32))
    ps = jax.lax.all_gather(top_p, scfg.axis)            # [S, n] each
    ds = jax.lax.all_gather(top_dst, scfg.axis)
    ss = jax.lax.all_gather(top_src, scfg.axis)
    m_src, m_dst, m_p = ops.topn_merge(ps, ds, ss, n=n, impl=cfg.impl)
    return m_src, m_dst, m_p, jax.lax.psum(dropped, scfg.axis)


# ---------------------------------------------------------------------------
# public pjit-able wrappers
# ---------------------------------------------------------------------------


def make_update_fn(scfg: ShardedConfig, mesh: jax.sharding.Mesh):
    """Returns jitted ``(state, src[B], dst[B], w[B]) -> state`` with batch
    data-sharded over the shard axis and state node-sharded."""
    a = scfg.axis
    state_spec = _state_spec(scfg)

    @functools.partial(
        compat.shard_map, mesh=mesh,
        in_specs=(state_spec, P(a), P(a), P(a)), out_specs=state_spec)
    def fn(state, src, dst, w):
        return _update_local(state, src, dst, w, scfg)

    return jax.jit(fn)


def make_query_fn(scfg: ShardedConfig, mesh: jax.sharding.Mesh,
                  threshold: float, max_items: int):
    """Returns jitted ``(state, src[B]) -> (dsts[B, max_items],
    probs[B, max_items], n_needed[B], dropped[num_shards])``; ``dropped``
    counts queries lost to bucket overflow, per requesting shard."""
    a = scfg.axis
    state_spec = _state_spec(scfg)

    @functools.partial(
        compat.shard_map, mesh=mesh,
        in_specs=(state_spec, P(a)), out_specs=(P(a), P(a), P(a), P(a)))
    def fn(state, src):
        return _query_local(state, src, threshold, max_items, scfg)

    return jax.jit(fn)


def make_maintain_fn(scfg: ShardedConfig, mesh: jax.sharding.Mesh,
                     total_threshold: int):
    """Returns jitted ``state -> state`` running the per-shard rolling
    maintenance step (decay one block on every shard whose row totals
    crossed ``total_threshold``)."""
    state_spec = _state_spec(scfg)

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(state_spec,), out_specs=state_spec)
    def fn(state):
        return _maintain_local(state, scfg, total_threshold)

    return jax.jit(fn)


def make_decay_fn(scfg: ShardedConfig, mesh: jax.sharding.Mesh):
    """Returns jitted ``state -> state``: one unconditional decay step per
    shard (rolling block when ``decay_block_rows`` is set)."""
    state_spec = _state_spec(scfg)

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(state_spec,), out_specs=state_spec)
    def fn(state):
        return _decay_local(state, scfg)

    return jax.jit(fn)


def make_topn_fn(scfg: ShardedConfig, mesh: jax.sharding.Mesh, n: int):
    """Returns jitted ``state -> (srcs[n], dsts[n], probs[n], dropped)``:
    the globally descending top-n edges of the whole sharded chain, plus the
    count of live edges the shards could not expose to the merge.  Outputs
    are replicated (every shard computes the same merge)."""
    state_spec = _state_spec(scfg)

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(state_spec,), out_specs=(P(), P(), P(), P()))
    def fn(state):
        return _topn_local(state, n, scfg)

    return jax.jit(fn)
