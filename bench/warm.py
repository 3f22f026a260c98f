"""Warm state, built on the device in one program from the seed.

Per shard ``s`` (one chip each), with ``N`` rows, ``C`` slots, a src table
of ``T`` slots and ``H = held_share * N`` held sources:

* candidate ids ``[0, candidate_ids_per_row * N * S)``; the ones the
  deployment's default ownership map puts on shard ``s`` keep one id per
  src-table home slot (``hash(id) & (T - 1)``), so every held key sits at
  probe 0 exactly where sequential inserts would place it; ``H`` of them,
  in seeded order, become rows ``0..H-1`` (row ``r`` has popularity rank
  ``r`` on its shard);
* every held source has ``out_degree`` successors (the ``full_rows`` most
  popular ones all ``C``, so new successors there evict), each with
  ``base_count``, plus ``events_per_row * N`` events drawn Zipf(src_zipf)
  over rows and Zipf(rank_zipf) over the first ``out_degree`` successor
  ranks (slot = rank);
* ``order`` is the exact descending order (stable), so the first
  odd-even pass changes nothing.

The state is laid out as the engine's ``MCState`` stacked over shards and
sharded over the mesh's ``shard`` axis; it is seated through the engine's
epoch store.  ``row_ids`` and ``counts`` go to the host for the traffic
and the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from generator import RANK_MULT, SUCC_MULT

NUM_BUCKETS = 256


def hash_u32(x):
    """splitmix32-style avalanche of the deployment's key hash."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def owner_of(ids, num_shards: int):
    """Default ownership: hash -> one of 256 virtual buckets -> bucket mod S."""
    b = (hash_u32(ids) >> jnp.uint32(8)) % jnp.uint32(NUM_BUCKETS)
    return (b % jnp.uint32(num_shards)).astype(jnp.int32)


def dst_of(src, rank):
    s = src.astype(jnp.uint32)
    r = rank.astype(jnp.uint32)
    v = s * jnp.uint32(SUCC_MULT) + r * jnp.uint32(RANK_MULT) + jnp.uint32(7)
    return (v & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def sizes(cfg: dict):
    mc, sv, w = cfg["mc"], cfg["serve"], cfg["warm"]
    n, c, s = mc["num_rows"], mc["capacity"], sv["num_shards"]
    t = 1
    while t < 4 * n:
        t *= 2
    return dict(n=n, c=c, s=s, t=t, h=int(w["held_share"] * n),
                k=w["candidate_ids_per_row"] * n * s, deg=w["out_degree"],
                full=min(int(w.get("full_rows", 0)), int(w["held_share"] * n)),
                m=int(w["events_per_row"] * n))


def _zipf_cdf(n: int, s: float):
    w = jnp.arange(1, n + 1, dtype=jnp.float32) ** jnp.float32(-s)
    c = jnp.cumsum(w)
    return c / c[-1]


def _build_shard(seed, shard, cfg: dict, state_types):
    z = sizes(cfg)
    n, c, t, h, k, deg, m = (z["n"], z["c"], z["t"], z["h"], z["k"],
                             z["deg"], z["m"])
    w = cfg["warm"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed[0]),
                                                seed[1]), shard)
    k_prio, k_src, k_rank = jax.random.split(key, 3)
    ids = jnp.arange(k, dtype=jnp.int32)
    hv = hash_u32(ids)
    home = (hv & jnp.uint32(t - 1)).astype(jnp.int32)
    mine = owner_of(ids, z["s"]) == shard
    big = jnp.int32(2 ** 31 - 1)
    best = jnp.full((t,), big, jnp.int32).at[home].min(
        jnp.where(mine, ids, big))
    winner = mine & (best[home] == ids)
    prio = jax.random.bits(k_prio, (k,), jnp.uint32) >> jnp.uint32(1)
    prio = jnp.where(winner, prio, jnp.uint32(0xFFFFFFFF))
    _, ranked = jax.lax.sort_key_val(prio, ids)
    row_ids = ranked[:h]
    n_winners = jnp.sum(winner.astype(jnp.int32))

    rows = jnp.arange(h, dtype=jnp.int32)
    slots = hash_u32(row_ids) & jnp.uint32(t - 1)
    tab_keys = jnp.full((t,), -1, jnp.int32).at[slots].set(row_ids)
    tab_vals = jnp.full((t,), -1, jnp.int32).at[slots].set(rows)

    ev_row = jnp.minimum(jnp.searchsorted(
        _zipf_cdf(h, w["src_zipf"]), jax.random.uniform(k_src, (m,)),
        side="right"), h - 1)
    ev_rank = jnp.minimum(jnp.searchsorted(
        _zipf_cdf(deg, w["rank_zipf"]), jax.random.uniform(k_rank, (m,)),
        side="right"), deg - 1)
    row_deg = jnp.where(jnp.arange(n) < z["full"], c, deg)
    held = ((jnp.arange(n) < h)[:, None]
            & (jnp.arange(c)[None, :] < row_deg[:, None]))
    cnt = jnp.where(held, jnp.int32(w["base_count"]), 0)
    cnt = cnt.at[ev_row, ev_rank].add(1)
    ids_n = jnp.zeros((n,), jnp.int32).at[:h].set(row_ids)
    dst = jnp.where(held, dst_of(ids_n[:, None],
                                 jnp.arange(c, dtype=jnp.int32)[None, :]), -1)
    order = jnp.argsort(-cnt, axis=1, stable=True).astype(jnp.int32)
    tot = jnp.sum(cnt, axis=1).astype(jnp.int32)

    mcstate, hashtable, slabs = state_types
    zero = jnp.int32(0)
    state = mcstate(
        src_table=hashtable(tab_keys, tab_vals),
        slabs=slabs(dst=dst, cnt=cnt, tot=tot, order=order),
        n_rows=jnp.int32(h),
        dh_keys=jnp.full((n, 1), -1, jnp.int32),
        dh_vals=jnp.full((n, 1), -1, jnp.int32),
        dropped_rows=zero, dropped_probes=zero, evictions=zero,
        deferred_new=zero, route_dropped=zero, decay_cursor=zero,
        decay_steps=zero, dh_rebuilds=zero, dh_tombstones=zero)
    return state, row_ids, cnt[:h], n_winners


def make_program(cfg: dict, mesh, state_types, shard_map):
    """The jitted builder ``(seed_words[2], shard_ids[S]) -> (state,
    row_ids[S, H], counts[S, H, C], winners[S])``, sharded over ``mesh``'s
    shard axis."""
    def body(seed, shard):
        out = _build_shard(seed, shard[0], cfg, state_types)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("shard")),
                             out_specs=P("shard")))


def make_builder(cfg: dict, mesh, state_types, shard_map):
    """``build(seed)`` runs :func:`make_program` on ``mesh``."""
    s = sizes(cfg)["s"]
    jitted = make_program(cfg, mesh, state_types, shard_map)
    shard_ids = jax.device_put(jnp.arange(s, dtype=jnp.int32),
                               NamedSharding(mesh, P("shard")))

    def build(seed: int):
        v = int(seed) % (1 << 64)
        words = jnp.asarray([v & 0xFFFFFFFF, v >> 32], jnp.uint32)
        return jitted(words, shard_ids)

    return build
