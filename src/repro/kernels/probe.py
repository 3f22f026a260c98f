"""Pallas TPU kernel: shared open-addressing probe (paper §II.1-2).

One lane-parallel linear-probe kernel serves every hash lookup in the
system.  The table layout is always ``keys/vals[N, H]`` — a stack of N
open-addressing tables probed independently:

  * **per-row dst hash** (paper §II.2 "optional optimization"): N = slab
    rows, H = per-row table size; ``rows[i]`` selects which table item i
    probes (``ops.dh_find``).
  * **flat src table** (paper §II.1, the node-id -> row lookup at the head
    of every query): N = 1, H = the table size; all items probe table 0
    (``ops.ht_find`` — the kernelized ``hashtable.lookup_batch``).

The stack is read as one row-major slot array cut into tiles: eight whole
per-row tables, or 1024 slots (8 x 128) of a large flat table.  Grid step
i resolves query i: its table base ``rows[i] * H`` and home slot ride in
SMEM (scalar prefetch), and the BlockSpec index maps DMA the tile holding
the home slot plus the tile after it (wrapping), so the probe window
always lies in the two tiles and a query moves two tiles of keys and of
values whatever the table size.  A per-row table lies inside one tile; the
flat table's window (``max_probes <= 1024``) wraps from its last tile into
its first.

The linear-probe loop is vectorised across the window instead of iterated:
for a query key ``d`` with home slot ``h0``, slot ``s`` of the table sits
at probe position ``p = (s - h0) mod H``.  The probe semantics of
``hashtable.lookup`` — scan from ``h0``, stop at the key or the first EMPTY,
give up after ``max_probes`` — become three reductions over the two tiles:

  key_p   = min p over slots holding the key      (H if none in window)
  empty_p = min p over slots holding EMPTY        (H if none in window)
  found   = key_p < empty_p                       (TOMB slots just probe on)

Min/max reductions make a slot seen twice harmless (a table smaller than
two tiles is loaded twice).  Results are written to SMEM outputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashtable import EMPTY, hash_u32

LANES = 128
TILE_ROWS = 8


def _probe_kernel(keys_q_ref, base_ref, home_ref, k0_ref, k1_ref, v0_ref,
                  v1_ref, slot_out_ref, found_out_ref, *, h: int,
                  tile_slots: int, n_tiles: int, max_probes: int):
    i = pl.program_id(0)
    d = keys_q_ref[i]
    base = base_ref[i]
    home = home_ref[i]
    g0 = home // tile_slots
    g1 = (g0 + 1) % n_tiles
    shape = k0_ref.shape
    pos = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    big = jnp.int32(h)

    def window(g, keys, vals):
        rel = g * tile_slots + pos - base       # slot within this table
        p = (rel - (home - base)) & (h - 1)     # probe position
        in_win = (rel >= 0) & (rel < h) & (p < max_probes)
        is_key = in_win & (keys == d)
        key_p = jnp.min(jnp.where(is_key, p, big))
        empty_p = jnp.min(jnp.where(in_win & (keys == EMPTY), p, big))
        return p, is_key, vals, key_p, empty_p

    p0, is_key0, vals0, key_p0, empty_p0 = window(g0, k0_ref[...],
                                                  v0_ref[...])
    p1, is_key1, vals1, key_p1, empty_p1 = window(g1, k1_ref[...],
                                                  v1_ref[...])
    key_p = jnp.minimum(key_p0, key_p1)
    empty_p = jnp.minimum(empty_p0, empty_p1)
    found = (base >= 0) & (key_p < empty_p)
    slot = jnp.maximum(
        jnp.max(jnp.where(is_key0 & (p0 == key_p), vals0, EMPTY)),
        jnp.max(jnp.where(is_key1 & (p1 == key_p), vals1, EMPTY)))
    slot_out_ref[i] = jnp.where(found, slot, EMPTY)
    found_out_ref[i] = found.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_probes", "interpret"))
def probe_find_pallas(rows: jax.Array, keys_q: jax.Array,
                      tab_keys: jax.Array, tab_vals: jax.Array,
                      *, max_probes: int = 64, interpret: bool):
    """Batched open-addressing probe. rows[B] select a table out of
    ``tab_keys/tab_vals[N, H]`` (rows < 0 = padding); keys_q[B] are the
    probed keys.  Returns ``(slots[B], found[B] int32)`` with slot EMPTY
    where not found."""
    n, h = tab_keys.shape
    if n == 1 and h > TILE_ROWS * LANES:   # flat table: 1024-slot tiles
        if max_probes > TILE_ROWS * LANES:
            raise ValueError(f"max_probes={max_probes} exceeds the "
                             f"{TILE_ROWS * LANES}-slot probe window")
        tile_rows, lanes = TILE_ROWS, LANES
        tab_keys = tab_keys.reshape(-1, LANES)
        tab_vals = tab_vals.reshape(-1, LANES)
    else:                                  # whole tables, 8 to a tile
        tile_rows, lanes = min(TILE_ROWS, n), h
        pad = (-n) % tile_rows
        tab_keys = jnp.pad(tab_keys, ((0, pad), (0, 0)),
                           constant_values=EMPTY)
        tab_vals = jnp.pad(tab_vals, ((0, pad), (0, 0)),
                           constant_values=EMPTY)
    tile_slots = tile_rows * lanes
    n_tiles = tab_keys.shape[0] // tile_rows

    base = jnp.where(rows >= 0, rows * h, -1).astype(jnp.int32)
    h0 = (hash_u32(keys_q) & jnp.uint32(h - 1)).astype(jnp.int32)
    home = jnp.maximum(base, 0) + h0

    def tile(shift):
        return pl.BlockSpec(
            (tile_rows, lanes),
            lambda i, keys, base, home: (
                (home[i] // tile_slots + shift) % n_tiles, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    b = rows.shape[0]
    slots, found = pl.pallas_call(
        functools.partial(_probe_kernel, h=h, tile_slots=tile_slots,
                          n_tiles=n_tiles, max_probes=max_probes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[tile(0), tile(1), tile(0), tile(1)],
            out_specs=[smem, smem],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ],
        interpret=interpret,
    )(keys_q.astype(jnp.int32), base, home, tab_keys, tab_keys, tab_vals,
      tab_vals)
    return slots, found
