"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import hashtable as ht
from repro.core import slab as sl
from repro.kernels import cdf_gather as cgk
from repro.kernels import cdf_query as cdfk
from repro.kernels import oddeven as oek
from repro.kernels import ops
from repro.kernels import probe as prk
from repro.kernels import ref
from repro.kernels import slab_update as suk
from repro.kernels import walk as wkk

SHAPES_2D = [(8, 16), (64, 128), (32, 256), (256, 128), (7, 32)]


def _rand_slabs(rng, n, c, density=0.7, dtype=np.int32):
    cnt = (rng.random((n, c)) < density) * rng.integers(1, 1000, (n, c))
    cnt = cnt.astype(dtype)
    dst = np.where(cnt > 0, rng.integers(0, 10_000, (n, c)), -1).astype(np.int32)
    tot = cnt.sum(axis=1).astype(dtype)
    order = np.argsort(-cnt, axis=1, kind="stable").astype(np.int32)
    return jnp.asarray(dst), jnp.asarray(cnt), jnp.asarray(tot), jnp.asarray(order)


# ---------------------------------------------------------------------------
# oddeven
# ---------------------------------------------------------------------------


def _rand_perms(rng, n, c):
    return jnp.asarray(
        np.stack([rng.permutation(c) for _ in range(n)]).astype(np.int32))


def _check_oddeven(cnt, order, got_o, passes):
    """The kernel's permutation equals the oracle's on the counts gathered
    into order position, and the counts it puts in order are the oracle's."""
    c_ord = jnp.take_along_axis(cnt, order, axis=1)
    want_c, want_o = ref.oddeven_ref(c_ord, order, passes)
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    got_c = jnp.take_along_axis(cnt, got_o, axis=1)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    return np.asarray(got_c)


@pytest.mark.parametrize("n,c", SHAPES_2D)
@pytest.mark.parametrize("passes", [1, 2, 5])
def test_oddeven_kernel_matches_ref(n, c, passes):
    rng = np.random.default_rng(n * 1000 + c + passes)
    cnt = jnp.asarray(rng.integers(0, 100, (n, c)).astype(np.int32))
    order = _rand_perms(rng, n, c)
    # pad rows to the block multiple the kernel requires
    rb = min(oek.DEFAULT_ROWS_PER_BLOCK, n)
    pad = (-n) % rb
    c_pad = jnp.pad(cnt, ((0, pad), (0, 0)))
    o_pad = jnp.pad(order, ((0, pad), (0, 0)))
    got_o = oek.oddeven_pallas(
        c_pad, o_pad, passes=passes, rows_per_block=rb, interpret=True)
    _check_oddeven(cnt, order, got_o[:n], passes)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_oddeven_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    cnt = jnp.asarray(rng.integers(0, 50, (16, 64))).astype(dtype)
    order = _rand_perms(rng, 16, 64)
    got_o = oek.oddeven_pallas(
        cnt, order, passes=3, rows_per_block=16, interpret=True)
    _check_oddeven(cnt, order, got_o, 3)


def test_oddeven_ref_equals_slab_semantics():
    """kernel-layout oracle == core slab.oddeven_passes semantics, and the
    kernel (which gathers in VMEM) == both."""
    rng = np.random.default_rng(1)
    cnt = jnp.asarray(rng.integers(0, 100, (32, 64)).astype(np.int32))
    order = _rand_perms(rng, 32, 64)
    want = sl.oddeven_passes(cnt, order, 2)
    c_ord = jnp.take_along_axis(cnt, order, axis=1)
    _, got = ref.oddeven_ref(c_ord, order, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_k = oek.oddeven_pallas(cnt, order, passes=2, rows_per_block=32,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want))


def test_oddeven_full_sort_after_C_passes():
    rng = np.random.default_rng(2)
    n, c = 16, 64
    cnt = jnp.asarray(rng.integers(0, 10_000, (n, c)).astype(np.int32))
    order = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (n, c))
    passes = c // 2 + 1
    got_o = oek.oddeven_pallas(
        cnt, order, passes=passes, rows_per_block=n, interpret=True)
    got_c = _check_oddeven(cnt, order, got_o, passes)
    assert np.all(got_c[:, :-1] >= got_c[:, 1:]), "not fully sorted"
    # permutation property retained
    assert np.all(np.sort(np.asarray(got_o), axis=1) == np.arange(c))


@pytest.mark.parametrize("c", [64, 128])
@pytest.mark.parametrize("passes", [1, 33])
def test_oddeven_sort_pallas_equals_ref_on_ties(c, passes):
    """ops.oddeven_sort's kernel path returns the oracle's permutation on
    rows full of tied counts (a strict compare never swaps a tie), over a
    row count that is not a multiple of the block."""
    rng = np.random.default_rng(c + passes)
    n = 300
    cnt = jnp.asarray(rng.integers(0, 4, (n, c)).astype(np.int32))
    order = _rand_perms(rng, n, c)
    want = ops.oddeven_sort(cnt, order, passes=passes, impl="ref")
    got = ops.oddeven_sort(cnt, order, passes=passes, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# slab_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(16, 32), (256, 128), (64, 64)])
@pytest.mark.parametrize("batch", [4, 64, 256])
def test_slab_update_kernel_matches_ref(n, c, batch):
    rng = np.random.default_rng(n + batch)
    dst, cnt, tot, _ = _rand_slabs(rng, n, c)
    # build updates: half hit existing edges, half miss / padding
    rows = rng.integers(0, n, batch).astype(np.int32)
    rows[rng.random(batch) < 0.2] = -1  # padding
    dsts = np.empty(batch, np.int32)
    dnp, cnp = np.asarray(dst), np.asarray(cnt)
    for i, r in enumerate(rows):
        live = np.nonzero((r >= 0) * (cnp[max(r, 0)] > 0))[0]
        if r >= 0 and len(live) and rng.random() < 0.7:
            dsts[i] = dnp[r, rng.choice(live)]
        else:
            dsts[i] = 123456 + i  # guaranteed miss
    w = rng.integers(1, 5, batch).astype(np.int32)
    rb = min(suk.DEFAULT_ROWS_PER_BLOCK, n)
    got_cnt, got_tot = suk.slab_update_pallas(
        jnp.asarray(rows), jnp.asarray(dsts), jnp.asarray(w),
        dst, cnt, tot, rows_per_block=rb, interpret=True)
    _, want_cnt, want_tot, _ = ref.slab_update_ref(
        jnp.asarray(rows), jnp.asarray(dsts), jnp.asarray(w), dst, cnt, tot)
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(want_cnt))
    np.testing.assert_array_equal(np.asarray(got_tot), np.asarray(want_tot))


def test_slab_update_duplicate_aggregation():
    """In-batch duplicates of one edge aggregate like contended atomics."""
    dst = jnp.asarray([[5, 7, -1, -1]], jnp.int32)
    cnt = jnp.asarray([[10, 3, 0, 0]], jnp.int32)
    tot = jnp.asarray([13], jnp.int32)
    rows = jnp.zeros((8,), jnp.int32)
    dsts = jnp.asarray([5] * 8, jnp.int32)
    w = jnp.ones((8,), jnp.int32)
    got_cnt, got_tot = suk.slab_update_pallas(
        rows, dsts, w, dst, cnt, tot, rows_per_block=1, interpret=True)
    assert int(got_cnt[0, 0]) == 18
    assert int(got_tot[0]) == 21


# ---------------------------------------------------------------------------
# cdf_query
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,c", [(8, 16), (128, 128), (64, 256)])
@pytest.mark.parametrize("t", [0.5, 0.9, 0.99])
@pytest.mark.parametrize("chunks", [1, 4])
def test_cdf_query_kernel_matches_ref(b, c, t, chunks):
    rng = np.random.default_rng(b + int(t * 100) + chunks)
    # zipf-ish sorted counts
    raw = np.sort(rng.zipf(1.5, (b, c)).astype(np.int32), axis=1)[:, ::-1]
    raw[rng.random((b, c)) < 0.1] = 0
    raw = np.sort(raw, axis=1)[:, ::-1].copy()
    c_ord = jnp.asarray(raw)
    d_ord = jnp.asarray(rng.integers(0, 1000, (b, c)).astype(np.int32))
    tot = jnp.asarray(raw.sum(axis=1).astype(np.int32))
    qb = min(cdfk.DEFAULT_QUERIES_PER_BLOCK, b)
    got_d, got_p, got_n = cdfk.cdf_query_pallas(
        c_ord, d_ord, tot, t, max_items=16, queries_per_block=qb,
        chunks=chunks, interpret=True)
    want_d, want_p, want_n = ref.cdf_query_ref(c_ord, d_ord, tot, t, 16)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-6, atol=1e-7)


def test_cdf_query_empty_rows():
    c_ord = jnp.zeros((4, 32), jnp.int32)
    d_ord = jnp.zeros((4, 32), jnp.int32)
    tot = jnp.zeros((4,), jnp.int32)
    got_d, got_p, got_n = cdfk.cdf_query_pallas(
        c_ord, d_ord, tot, 0.9, max_items=8, queries_per_block=4,
        interpret=True)
    assert np.all(np.asarray(got_n) == 0)
    assert np.all(np.asarray(got_p) == 0)


def test_cdf_query_complexity_matches_quantile():
    """n_needed equals the quantile function of the edge distribution —
    the paper's O(CDF^-1(t)) claim, checked exactly."""
    # geometric-ish distribution: p_i ~ 2^-i  ->  CDF^-1(0.9) is ~4 items
    c_ord = jnp.asarray([[512, 256, 128, 64, 32, 16, 8, 8]], jnp.int32)
    d_ord = jnp.arange(8, dtype=jnp.int32)[None]
    tot = jnp.asarray([1024], jnp.int32)
    _, _, n = cdfk.cdf_query_pallas(
        c_ord, d_ord, tot, 0.9, max_items=8, queries_per_block=1,
        interpret=True)
    # cumsum/1024: .5 .75 .875 .9375 -> 4 items needed
    assert int(n[0]) == 4


# ---------------------------------------------------------------------------
# probe (paper §II.1-2: the shared open-addressing lookup as a batched kernel)
# ---------------------------------------------------------------------------


def _rand_row_tables(rng, n, h, fill=0.4, tomb=0.2, max_probes=64):
    """Per-row tables built through real core inserts/deletes so the probe
    chains (including tombstones) are exactly what production produces."""
    keys = np.full((n, h), ht.EMPTY, np.int32)
    vals = np.full((n, h), ht.EMPTY, np.int32)
    live = {}
    for r in range(n):
        tab = ht.make(h)
        inserted = []
        for i in range(int(fill * h)):
            k = int(rng.integers(0, 100_000))
            tab, _, ok = ht.insert(tab, jnp.int32(k), jnp.int32(i),
                                   max_probes=max_probes)
            if bool(ok):
                inserted.append((k, i))
        rng.shuffle(inserted)
        n_del = int(tomb * len(inserted))
        for k, _ in inserted[:n_del]:
            tab, _ = ht.delete(tab, jnp.int32(k), max_probes=max_probes)
        live[r] = dict(inserted[n_del:])
        keys[r] = np.asarray(tab.keys)
        vals[r] = np.asarray(tab.vals)
    return jnp.asarray(keys), jnp.asarray(vals), live


@pytest.mark.parametrize("n,h", [(4, 32), (16, 128), (7, 64)])
def test_dh_find_kernel_matches_ref(n, h):
    rng = np.random.default_rng(n * 100 + h)
    keys, vals, live = _rand_row_tables(rng, n, h)
    batch = 64
    rows = rng.integers(0, n, batch).astype(np.int32)
    rows[rng.random(batch) < 0.15] = -1          # padding
    dsts = np.empty(batch, np.int32)
    for i, r in enumerate(rows):
        pool = list(live.get(int(max(r, 0)), {}))
        if r >= 0 and pool and rng.random() < 0.7:
            dsts[i] = pool[int(rng.integers(0, len(pool)))]
        else:
            dsts[i] = 900_000 + i                # guaranteed miss
    rows_j, dsts_j = jnp.asarray(rows), jnp.asarray(dsts)
    got_s, got_f = prk.probe_find_pallas(
        rows_j, dsts_j, keys, vals, max_probes=64, interpret=True)
    want_s, want_f = ref.dh_find_ref(rows_j, dsts_j, keys, vals, 64)
    np.testing.assert_array_equal(np.asarray(got_f).astype(bool),
                                  np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    # oracle of the oracle: ref agrees with the per-row core probe + the
    # ground-truth live dict
    for i, (r, d) in enumerate(zip(rows, dsts)):
        if r < 0:
            assert not bool(want_f[i])
            continue
        expect = live[int(r)].get(int(d))
        assert bool(want_f[i]) == (expect is not None)
        if expect is not None:
            assert int(want_s[i]) == expect


def test_dh_find_tombstone_chains_probe_through():
    """Probes must walk through TOMB lanes (deleted keys) to later entries."""
    h = 32
    tab = ht.make(h)
    # three keys colliding into one chain
    base = jnp.int32(11)
    h0 = int(ht._slot0(base, h))
    chain = [k for k in range(2000)
             if int(ht._slot0(jnp.int32(k), h)) == h0][:3]
    assert len(chain) == 3
    for i, k in enumerate(chain):
        tab, _, _ = ht.insert(tab, jnp.int32(k), jnp.int32(i))
    tab, _ = ht.delete(tab, jnp.int32(chain[0]))   # TOMB at chain head
    keys, vals = tab.keys[None], tab.vals[None]
    rows = jnp.zeros((3,), jnp.int32)
    dsts = jnp.asarray(chain, jnp.int32)
    got_s, got_f = prk.probe_find_pallas(rows, dsts, keys, vals,
                                      max_probes=16, interpret=True)
    want_s, want_f = ref.dh_find_ref(rows, dsts, keys, vals, 16)
    np.testing.assert_array_equal(np.asarray(got_f).astype(bool),
                                  np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    assert not bool(got_f[0])                      # deleted
    assert bool(got_f[1]) and bool(got_f[2])       # found through the TOMB


# ---------------------------------------------------------------------------
# fused decay (composes the oddeven kernel; paper §II.C)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_decay_sort_matches_core_decay(impl):
    from repro.core import slab as slab_mod

    rng = np.random.default_rng(7)
    n, c = 16, 32
    dst, cnt, tot, order = _rand_slabs(rng, n, c)
    got_cnt, got_dst, got_order, got_tot = ops.decay_sort(
        cnt, dst, order, impl=impl)
    slabs, _ = slab_mod.decay(slab_mod.Slabs(dst, cnt, tot, order))
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(slabs.cnt))
    np.testing.assert_array_equal(np.asarray(got_dst), np.asarray(slabs.dst))
    np.testing.assert_array_equal(np.asarray(got_tot), np.asarray(slabs.tot))
    # order: both must be fully sorted descending (ties may permute)
    c_got = np.take_along_axis(np.asarray(got_cnt), np.asarray(got_order), 1)
    assert np.all(c_got[:, :-1] >= c_got[:, 1:])
    # permutation property
    assert np.all(np.sort(np.asarray(got_order), 1) == np.arange(c))


# ---------------------------------------------------------------------------
# probe: flat src table (N = 1 case of the shared kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t_size", [64, 256, 4096])
def test_probe_flat_table_matches_core_lookup(t_size):
    """ops.ht_find == hashtable.lookup_batch on a real src table with
    tombstones, for both dispatches (4096 slots: the two-tile window of a
    table larger than one tile, wrapping at its end)."""

    rng = np.random.default_rng(t_size)
    tab = ht.make(t_size)
    keys = rng.choice(100_000, size=t_size // 4, replace=False).astype(np.int32)
    for i, k in enumerate(keys):
        tab, _, ok = ht.insert(tab, jnp.int32(k), jnp.int32(i))
        assert bool(ok)
    for k in keys[:: 5]:                         # delete every 5th -> TOMBs
        tab, _ = ht.delete(tab, jnp.int32(k))
    queries = np.concatenate([keys, 900_000 + np.arange(16, dtype=np.int32)])
    rng.shuffle(queries)
    q = jnp.asarray(queries)
    want_v, want_f = ht.lookup_batch(tab, q)
    for impl in ("ref", "pallas"):
        got_v, got_f = ops.ht_find(q, tab.keys, tab.vals, impl=impl)
        np.testing.assert_array_equal(np.asarray(got_f).astype(bool),
                                      np.asarray(want_f), err_msg=impl)
        # lookup_batch leaves val EMPTY when not found; ht_find matches
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v),
                                      err_msg=impl)
    # the kernel routing inside lookup_batch itself
    kv, kf = ht.lookup_batch(tab, q, impl="pallas")
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(kf).astype(bool),
                                  np.asarray(want_f))


# ---------------------------------------------------------------------------
# cdf_query: top-k mode + chunk-invariance (integer-walk contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_cdf_query_topk_mode_matches_ref(chunks):
    rng = np.random.default_rng(chunks)
    b, c = 32, 64
    raw = np.sort(rng.zipf(1.5, (b, c)).astype(np.int32), axis=1)[:, ::-1]
    raw[rng.random((b, c)) < 0.3] = 0
    raw = np.sort(raw, axis=1)[:, ::-1].copy()
    c_ord, tot = jnp.asarray(raw), jnp.asarray(raw.sum(1).astype(np.int32))
    d_ord = jnp.asarray(rng.integers(0, 1000, (b, c)).astype(np.int32))
    got_d, got_p, got_n = cdfk.cdf_query_pallas(
        c_ord, d_ord, tot, max_items=8, queries_per_block=b, chunks=chunks,
        topk=True, interpret=True)
    want_d, want_p, want_n = ref.cdf_query_ref(c_ord, d_ord, tot, None, 8)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    assert np.asarray(got_p).tobytes() == np.asarray(want_p).tobytes()
    # top-k keeps every live item in the window
    np.testing.assert_array_equal(np.asarray(want_n), (raw > 0).sum(1))


@pytest.mark.parametrize("t", [0.3, 0.9])
def test_cdf_query_chunkings_bit_identical(t):
    """Any chunking == any other, bit for bit: the integer-walk contract."""
    rng = np.random.default_rng(int(t * 10))
    b, c = 64, 128
    raw = np.sort(rng.zipf(1.3, (b, c)).astype(np.int32), axis=1)[:, ::-1]
    raw[rng.random((b, c)) < 0.2] = 0
    raw = np.sort(raw, axis=1)[:, ::-1].copy()
    c_ord, tot = jnp.asarray(raw), jnp.asarray(raw.sum(1).astype(np.int32))
    d_ord = jnp.asarray(rng.integers(0, 1000, (b, c)).astype(np.int32))
    outs = [cdfk.cdf_query_pallas(c_ord, d_ord, tot, t, max_items=16,
                                  queries_per_block=32, chunks=ch,
                                  interpret=True)
            for ch in (1, 2, 4)]
    for other in outs[1:]:
        for a, bb in zip(outs[0], other):
            assert np.asarray(a).tobytes() == np.asarray(bb).tobytes()


# ---------------------------------------------------------------------------
# cdf_gather: fused in-kernel row gather (scalar prefetch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(16, 32), (64, 128)])
@pytest.mark.parametrize("t,chunks", [(0.5, 1), (0.9, 2), (None, 1)])
def test_cdf_gather_kernel_matches_fused_and_unfused_ref(n, c, t, chunks):
    rng = np.random.default_rng(n + c + chunks)
    dst, cnt, tot, order = _rand_slabs(rng, n, c)
    b = 24
    rows = jnp.asarray(rng.integers(0, n, b).astype(np.int32))
    found = jnp.asarray(rng.random(b) < 0.8)
    rows = jnp.where(found, rows, 0)
    k = 8
    got = cgk.cdf_query_fused_pallas(
        rows, found.astype(jnp.int32), cnt, dst, order, tot,
        0.0 if t is None else t, max_items=k, chunks=chunks,
        topk=t is None, interpret=True)
    want = ref.cdf_query_fused_ref(rows, found, cnt, dst, order, tot, t, k)
    # and the unfused pipeline on the same gathered rows
    ord_r = order[rows]
    c_ord = jnp.where(found[:, None],
                      jnp.take_along_axis(cnt[rows], ord_r, axis=1), 0)
    d_ord = jnp.take_along_axis(dst[rows], ord_r, axis=1)
    unfused = ref.cdf_query_ref(c_ord, d_ord, tot[rows], t, k)
    for g, w, u in zip(got, want, unfused):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
        assert np.asarray(w).tobytes() == np.asarray(u).tobytes()


# ---------------------------------------------------------------------------
# walk: one-shot k-step greedy draft kernel
# ---------------------------------------------------------------------------


def _walk_fixture(rng, n_tokens=64, order=2, num_rows=256):
    """A chain learned from a noisy successor stream, plus its raw arrays."""
    from repro.core import mcprioq as mc
    from repro.core import speculative as spec

    ncfg = spec.NGramConfig(
        order=order, mc=mc.MCConfig(num_rows=num_rows, capacity=8,
                                    sort_passes=2))
    st = spec.init(ncfg)
    succ = rng.integers(0, n_tokens, (n_tokens,)).astype(np.int32)
    toks = np.empty((4, 256), np.int32)
    toks[:, 0] = rng.integers(0, n_tokens, 4)
    for i in range(1, 256):
        follow = succ[toks[:, i - 1]]
        noise = rng.integers(0, n_tokens, 4)
        toks[:, i] = np.where(rng.random(4) < 0.9, follow, noise)
    st = spec.observe(st, jnp.asarray(toks), cfg=ncfg)
    return st, ncfg, toks


@pytest.mark.parametrize("k", [1, 4, 7])
def test_draft_walk_kernel_matches_scan_oracle(k):
    rng = np.random.default_rng(k)
    st, ncfg, toks = _walk_fixture(rng)
    chain = st.chain
    # mix of learned contexts and unknown ones (dead lanes)
    window = jnp.asarray(np.concatenate(
        [toks[:, 100:102], np.full((2, 2), 7777, np.int32)]).astype(np.int32))
    args = (window, chain.src_table.keys, chain.src_table.vals,
            chain.slabs.cnt, chain.slabs.dst, chain.slabs.order[:, 0])
    got_t, got_o = wkk.draft_walk_pallas(
        *args, k=k, max_probes=64, queries_per_block=window.shape[0],
        interpret=True)
    want_t, want_o = ref.draft_walk_ref(*args, k=k, max_probes=64)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(want_t))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    # dead lanes emit token 0 / ok False from the first step
    assert not np.asarray(got_o)[-2:].any()
    assert not np.asarray(got_t)[-2:].any()


def test_draft_walk_small_table_probes_all_of_it():
    """A src table shorter than max_probes (32 slots, 64 probes) is one
    lane row: the walk probes the whole of it, as the oracle does."""
    rng = np.random.default_rng(5)
    st, ncfg, toks = _walk_fixture(rng, num_rows=8)
    chain = st.chain
    assert chain.src_table.keys.shape[0] < 64
    window = jnp.asarray(toks[:, 40:42])
    args = (window, chain.src_table.keys, chain.src_table.vals,
            chain.slabs.cnt, chain.slabs.dst, chain.slabs.order[:, 0])
    got_t, got_o = wkk.draft_walk_pallas(
        *args, k=3, max_probes=64, queries_per_block=window.shape[0],
        interpret=True)
    want_t, want_o = ref.draft_walk_ref(*args, k=3, max_probes=64)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(want_t))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    assert np.asarray(want_o).any()


def test_draft_walk_ok_is_prefix_monotone():
    """ok rows are all-True prefixes: once a lane dies it stays dead."""
    rng = np.random.default_rng(11)
    st, ncfg, toks = _walk_fixture(rng)
    chain = st.chain
    window = jnp.asarray(toks[:, 17:19])
    _, oks = wkk.draft_walk_pallas(
        window, chain.src_table.keys, chain.src_table.vals,
        chain.slabs.cnt, chain.slabs.dst, chain.slabs.order[:, 0],
        k=6, max_probes=64, queries_per_block=window.shape[0],
        interpret=True)
    oks = np.asarray(oks).astype(bool)
    assert np.all(oks == (np.cumprod(oks, axis=1) > 0))
