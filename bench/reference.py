"""Plain reference of the chain's semantics, in numpy, for the comparison
that decides ``correct``.  It imports nothing of the program.

What it models, batch by batch, as the deployment states it:

* routing: a batch of ``B`` items is split into ``S`` contiguous sender
  slices; each slice sends at most ``cap = int(bucket_factor * (B/S)/S)``
  items (``max(1, ...)``) to each owner, in batch order, and drops the
  rest (``route_drops``).  A shard sees its items in batch order;
* counts: each unique (src, dst) edge of a shard's items adds its
  multiplicity to its slot and to the row total;
* new edges, in order of first arrival: a new source takes the next free
  row; the edge takes the first free slot (count 0) of its row, or, in a
  full row, replaces the slot at the tail of the row's current order and
  inherits its count (Space-Saving);
* order: after every batch one odd-even pass (an even then an odd sweep of
  adjacent compare-exchanges, swapping when the left count is smaller)
  runs over every row of every shard;
* maintenance: when any row total of a shard exceeds ``decay_threshold``,
  one block of ``decay_block_rows`` rows (cursor order) is halved, slots
  that reach 0 are freed and the block's order is re-sorted stably;
* reads: ``query`` walks a row in its order and keeps item ``j`` while the
  counts before it sum to less than ``threshold * tot`` (``cnt / tot`` is
  the probability); ``topn`` takes each row's first ``min(n, C)`` order
  positions and returns the ``n`` most probable edges, ties to the lower
  row position and then the lower shard.

``count_dtype``/``float_dtype`` are int64/float32 for the reference and
bfloat16/bfloat16 for the control, which computes the same semantics one
precision step down.
"""

from __future__ import annotations

import numpy as np

EMPTY = -1
NUM_BUCKETS = 256


def hash_u32(x) -> np.ndarray:
    x = np.asarray(x).astype(np.uint32)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def owner_of(ids, num_shards: int) -> np.ndarray:
    b = (hash_u32(ids) >> np.uint32(8)) % np.uint32(NUM_BUCKETS)
    return (b % np.uint32(num_shards)).astype(np.int64)


def route_drops(src: np.ndarray, num_shards: int, bucket_factor: float):
    """True where the fixed-capacity router drops an item of this batch."""
    src = np.asarray(src)
    n = num_shards
    local = src.size // n
    cap = max(1, int(bucket_factor * max(1, local // n)))
    owner = np.where(src >= 0, owner_of(np.maximum(src, 0), n), n)
    drop = np.zeros(src.size, bool)
    for s in range(n):
        own = owner[s * local:(s + 1) * local]
        rank = np.zeros(own.size, np.int64)
        for o in range(n):
            sel = own == o
            rank[sel] = np.arange(int(sel.sum()))
        drop[s * local:(s + 1) * local] = (own < n) & (rank >= cap)
    return drop


def bfloat16():
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16)


class ReferenceChain:
    def __init__(self, num_shards: int, num_rows: int, capacity: int, *,
                 decay_threshold: int, decay_block_rows: int,
                 bucket_factor: float, count_dtype=np.int64,
                 float_dtype=np.float32):
        s, n, c = num_shards, num_rows, capacity
        self.S, self.N, self.C = s, n, c
        self.decay_threshold = decay_threshold
        self.block = min(decay_block_rows or n, n)
        self.bucket_factor = bucket_factor
        self.cdt, self.fdt = np.dtype(count_dtype), np.dtype(float_dtype)
        self.cnt = np.zeros((s, n, c), self.cdt)
        self.tot = np.zeros((s, n), self.cdt)
        self.dst = np.full((s, n, c), EMPTY, np.int32)
        self.order = np.tile(np.arange(c, dtype=np.int32), (s, n, 1))
        self.row_src = np.full((s, n), EMPTY, np.int64)
        self._keys = [np.zeros(0, np.int64)] * s     # sorted held ids
        self._rows = [np.zeros(0, np.int64)] * s
        self._new = [dict() for _ in range(s)]       # ids allocated later
        self.n_rows = np.zeros(s, np.int64)
        self.evictions = 0
        self.dropped_rows = 0
        self.decay_cursor = np.zeros(s, np.int64)
        self.decay_steps = 0
        self._unsorted = [np.zeros(0, np.int64) for _ in range(s)]
        self._over = [set() for _ in range(s)]      # rows over the threshold
        self._stale = [None] * s                    # rows changed since topn
        self._window = {}                           # (s, k) -> probs, dsts

    # ------------------------------------------------------------------
    def seat(self, row_ids: np.ndarray, counts: np.ndarray, dst_of):
        """Warm state: shard ``s`` row ``r`` holds source ``row_ids[s, r]``
        with successor ``k`` (slot ``k``) counted ``counts[s, r, k]``; a
        slot counted 0 is empty."""
        s_, h, deg = counts.shape
        for s in range(self.S):
            ids = row_ids[s].astype(np.int64)
            self.cnt[s, :h, :deg] = counts[s].astype(self.cdt)
            self.dst[s, :h, :deg] = np.where(
                counts[s] > 0, dst_of(ids[:, None], np.arange(deg)[None, :]),
                EMPTY)
            self.tot[s, :h] = counts[s].astype(np.float64).sum(1).astype(
                self.cdt)
            self.order[s, :h] = np.argsort(-self.cnt[s, :h].astype(np.float64),
                                           axis=1, kind="stable")
            self.row_src[s, :h] = ids
            self._over[s] = set(np.flatnonzero(
                self.tot[s].astype(np.float64) > self.decay_threshold
            ).tolist())
            srt = np.argsort(ids, kind="stable")
            self._keys[s], self._rows[s] = ids[srt], srt.astype(np.int64)
            self.n_rows[s] = h

    def shard_of(self, src) -> np.ndarray:
        src = np.asarray(src)
        if self.S == 1:
            return np.zeros(src.shape, np.int64)
        return owner_of(src, self.S)

    def lookup(self, s: int, src: np.ndarray) -> np.ndarray:
        """Row of each source on shard ``s``; -1 where unknown."""
        src = np.asarray(src, np.int64)
        keys = self._keys[s]
        rows = np.full(src.shape, -1, np.int64)
        if keys.size:
            pos = np.minimum(np.searchsorted(keys, src), keys.size - 1)
            hit = keys[pos] == src
            rows[hit] = self._rows[s][pos[hit]]
        new = self._new[s]
        if new:
            for i in np.flatnonzero(rows < 0):
                rows[i] = new.get(int(src[i]), -1)
        return rows

    # ------------------------------------------------------------------
    def observe(self, src: np.ndarray, dst: np.ndarray) -> int:
        """One engine batch (padding is ``src < 0``).  Returns the number
        of events the router drops (they are not learned)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        active = src >= 0
        dropped = 0
        if self.S > 1:
            drop = route_drops(src, self.S, self.bucket_factor)
            dropped = int(drop.sum())
            active &= ~drop
        shard = self.shard_of(np.maximum(src, 0))
        for s in range(self.S):
            sel = active & (shard == s)
            touched = self._apply_local(s, src[sel], dst[sel])
            rows = np.union1d(touched, self._unsorted[s])
            self._odd_even(s, rows)
            self._changed(s, rows)
            over = touched[self.tot[s, touched].astype(np.float64)
                           > self.decay_threshold]
            self._over[s].update(over.tolist())
            self._maintain(s)
        return dropped

    def _add(self, arr, idx, w):
        arr[idx] = (arr[idx].astype(np.float64) + w).astype(self.cdt)

    def _apply_local(self, s: int, src, dst) -> np.ndarray:
        if src.size == 0:
            return np.zeros(0, np.int64)
        key = (src << 32) | dst
        uniq, first, mult = np.unique(key, return_index=True,
                                      return_counts=True)
        u_src, u_dst = uniq >> 32, uniq & 0xFFFFFFFF
        rows = self.lookup(s, u_src)
        found = rows >= 0
        r = np.maximum(rows, 0)
        hit = self.dst[s, r] == u_dst[:, None]
        fast = found & hit.any(1)
        fr, fs = r[fast], hit[fast].argmax(1)
        self._add(self.cnt[s], (fr, fs), mult[fast])
        ur, inv = np.unique(fr, return_inverse=True)
        self._add(self.tot[s], ur, np.bincount(inv, weights=mult[fast]))
        touched = [ur]
        cnt, dsts, order, tot = (self.cnt[s], self.dst[s], self.order[s],
                                 self.tot[s])
        slow = np.flatnonzero(~fast)
        for i in slow[np.argsort(first[slow], kind="stable")]:
            sv, dv, wv = int(u_src[i]), int(u_dst[i]), int(mult[i])
            row = int(self.lookup(s, np.asarray([sv]))[0])
            if row < 0:
                if self.n_rows[s] >= self.N:
                    self.dropped_rows += wv
                    continue
                row = int(self.n_rows[s])
                self.n_rows[s] += 1
                self._new[s][sv] = row
                self.row_src[s, row] = sv
            same = np.flatnonzero(dsts[row] == dv)
            free = np.flatnonzero(cnt[row] == 0)
            if same.size:
                slot, base = int(same[0]), cnt[row, same[0]]
            elif free.size:
                slot, base = int(free[0]), 0
            else:
                slot = int(order[row, -1])
                base = cnt[row, slot]
                self.evictions += 1
            cnt[row, slot] = self.cdt.type(float(base) + wv)
            dsts[row, slot] = dv
            tot[row] = self.cdt.type(float(tot[row]) + wv)
            touched.append(np.asarray([row]))
        return np.unique(np.concatenate(touched))

    def _odd_even(self, s: int, rows: np.ndarray):
        """One odd-even pass over ``rows``; the other rows have no adjacent
        inversion, so the pass leaves them as they are."""
        if rows.size == 0:
            self._unsorted[s] = rows
            return
        o = self.order[s, rows]
        c = np.take_along_axis(self.cnt[s, rows], o, 1)
        for start in (0, 1):
            m = (self.C - start) // 2
            li = np.arange(start, start + 2 * m, 2)
            ri = li + 1
            cl, cr, ol, orr = c[:, li], c[:, ri], o[:, li], o[:, ri]
            sw = cl < cr
            c[:, li], c[:, ri] = np.where(sw, cr, cl), np.where(sw, cl, cr)
            o[:, li], o[:, ri] = np.where(sw, orr, ol), np.where(sw, ol, orr)
        self.order[s, rows] = o
        self._unsorted[s] = rows[(c[:, :-1] < c[:, 1:]).any(1)]

    def _changed(self, s: int, rows):
        if self._stale[s] is not None:
            self._stale[s].update(np.asarray(rows).tolist())

    def _maintain(self, s: int):
        if not self._over[s]:
            return
        r, n = self.block, self.N
        cur = int(self.decay_cursor[s] % (-(-n // r)))
        lo = min(cur * r, n - r)
        blk = slice(lo, lo + r)
        c = np.floor(self.cnt[s, blk].astype(np.float64) / 2).astype(self.cdt)
        self.cnt[s, blk] = c
        self.dst[s, blk] = np.where(c.astype(np.float64) == 0, EMPTY,
                                    self.dst[s, blk])
        self.tot[s, blk] = c.astype(np.float64).sum(1).astype(self.cdt)
        o = self.order[s, blk]
        c_ord = np.take_along_axis(c, o, 1).astype(np.float64)
        self.order[s, blk] = np.take_along_axis(
            o, np.argsort(-c_ord, axis=1, kind="stable"), 1)
        self._unsorted[s] = np.setdiff1d(self._unsorted[s],
                                         np.arange(lo, lo + r))
        self.decay_cursor[s] = cur + 1
        self.decay_steps += 1
        blk_rows = np.arange(lo, lo + r)
        still = blk_rows[self.tot[s, blk].astype(np.float64)
                         > self.decay_threshold]
        self._over[s] = ({x for x in self._over[s] if not lo <= x < lo + r}
                         | set(still.tolist()))
        self._changed(s, blk_rows)

    # ------------------------------------------------------------------
    def query(self, src, threshold: float, max_items: int):
        """``(dsts[B, k], probs[B, k], n_needed[B])`` at the current state."""
        src = np.asarray(src, np.int64)
        b, k = src.size, max_items
        dk = np.full((b, k), EMPTY, np.int32)
        pk = np.zeros((b, k), np.float32)
        nn = np.zeros(b, np.int32)
        shard = self.shard_of(np.maximum(src, 0))
        kk = min(k, self.C)
        for s in range(self.S):
            sel = np.flatnonzero((shard == s) & (src >= 0))
            rows = self.lookup(s, src[sel])
            sel, rows = sel[rows >= 0], rows[rows >= 0]
            if sel.size == 0:
                continue
            o = self.order[s, rows]
            c = np.take_along_axis(self.cnt[s, rows], o, 1)
            d = np.take_along_axis(self.dst[s, rows], o, 1)
            totf = np.maximum(self.tot[s, rows].astype(np.float64), 1).astype(
                self.fdt)
            cum = np.cumsum(c.astype(np.float64), 1)
            before = (cum - c.astype(np.float64)).astype(self.fdt)
            limit = (self.fdt.type(threshold) * totf).astype(self.fdt)
            needed = (before < limit[:, None]) & (c.astype(np.float64) > 0)
            nn[sel] = needed.sum(1)
            keep = needed[:, :kk]
            p = (c[:, :kk].astype(self.fdt) / totf[:, None]).astype(self.fdt)
            dk[sel, :kk] = np.where(keep, d[:, :kk], EMPTY)
            pk[sel, :kk] = np.where(keep, p.astype(np.float32), 0)
        return dk, pk, nn

    def _window_rows(self, s: int, k: int, rows):
        w = self.order[s, rows, :k]
        c = np.take_along_axis(self.cnt[s, rows], w, 1)
        d = np.take_along_axis(self.dst[s, rows], w, 1)
        totf = np.maximum(self.tot[s, rows].astype(np.float64), 1).astype(
            self.fdt)
        p = np.where(c.astype(np.float64) > 0,
                     (c.astype(self.fdt) / totf[:, None]).astype(self.fdt),
                     0).astype(np.float32)
        return p, d

    def window_probs(self, s: int, k: int):
        """Probabilities ``[N * k]`` of each row's first ``k`` order
        positions on shard ``s`` (0 for free slots), and their dsts; kept
        between calls and refreshed on the rows changed since."""
        if (s, k) not in self._window or self._stale[s] is None:
            p, d = self._window_rows(s, k, slice(None))
            self._window[(s, k)] = (p, d)
        elif self._stale[s]:
            rows = np.fromiter(self._stale[s], np.int64)
            p, d = self._window[(s, k)]
            p[rows], d[rows] = self._window_rows(s, k, rows)
        self._stale[s] = set()
        p, d = self._window[(s, k)]
        return p.reshape(-1), d.reshape(-1)

    def topn(self, n: int):
        """``(srcs[n], dsts[n], probs[n])``, globally descending."""
        k = min(n, self.C)
        lists = []
        for s in range(self.S):
            p, d = self.window_probs(s, k)
            if p.size > n:
                cut = np.partition(p, p.size - n)[p.size - n]
                cand = np.flatnonzero(p >= cut)
            else:
                cand = np.arange(p.size)
            cand = cand[np.lexsort((cand, -p[cand]))][:n]
            live = p[cand] > 0
            lists.append((np.where(live, self.row_src[s, cand // k], EMPTY),
                          np.where(live, d[cand], EMPTY),
                          np.where(live, p[cand], 0).astype(np.float32)))
        out_s, out_d, out_p = [], [], []
        ptr = [0] * self.S
        for _ in range(n):
            heads = [lists[s][2][ptr[s]] if ptr[s] < len(lists[s][2]) else 0.0
                     for s in range(self.S)]
            best = int(np.argmax(heads))
            p = heads[best]
            if p > 0:
                out_s.append(lists[best][0][ptr[best]])
                out_d.append(lists[best][1][ptr[best]])
            else:
                out_s.append(EMPTY)
                out_d.append(EMPTY)
            out_p.append(p)
            ptr[best] += 1
        return (np.asarray(out_s, np.int64), np.asarray(out_d, np.int64),
                np.asarray(out_p, np.float32))

    def edge_prob(self, src: int, dst: int, k: int):
        """Probability of edge (src, dst) if it is in its row's first ``k``
        order positions, else None."""
        s = int(self.shard_of(np.asarray([src]))[0])
        row = int(self.lookup(s, np.asarray([src]))[0])
        if row < 0:
            return None
        w = self.order[s, row, :k]
        hit = np.flatnonzero(self.dst[s, row, w] == dst)
        if hit.size == 0 or float(self.cnt[s, row, w[hit[0]]]) <= 0:
            return None
        totf = self.fdt.type(max(float(self.tot[s, row]), 1))
        return np.float32(self.fdt.type(self.cnt[s, row, w[hit[0]]]) / totf)
