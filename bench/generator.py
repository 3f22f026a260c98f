"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and a
deployment (``configs/<config>.json``) into seeded, vectorised traffic.

Everything is drawn with numpy from ``--seed`` and the id layout of the
warm state (``held_ids``), so the same seed gives the same traffic.  A mix
has two parts:

* ``ingest``: ``loop: closed`` sends full batches back to back (batch ``i``
  is drawn from ``(seed, i)``, so any number of batches reproduces);
  ``loop: open`` paces events evenly at ``rate_per_s`` (or
  ``events_per_read`` times the read rate).  ``new_share`` of a closed
  batch are new edges: ``new_src_share`` of them first transitions of
  never-seen sources, the rest new successors of held sources.
* ``reads``: an open loop of calls paced evenly at ``rate_per_s``, or at
  ``calls_per_event`` times an open ingester's rate; every
  ``topn_every``-th call (at a seeded position in each block) is a top-n,
  the others query ``query_width`` sources.

Sources follow Zipf(``src_zipf``) over the held sources by popularity rank
(rank ``g`` is row ``g // S`` of shard ``g % S``); successors follow
Zipf(``rank_zipf``) over the first ``out_degree`` held ones; a new
successor has a rank of ``capacity`` or more, which no row holds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

SUCC_MULT = 2654435761
RANK_MULT = 40503
NEW_RANKS = 1 << 20         # new successors draw their rank from [C, C + this)
STREAM_CLOSED, STREAM_OPEN, STREAM_READS, STREAM_SAMPLE = 1, 2, 3, 4


def seed_words(seed: int):
    """Non-negative entropy for numpy's SeedSequence from any whole seed."""
    return [int(seed) % (1 << 64)]


def dst_of(src, rank):
    """Successor id of ``src`` at ``rank``: distinct for every rank < 2^31."""
    s = np.asarray(src, np.uint64)
    r = np.asarray(rank, np.uint64)
    return ((s * np.uint64(SUCC_MULT) + r * np.uint64(RANK_MULT) + np.uint64(7))
            % np.uint64(1 << 31)).astype(np.int32)


def zipf_cdf(n: int, s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def zipf_draw(rng, cdf: np.ndarray, size: int) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      cdf.size - 1)


def new_source_base(cfg: dict) -> int:
    """First id never held: above the warm state's candidate id space."""
    w = cfg["warm"]
    return w["candidate_ids_per_row"] * cfg["mc"]["num_rows"] * \
        cfg["serve"]["num_shards"]


@dataclasses.dataclass
class Reads:
    due: np.ndarray          # float64[n] seconds from the window's start
    is_topn: np.ndarray      # bool[n]
    srcs: np.ndarray         # int32[n, width] (rows of top-n calls unused)
    checked: np.ndarray      # bool[n]: answer compared with the reference


class Traffic:
    def __init__(self, mix: dict, cfg: dict, held_ids: np.ndarray, seed: int,
                 seconds: float):
        self.mix, self.cfg, self.seed = mix, cfg, seed
        self.seconds = float(seconds)
        self.batch = int(cfg["batch"])
        self.deg = int(cfg["warm"]["out_degree"])
        self.cap = int(cfg["mc"]["capacity"])
        s, h = held_ids.shape
        self.popular = np.ascontiguousarray(held_ids.T).reshape(-1)
        ing, rd = mix["ingest"], mix["reads"]
        self.src_cdf = zipf_cdf(s * h, ing["src_zipf"])
        self.rank_cdf = zipf_cdf(self.deg, ing["rank_zipf"])
        self.read_cdf = (self.src_cdf if rd["src_zipf"] == ing["src_zipf"]
                         else zipf_cdf(s * h, rd["src_zipf"]))
        self.new_base = new_source_base(cfg)
        self.closed = ing["loop"] == "closed"
        n_new = int(round(ing.get("new_share", 0.0) * self.batch))
        self.n_new_src = int(round(n_new * ing.get("new_src_share", 0.5)))
        self.n_new_succ = n_new - self.n_new_src

    def _rng(self, *stream):
        return np.random.default_rng(seed_words(self.seed) + list(stream))

    def _held_events(self, rng, n: int):
        src = self.popular[zipf_draw(rng, self.src_cdf, n)]
        rank = zipf_draw(rng, self.rank_cdf, n)
        return src.astype(np.int32), dst_of(src, rank)

    # ------------------------------------------------------------------
    def closed_batch(self, i: int):
        """Batch ``i`` of a closed loop: a full batch of events, exactly
        ``n_new_src + n_new_succ`` of them new edges at seeded positions."""
        rng = self._rng(STREAM_CLOSED, i)
        src, dst = self._held_events(rng, self.batch)
        pos = rng.permutation(self.batch)
        ps, pn = pos[:self.n_new_src], pos[self.n_new_src:
                                           self.n_new_src + self.n_new_succ]
        new_src = (self.new_base + i * self.n_new_src
                   + np.arange(self.n_new_src)).astype(np.int32)
        src[ps], dst[ps] = new_src, dst_of(new_src, 0)
        succ_src = src[pn]
        dst[pn] = dst_of(succ_src, self.cap + rng.integers(0, NEW_RANKS,
                                                           pn.size))
        return src, dst

    def ingest_rate(self) -> float:
        ing = self.mix["ingest"]
        if "events_per_read" in ing:
            return ing["events_per_read"] * self.mix["reads"]["rate_per_s"]
        return float(ing["rate_per_s"])

    def read_rate(self) -> float:
        rd = self.mix["reads"]
        if "calls_per_event" in rd:
            return rd["calls_per_event"] * float(
                self.mix["ingest"]["rate_per_s"])
        return float(rd["rate_per_s"])

    def open_events(self, rate=None):
        """Evenly paced held-edge events over the window: ``(due, src, dst)``."""
        rate = self.ingest_rate() if rate is None else rate
        n = int(round(rate * self.seconds))
        rng = self._rng(STREAM_OPEN)
        src, dst = self._held_events(rng, n)
        return np.arange(n) / rate, src, dst

    def reads(self, rate=None, query_checks: int = 160,
              topn_checks: int = 8) -> Reads:
        rd = self.mix["reads"]
        rate = self.read_rate() if rate is None else rate
        n = int(round(rate * self.seconds))
        rng = self._rng(STREAM_READS)
        every = int(rd.get("topn_every", 0))
        is_topn = np.zeros(n, bool)
        if every:
            blocks = np.arange(0, n, every)
            is_topn[np.minimum(blocks + rng.integers(0, every, blocks.size),
                               n - 1)] = True
        width = int(rd["query_width"])
        srcs = self.popular[zipf_draw(rng, self.read_cdf, n * width)]
        srcs = srcs.reshape(n, width).astype(np.int32)
        pick = self._rng(STREAM_SAMPLE)
        checked = np.zeros(n, bool)
        for want, kind in ((query_checks, ~is_topn), (topn_checks, is_topn)):
            idx = np.flatnonzero(kind)
            checked[pick.choice(idx, min(want, idx.size), replace=False)] = True
        return Reads(np.arange(n) / rate, is_topn, srcs, checked)
