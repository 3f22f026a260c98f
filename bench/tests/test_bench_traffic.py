"""The generator reproduces from the seed and gives the shares its mixes
state."""

import json
from pathlib import Path

import numpy as np

import drive  # noqa: F401  (puts the benchmark on the path)
from generator import Traffic, dst_of, new_source_base

BENCH = Path(__file__).resolve().parents[1]


def load(config, mix):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mx = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    return cfg, mx


def held(cfg, seed=0, rows=4096):
    s = cfg["serve"]["num_shards"]
    rng = np.random.default_rng(seed)
    return rng.choice(1 << 20, (s, rows), replace=False).astype(np.int32)


def is_held(cfg, ids, src, dst):
    known = np.isin(src, ids)
    rank_ok = np.zeros(src.size, bool)
    for r in range(cfg["warm"]["out_degree"]):
        rank_ok |= dst_of(src, r) == dst
    return known & rank_ok


def test_same_seed_same_traffic():
    cfg, mix = load("rec-1m", "churn")
    ids = held(cfg)
    a = Traffic(mix, cfg, ids, 2 ** 31 + 77, 2.0)
    b = Traffic(mix, cfg, ids, 2 ** 31 + 77, 2.0)
    c = Traffic(mix, cfg, ids, 2 ** 31 + 78, 2.0)
    for i in (0, 5, 123):
        assert all((x == y).all() for x, y in zip(a.closed_batch(i),
                                                  b.closed_batch(i)))
    assert not (a.closed_batch(0)[0] == c.closed_batch(0)[0]).all()
    ra, rb = a.reads(), b.reads()
    assert (ra.srcs == rb.srcs).all() and (ra.checked == rb.checked).all()


def test_churn_has_five_percent_new_edges():
    cfg, mix = load("rec-1m", "churn")
    ids = held(cfg)
    t = Traffic(mix, cfg, ids, 5, 1.0)
    base = new_source_base(cfg)
    new = 0
    n = 0
    for i in range(40):
        src, dst = t.closed_batch(i)
        assert src.size == cfg["batch"]
        fresh_src = src >= base
        new += int(fresh_src.sum() + (~fresh_src & ~is_held(
            cfg, ids, src, dst)).sum())
        n += src.size
    assert new / n == round(0.05 * cfg["batch"]) / cfg["batch"]
    assert abs(new / n - 0.05) < 0.002


def test_churn_new_successors_evict_in_full_rows():
    """About half of churn's new successors land on the full hot rows, where
    each one evicts (at the deployment's 786432 held sources)."""
    cfg, mix = load("rec-1m", "churn")
    h = int(cfg["warm"]["held_share"] * cfg["mc"]["num_rows"])
    ids = np.random.default_rng(1).permutation(1 << 21)[:h][None].astype(
        np.int32)
    full = set(ids[0, :cfg["warm"]["full_rows"]].tolist())
    t = Traffic(mix, cfg, ids, 6, 1.0)
    base = new_source_base(cfg)
    succ = []
    for i in range(200):
        src, dst = t.closed_batch(i)
        for j in np.flatnonzero((src < base) & ~is_held(cfg, ids, src, dst)):
            assert all(dst[j] != dst_of(src[j], r)
                       for r in range(cfg["mc"]["capacity"]))
            succ.append(int(src[j]) in full)
    assert len(succ) == 200 * t.n_new_succ
    assert 0.45 < np.mean(succ) < 0.58


def test_steady_has_no_new_edge():
    cfg, mix = load("rec-1m", "steady")
    ids = held(cfg)
    due, src, dst = Traffic(mix, cfg, ids, 9, 0.5).open_events()
    assert src.size == round(mix["ingest"]["rate_per_s"] * 0.5)
    assert np.all(np.diff(due) > 0)
    assert is_held(cfg, ids, src, dst).all()


def test_read_mix_is_ycsb_b():
    cfg, mix = load("rec-8m-x4", "read-x4")
    ids = held(cfg)
    t = Traffic(mix, cfg, ids, 11, 400.0 / mix["reads"]["rate_per_s"])
    rd = t.reads()
    due, src, dst = t.open_events()
    calls = rd.due.size
    queries = int((~rd.is_topn).sum())
    assert rd.is_topn.sum() == calls // mix["reads"]["topn_every"]
    records_read = queries * mix["reads"]["query_width"]
    # YCSB-B: 95% of records touched are reads, 5% updates (queries count
    # their sources; top-n calls are reads of the whole chain, not counted)
    share = src.size / (src.size + records_read)
    assert abs(share - 0.05) < 0.002
    assert is_held(cfg, ids, src, dst).all()
