"""The comparison that decides ``correct``: the program's timed answers and
final state against the plain reference (``reference.py``) fed the same
batches in the same order.

Numbers compared (each has a limit in ``limits.json``):

* ``state_diff``: rows (over every shard) whose counts, successors, order
  or total differ after the window, plus src-table entries that differ,
  plus the differences of the row count and of the eviction count;
* ``read_diff``: sampled read answers that differ: a query row whose
  successors or ``n_needed`` differ from the reference at the epoch the
  read saw, every row of a query that saw an epoch older than the last
  observe acknowledged before it was issued, and a top-n call whose edges
  differ (tie-robust: edges above the last probability as a set, edges at
  it as edges of that probability);
* ``prob_ulp``: the widest gap, in float32 ulps of the reference, between a
  served probability and the reference's ``cnt / tot``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from generator import dst_of
from reference import EMPTY, ReferenceChain, bfloat16


@dataclasses.dataclass
class ProgramState:
    cnt: np.ndarray      # [S, N, C]
    dst: np.ndarray
    order: np.ndarray
    tot: np.ndarray      # [S, N]
    tab_keys: np.ndarray  # [S, T]
    tab_vals: np.ndarray
    n_rows: np.ndarray   # [S]
    evictions: int


@dataclasses.dataclass
class Read:
    version: int         # epoch the call read (store version)
    acked: int           # last epoch acknowledged before the call was issued
    srcs: np.ndarray     # queried sources (query) or None (top-n)
    answer: tuple        # (dsts, probs, n_needed) or (srcs, dsts, probs)
    routed_out: np.ndarray  # bool per source: dropped by the router (failed)


def _ulps(a, b) -> float:
    a = np.asarray(a, np.float32).astype(np.float64)
    b = np.asarray(b, np.float32)
    if b.size == 0:
        return 0.0
    gap = np.abs(a - b.astype(np.float64)) / np.spacing(np.abs(b)).astype(
        np.float64)
    return float(gap.max())


def make_reference(cfg: dict, control: bool) -> ReferenceChain:
    mc, sv = cfg["mc"], cfg["serve"]
    kw = dict(count_dtype=np.int64, float_dtype=np.float32)
    if control:
        kw = dict(count_dtype=bfloat16(), float_dtype=bfloat16())
    return ReferenceChain(sv["num_shards"], mc["num_rows"], mc["capacity"],
                          decay_threshold=sv["decay_threshold"],
                          decay_block_rows=mc.get("decay_block_rows", 0),
                          bucket_factor=sv["bucket_factor"], **kw)


def _query_diff(ref, rd: Read, threshold, max_items):
    d, p, n = (np.asarray(x) for x in rd.answer)
    rd_, rp, rn = ref.query(rd.srcs, threshold, max_items)
    keep = ~rd.routed_out
    if rd.version < rd.acked:
        return int(keep.sum()), 0.0
    same = (d == rd_).all(1) & (n == rn)
    bad = int((~same & keep).sum())
    ok = same & keep
    return bad, _ulps(p[ok], rp[ok])


def _topn_diff(ref, rd: Read, n: int):
    s, d, p = (np.asarray(x) for x in rd.answer)
    rs, rdd, rp = ref.topn(n)
    gap = _ulps(p, rp)
    if rd.version < rd.acked or p.shape != rp.shape:
        return 1, gap
    last, last_p = rp[-1], p[-1]
    above = {(int(a), int(b)) for a, b, q in zip(rs, rdd, rp) if q > last}
    above_p = {(int(a), int(b)) for a, b, q in zip(s, d, p) if q > last_p}
    ok = above == above_p
    k = min(n, ref.C)
    for a, b, q in zip(s, d, p):
        if q == last_p and q > 0:
            ep = ref.edge_prob(int(a), int(b), k)
            ok &= ep is not None and _ulps(q, ep) <= 1.0
        elif q == 0:
            ok &= a == EMPTY and b == EMPTY
    return (0 if ok else 1), gap


def _state_diff(ref: ReferenceChain, st: ProgramState) -> int:
    f = np.float64
    diff = 0
    for s in range(ref.S):
        rows = ((st.cnt[s].astype(f) != ref.cnt[s].astype(f)).any(1)
                | (st.dst[s] != ref.dst[s]).any(1)
                | (st.order[s] != ref.order[s]).any(1)
                | (st.tot[s].astype(f) != ref.tot[s].astype(f)))
        diff += int(rows.sum())
        live = st.tab_keys[s] >= 0
        prog = ((st.tab_keys[s][live].astype(np.int64) << 32)
                | st.tab_vals[s][live].astype(np.int64))
        n = int(ref.n_rows[s])
        want = (ref.row_src[s, :n].astype(np.int64) << 32) | np.arange(n)
        diff += int(np.setxor1d(prog, want).size)
        diff += abs(int(st.n_rows[s]) - n)
    diff += abs(int(st.evictions) - int(ref.evictions))
    return diff


def compare(cfg: dict, warm_ids, warm_counts, observes, reads,
            state: ProgramState, control: bool = False) -> dict:
    """``observes``: ``[(version, src, dst)]`` in publish order (``version``
    is the epoch the observe published); ``reads``: checked ``Read``s."""
    sv = cfg["serve"]
    ref = make_reference(cfg, control)
    ref.seat(warm_ids, warm_counts, dst_of)
    order = sorted(range(len(reads)), key=lambda i: reads[i].version)
    read_diff, ulp = 0, 0.0
    j = 0
    for i in order:
        rd = reads[i]
        while j < len(observes) and observes[j][0] <= rd.version:
            ref.observe(observes[j][1], observes[j][2])
            j += 1
        if rd.srcs is None:
            bad, gap = _topn_diff(ref, rd, sv["topn"])
        else:
            bad, gap = _query_diff(ref, rd, sv["threshold"], sv["max_items"])
        read_diff += bad
        ulp = max(ulp, gap)
    for v, src, dst in observes[j:]:
        ref.observe(src, dst)
    return {"state_diff": _state_diff(ref, state), "read_diff": read_diff,
            "prob_ulp": ulp,
            "max_row_total": float(ref.tot.astype(np.float64).max()),
            "ref_decay_steps": ref.decay_steps, "ref_evictions":
            ref.evictions}
