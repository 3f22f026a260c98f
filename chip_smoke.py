#!/usr/bin/env python3
"""Chip smoke test: the chain server's main path on a TPU at 2^20 rows.

Drives ``ShardedEngine`` observe -> query -> topn through its public API on
one chip, with ``MCConfig(num_rows=2**20, capacity=64)`` allocated in full,
and fails unless:

  * the device is a TPU and the compiled update and query programs contain
    Pallas kernels (``tpu_custom_call``);
  * every query answer, the top-n answer and a digest of the learned state
    equal, bit for bit, those of an ``impl="ref"`` engine fed the same
    events (the plain-XLA reference path, no Pallas);
  * no read was degraded, no write failed, no dispatch was retried and no
    item was dropped by the router.

The events are seeded Zipf transitions made in bulk with numpy.  Every new
(src, dst) edge takes one serial step of the learner's new-edge scan, so
the load is cut to ``--batches`` batches (printed with the cut) while the
state stays allocated at full size.

  python3 chip_smoke.py                 # one chip
  python3 chip_smoke.py --four-chips    # num_shards=4 against num_shards=1

``--four-chips`` runs only the sharded path: the same events through a
four-shard engine (one shard per chip) and a one-shard engine in this
process, whose query and top-n answers must agree with no routing or row
drops.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NUM_ROWS = 2 ** 20
CAPACITY = 64
OUT_DEGREE = 48          # successors per source: fits a row, no evictions
ZIPF_S = 1.1             # source skew and successor-rank skew
QUERY_WIDTH = 64         # sources per query call
THRESHOLDS = (0.5, 0.9, 0.99)
TOPN = 16


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_events(seed: int, n: int, num_nodes: int):
    """Seeded Zipf transitions: Zipf sources over ``num_nodes`` ids (ranks
    shuffled over the id space), each with a Zipf choice among its
    ``OUT_DEGREE`` successors."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(num_nodes).astype(np.int64)
    src = ids[(rng.zipf(ZIPF_S, n) - 1) % num_nodes]
    rank = (rng.zipf(ZIPF_S, n) - 1) % OUT_DEGREE
    dst = (src * 2654435761 + rank * 40503 + 7) % num_nodes
    return src.astype(np.int32), dst.astype(np.int32)


def make_queries(seed: int, src: np.ndarray, calls: int, num_nodes: int):
    """Query batches: the hottest sources, sources seen once or more, and
    ids never observed (unknown sources answer empty)."""
    rng = np.random.default_rng(seed + 1)
    ids, counts = np.unique(src, return_counts=True)
    hot = ids[np.argsort(-counts, kind="stable")[:QUERY_WIDTH]]
    out = []
    for i in range(calls):
        kind = i % 3
        if kind == 0:
            q = rng.permutation(hot)
        elif kind == 1:
            q = rng.choice(ids, QUERY_WIDTH)
        else:
            q = np.where(rng.random(QUERY_WIDTH) < 0.5,
                         rng.choice(ids, QUERY_WIDTH),
                         num_nodes + rng.integers(0, num_nodes, QUERY_WIDTH))
        out.append((q.astype(np.int32), THRESHOLDS[i % len(THRESHOLDS)]))
    return out


def state_digest(state):
    """Per-array uint32 digest of a state pytree, computed on the device
    (position-weighted sums, exact in wrapping arithmetic)."""
    import jax
    import jax.numpy as jnp

    def one(x):
        v = x.reshape(-1).astype(jnp.uint32)
        w = jnp.arange(v.size, dtype=jnp.uint32) * jnp.uint32(2654435761)
        return jnp.sum(v * (w + jnp.uint32(1)))

    leaves = jax.tree_util.tree_leaves(state)
    return np.asarray(jax.jit(lambda xs: jnp.stack([one(x) for x in xs]))(
        leaves))


def build_engine(impl: str, num_shards: int, mesh=None):
    from repro.core import mcprioq as mc
    from repro.core import sharded as sh
    from repro.serve.engine import ShardedEngine, ShardedServeConfig

    base = mc.MCConfig(num_rows=NUM_ROWS, capacity=CAPACITY, sort_passes=1,
                       impl=impl)
    # a bucket of num_shards fair shares holds a whole sender slice, so the
    # router can drop nothing; every shard's local batch (and its new-edge
    # scan) is then exactly one observe() batch long
    scfg = sh.ShardedConfig(base=base, num_shards=num_shards,
                            bucket_factor=float(num_shards))
    # decay off: rolling decay halves row blocks whose placement depends on
    # the shard layout, and this run compares layouts and paths exactly
    return ShardedEngine(ShardedServeConfig(
        sharded=scfg, decay_threshold=2 ** 30, topn=TOPN), mesh=mesh)


def compile_programs(engine, batch: int):
    """AOT-compile the engine's update and query programs on the arrays it
    dispatches (the persistent cache then serves its own first calls);
    returns seconds, checks for Pallas kernels in each and prints each
    program's compile-time memory analysis."""
    import jax.numpy as jnp
    from repro.core import sharded as sh

    scfg = engine.cfg.sharded
    snap = engine.store.acquire()
    try:
        ids = jnp.asarray(np.zeros(batch, np.int32))
        q = jnp.asarray(np.zeros(QUERY_WIDTH, np.int32))
        t0 = time.perf_counter()
        upd = sh.make_update_fn(scfg, engine.mesh).lower(
            snap.state, ids, ids, ids).compile()
        qry = sh.make_query_fn(scfg, engine.mesh, THRESHOLDS[0],
                               engine.cfg.max_items).lower(
            snap.state, q).compile()
        seconds = time.perf_counter() - t0
    finally:
        engine.store.release(snap)
    for name, prog in (("update", upd), ("query", qry)):
        check("tpu_custom_call" in prog.as_text(),
              f"compiled {name} program has no Pallas kernel")
        mem = prog.memory_analysis()
        print(f"memory_analysis {name}: arguments "
              f"{mem.argument_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B")
    return seconds


def memory_line(dev, when: str) -> str:
    st = dev.memory_stats() or {}
    return (f"memory {when}: bytes_in_use {st.get('bytes_in_use')}, "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')}")


def serve(engine, src, dst, batch: int, queries, after_first=None):
    """Load the events, then answer the queries and one topn.  Returns
    (load seconds, first-observe seconds, answers, topn).  ``after_first``
    is called once the first observe() has returned."""
    t0 = time.perf_counter()
    engine.observe(src[:batch], dst[:batch])
    first = time.perf_counter() - t0
    if after_first is not None:
        after_first()
    for lo in range(batch, src.size, batch):
        engine.observe(src[lo:lo + batch], dst[lo:lo + batch])
    snap = engine.store.acquire()
    try:
        np.asarray(snap.state.n_rows)          # wait for the last update
    finally:
        engine.store.release(snap)
    load = time.perf_counter() - t0
    answers = []
    for q, t in queries:
        d, p, n = engine.query(q, threshold=t)
        answers.append((np.asarray(d), np.asarray(p), np.asarray(n)))
    topn = tuple(np.asarray(x) for x in engine.topn())
    return load, first, answers, topn


def engine_digest(engine):
    snap = engine.store.acquire()
    try:
        return state_digest(snap.state)
    finally:
        engine.store.release(snap)


def check_counters(engine, label: str):
    st = engine.stats_snapshot()
    for key in ("degraded_answers", "write_errors", "dispatch_retries",
                "route_dropped", "query_dropped", "dropped_rows"):
        check(st[key] == 0, f"{label}: {key}={st[key]}")
    return st


def same_answers(a, b) -> bool:
    return all(x.tobytes() == y.tobytes()
               for pa, pb in zip(a, b) for x, y in zip(pa, pb))


def same_topn(a, b) -> bool:
    """Top-n answers of two shard layouts: the probabilities equal bit for
    bit, and so do the (src, dst) edges at every probability above the
    last one (edges tied at the last probability are cut by row position,
    which the layout decides)."""
    (sa, da, pa), (sb, db, pb) = a, b
    if pa.tobytes() != pb.tobytes():
        return False
    above = pa > pa[-1]
    return (sorted(zip(sa[above], da[above]))
            == sorted(zip(sb[above], db[above])))


def phase_one_chip(args, dev):
    import jax

    src, dst = make_events(args.seed, args.batches * args.batch, NUM_ROWS)
    edges = src.astype(np.int64) * NUM_ROWS + dst
    new_edges = np.unique(edges).size
    # the first batch also compiles: the per-edge rate is taken after it
    new_later = new_edges - np.unique(edges[:args.batch]).size
    queries = make_queries(args.seed, src, args.queries, NUM_ROWS)
    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"{len(jax.devices())} visible")
    print(f"state: MCConfig(num_rows={NUM_ROWS}, capacity={CAPACITY}) "
          f"allocated in full")
    print(f"load: {src.size} events in {args.batches} batches of "
          f"{args.batch}, {new_edges} new edges over "
          f"{np.unique(src).size} sources; cut to "
          f"{new_edges / (NUM_ROWS * CAPACITY):.4%} of the "
          f"{NUM_ROWS * CAPACITY} edge slots (--batches)")

    results = {}
    for impl in ("auto", "ref"):     # the Pallas path first: its peak
        engine = build_engine(impl, 1)
        after_first = None
        compile_s = None
        if impl == "auto":
            print(memory_line(dev, "after the state is built"))
            compile_s = compile_programs(engine, args.batch)
            print(memory_line(dev, "after the AOT compile"))
            after_first = lambda: print(memory_line(
                dev, "after the first observe"))
        load, first, answers, topn = serve(engine, src, dst, args.batch,
                                           queries, after_first)
        st = check_counters(engine, impl)
        results[impl] = (answers, topn, engine_digest(engine))
        if impl == "auto":
            peak = dev.memory_stats().get("peak_bytes_in_use")
            print(f"compile: update+query programs {compile_s:.3f} s "
                  f"(AOT); first observe {first:.3f} s")
            print(f"load: {load:.3f} s, {st['n_rows']} rows; after the "
                  f"first batch {load - first:.3f} s for {new_later} new "
                  f"edges, {(load - first) / new_later * 1e6:.3f} us per "
                  f"new edge")
            print(f"peak_bytes_in_use: {peak}")
        else:
            print(f"ref path: load {load:.3f} s (first observe "
                  f"{first:.3f} s)")
        engine.close()
        del engine
        gc.collect()                 # free this path's state before the next

    (a_ans, a_top, a_dig), (r_ans, r_top, r_dig) = (results["auto"],
                                                    results["ref"])
    check(same_answers(a_ans, r_ans),
          "query answers differ from the impl='ref' path")
    check(all(x.tobytes() == y.tobytes() for x, y in zip(a_top, r_top)),
          "topn answer differs from the impl='ref' path")
    check(a_dig.tobytes() == r_dig.tobytes(),
          "learned state differs from the impl='ref' path")
    live = sum(int((a[2] > 0).sum()) for a in a_ans)
    print(f"answers: {len(a_ans)} query calls ({live} non-empty rows) and "
          f"topn({TOPN}) bit-identical to impl='ref'; state digest equal")


def phase_four_chips(args, dev):
    import jax
    from repro import compat

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    src, dst = make_events(args.seed, args.batches * args.batch, NUM_ROWS)
    queries = make_queries(args.seed, src, args.queries, NUM_ROWS)
    print(f"device: {dev.device_kind} x{len(devices)}")
    print(f"load: {src.size} events in {args.batches} batches of "
          f"{args.batch}; 4 shards of {NUM_ROWS} rows against 1 shard")
    one = compat.make_mesh((1,), ("shard",), devices=devices[:1])
    out = {}
    for shards, mesh in ((4, None), (1, one)):
        engine = build_engine("auto", shards, mesh=mesh)
        load, first, answers, topn = serve(engine, src, dst, args.batch,
                                           queries)
        st = check_counters(engine, f"{shards} shards")
        print(f"{shards} shard(s): load {load:.3f} s (first observe "
              f"{first:.3f} s), route_dropped={st['route_dropped']} "
              f"dropped_rows={st['dropped_rows']}")
        out[shards] = (answers, topn)
        engine.close()
        del engine
        gc.collect()
    check(same_answers(out[4][0], out[1][0]),
          "query answers differ between 4 shards and 1")
    check(same_topn(out[4][1], out[1][1]),
          "topn answers differ between 4 shards and 1")
    print(f"answers: {len(out[4][0])} query calls and topn({TOPN}) equal "
          f"across 4 shards and 1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard vs 1-shard phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4096,
                    help="events per observe() call")
    ap.add_argument("--batches", type=int, default=3,
                    help="observe() calls in the load (the cut)")
    ap.add_argument("--queries", type=int, default=300,
                    help="query() calls answered and compared")
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package beside this "
              f"script: {e}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              f"runs only on the chip", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    try:
        if args.four_chips:
            phase_four_chips(args, dev)
        else:
            phase_one_chip(args, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
