"""Benchmark harness: one benchmark per paper claim.

The paper (MCPrioQ) is evaluated on complexity/throughput, not accuracy; it
has no numbered tables, so each benchmark validates one stated claim:

  B1 update_throughput   O(1) amortised updates (§II.A) — edges/sec flat in
                         graph size
  B2 query_cdf           O(CDF^-1(t)) inference (§II.B) — items touched vs
                         threshold, per Zipf exponent
  B3 sortedness          approximate order under continuous updates (§II.2)
  B4 decay               §II.C maintenance: stop-the-world vs rolling decay
                         (per-call cost must scale with decay_block_rows,
                         not num_rows), dst-hash repair on/off
  B5 hash_vs_scan        dst hash-table vs slab scan (§II.2 "may not be that
                         obvious")
  B6 drafter             serving feature: n-gram drafter acceptance rate
  B7 sharded_routing     all_to_all node-sharded scaling (8 fake devices)
  B8 persist             durability subsystem (DESIGN.md §10): snapshot
                         save/restore, WAL append per fsync policy + replay
                         throughput, N -> M elastic reshard (8 fake devices)
  B9 faults              crash soak (DESIGN.md §12): SIGKILL a serving
                         worker in a loop (externally and from inside the
                         persistence failpoints), assert bit-exact recovery
                         vs the deterministic-replay oracle, record
                         recovery time per kill (tools/chaos/soak.py)
  B10 obs                telemetry overhead (DESIGN.md §13): armed vs
                         disarmed on the observe/query hot paths plus the
                         disarmed gate cost in isolation — disarmed must
                         be ~free, armed must stay within budget

Prints ``name,us_per_call,derived`` CSV lines (harness contract) and writes
``BENCH_<bench>.json`` next to this file with the same rows in machine-
readable form, so successive PRs can diff perf runs.

``--smoke`` shrinks every benchmark to CI scale (same recorders, same JSON
schema, minutes not hours); ``--validate`` checks every ``BENCH_*.json`` on
disk against the recorder schema and exits non-zero on stale files.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import mcprioq as mc
from repro.core import speculative as spec
from repro.data.synthetic import MarkovGraphSampler

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(_HERE)

SMOKE = False  # set by --smoke: CI-scale sizes, full recorder coverage


class Recorder:
    """Collects (name, us_per_call, derived, extras) rows per benchmark and
    mirrors every CSV line into ``BENCH_<bench>.json``."""

    def __init__(self):
        self.rows = {}
        self.failed = []   # names of rows a failed child process left

    def emit(self, bench: str, name: str, us: float, derived: str, **extra):
        if extra.get("failed"):
            self.failed.append(name)
        # small values (per-query latencies, ratios) keep their decimals
        print(f"{name},{us:.2f},{derived}" if us < 100 else
              f"{name},{us:.1f},{derived}")
        self.rows.setdefault(bench, []).append(
            {"name": name, "us_per_call": round(us, 3), "derived": derived,
             **extra})

    def write(self, bench: str):
        path = os.path.join(_HERE, f"BENCH_{bench}.json")
        with open(path, "w") as f:
            json.dump({"bench": bench, "rows": self.rows.get(bench, [])},
                      f, indent=1)
        return path


REC = Recorder()


def _time(fn, *args, n=10, warmup=2):
    """Median per-call latency in us (robust to CPU scheduling noise)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e6  # us


def _time_paired(fns, n=20, warmup=2):
    """Min per-call latency in us for several candidates, sampled in
    alternation so slow drift (thermal, background load) hits every
    candidate equally — the right design for A-vs-B sweeps where the
    quantity of interest is the ratio."""
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples = [[] for _ in fns]
    for _ in range(n):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[i].append(time.perf_counter() - t0)
    return [float(np.min(s)) * 1e6 for s in samples]


def bench_update_throughput():
    """B1: edges/sec for batched updates; flat across graph sizes = O(1),
    plus a new-edge-fraction sweep of the fused pipeline vs the seed path."""
    batch = 256 if SMOKE else 1024
    rows = []
    for num_nodes in (256, 1024) if SMOKE else (256, 1024, 4096):
        cfg = mc.MCConfig(num_rows=num_nodes, capacity=64, sort_passes=1)
        graph = MarkovGraphSampler(num_nodes=num_nodes, out_degree=32, seed=0)
        state = mc.init(cfg)
        # warm the graph so updates take the fast path (paper's normal case)
        for _ in range(4):
            s, d = graph.sample_transitions(batch)
            state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                    cfg=cfg)
        s, d = graph.sample_transitions(batch)
        s, d = jnp.asarray(s), jnp.asarray(d)
        us = _time(lambda: mc.update_batch(state, s, d, cfg=cfg), n=5)
        eps = batch / (us / 1e6)
        rows.append((num_nodes, us, eps))
        REC.emit("update", f"B1_update_throughput[nodes={num_nodes}]", us,
                 f"{eps:.0f} edges/s", nodes=num_nodes,
                 edges_per_s=round(eps))
    # O(1) check: us/edge varies < 3x across 16x graph growth
    per_edge = [r[1] / batch for r in rows]
    REC.emit("update", "B1_o1_ratio", max(per_edge) / min(per_edge),
             "us/edge ratio across 16x graph sizes")

    # new-edge-fraction sweep: fused pipeline (bounded slow path, kernel
    # dispatch) vs the seed implementation (O(B) sequential scan per batch).
    # Injected new edges reuse warmed srcs, so num_rows stays at graph scale.
    num_nodes = 512 if SMOKE else 1024
    cfg = mc.MCConfig(num_rows=num_nodes, capacity=64, sort_passes=1,
                      max_new_per_batch=128)
    graph = MarkovGraphSampler(num_nodes=num_nodes, out_degree=32, seed=0)
    state = mc.init(cfg)
    # warm with the FULL edge list, uncapped, so every graph edge is live
    # and frac exactly controls the new-edge count (paper's steady state);
    # warming through the capped config would silently defer most edges
    warm_cfg = dataclasses.replace(cfg, max_new_per_batch=0)
    all_src = np.repeat(np.arange(num_nodes, dtype=np.int32),
                        graph.out_degree)
    all_dst = graph.dsts.reshape(-1).astype(np.int32)
    for i in range(0, all_src.size, batch):
        state = mc.update_batch(state, jnp.asarray(all_src[i:i + batch]),
                                jnp.asarray(all_dst[i:i + batch]),
                                cfg=warm_cfg)
    for frac in (0.0, 0.1) if SMOKE else (0.0, 0.01, 0.1, 0.5):
        s, d = graph.sample_transitions_mixed(batch, frac)
        s, d = jnp.asarray(s), jnp.asarray(d)
        us_new = _time(lambda: mc.update_batch(state, s, d, cfg=cfg), n=15)
        us_ref = _time(
            lambda: mc.update_batch_reference(state, s, d, cfg=cfg), n=15)
        speedup = us_ref / us_new
        # work parity check: edges the capped path defers but the seed
        # path applies (0 while round(frac * batch) <= max_new_per_batch)
        deferred = int(mc.update_batch(state, s, d, cfg=cfg).deferred_new
                       - state.deferred_new)
        REC.emit("update", f"B1_new_edge_sweep[frac={frac}]", us_new,
                 f"{speedup:.1f}x vs seed path ({us_ref:.0f} us, "
                 f"deferred={deferred})",
                 new_edge_fraction=frac, batch=batch,
                 us_per_call_seed=round(us_ref, 3),
                 speedup_vs_seed=round(speedup, 2),
                 deferred_new=deferred,
                 max_new_per_batch=cfg.max_new_per_batch)
    REC.write("update")


def bench_query_cdf():
    """B2: items touched (CDF^-1) and latency vs threshold and Zipf s, plus
    the DESIGN.md §8 read-side sweeps: fused vs unfused gather by batch
    size, and chunked early-exit cost vs mean_items (must track CDF^-1(t),
    not C)."""
    n = 512 if SMOKE else 2048
    cfg = mc.MCConfig(num_rows=n, capacity=64, sort_passes=2)
    fused_speedups = []   # (B >= 1024 rows) -> B2_fused_check aggregate
    for zipf_s in (1.5,) if SMOKE else (1.2, 1.5, 2.0):
        graph = MarkovGraphSampler(num_nodes=n, out_degree=48,
                                   zipf_s=zipf_s, seed=1)
        state = mc.init(cfg)
        for _ in range(10 if SMOKE else 30):
            s, d = graph.sample_transitions(n)
            state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                    cfg=cfg)
        srcs = jnp.arange(512, dtype=jnp.int32)
        for t in (0.5, 0.9, 0.99):
            us = _time(lambda: mc.query_threshold(
                state, srcs, t, cfg=cfg, max_items=48), n=5)
            _, _, n_needed = mc.query_threshold(state, srcs, t, cfg=cfg,
                                                max_items=48)
            mean_items = float(jnp.mean(n_needed.astype(jnp.float32)))
            REC.emit("query_cdf", f"B2_query_cdf[s={zipf_s};t={t}]", us / 512,
                     f"{mean_items:.2f} items touched (CDF^-1)",
                     zipf_s=zipf_s, threshold=t,
                     mean_items=round(mean_items, 3))

        # fused vs unfused gather: the in-kernel row gather must beat the
        # host-side O(B*C) _ordered_rows pipeline as B grows
        for batch in (128, 256) if SMOKE else (256, 1024, 4096):
            srcs_b = jnp.asarray(
                np.arange(batch, dtype=np.int32) % n)

            def q(fused):
                cfg_f = dataclasses.replace(cfg, fused_query=fused)
                return lambda: mc.query_threshold(
                    state, srcs_b, 0.9, cfg=cfg_f, max_items=16)

            us_unf, us_fus = _time_paired([q(False), q(True)],
                                          n=8 if SMOKE else 30)
            res = {False: us_unf, True: us_fus}
            speedup = res[False] / res[True]
            if batch >= (256 if SMOKE else 1024):
                fused_speedups.append(speedup)
            for fused in (False, True):
                REC.emit("query_cdf",
                         f"B2_fused_sweep[s={zipf_s};B={batch};"
                         f"fused={fused}]", res[fused],
                         f"{speedup:.2f}x fused/unfused at B={batch}",
                         zipf_s=zipf_s, batch=batch, fused=fused,
                         threshold=0.9,
                         speedup_fused=round(speedup, 3))
    if fused_speedups:
        # single-row CPU timings are noisy; the aggregate is the claim
        geo = float(np.exp(np.mean(np.log(fused_speedups))))
        REC.emit("query_cdf", "B2_fused_check", geo,
                 f"geomean fused speedup over {len(fused_speedups)} "
                 f"B>=1024 rows",
                 geomean_speedup=round(geo, 3),
                 rows_aggregated=len(fused_speedups))
    # chunked early-exit sweep (pallas kernel, big C): per-call cost must
    # grow with mean_items (CDF^-1(t)), not capacity — later chunks of
    # satisfied blocks are predicated off with @pl.when.  Rows carry a
    # near-uniform live prefix so CDF^-1(t) ~ t * live actually spans the
    # chunks (a steep zipf row saturates inside chunk 0 at every t), and
    # the kernel is timed directly on pre-ordered rows so the probe/gather
    # stages don't mask the walk.
    from repro.kernels import ops as kops
    cap = 256
    bq = 128 if SMOKE else 512
    rng = np.random.default_rng(2)
    live = cap - 32
    c_np = np.zeros((bq, cap), np.int32)
    c_np[:, :live] = rng.integers(90, 110, (bq, live))
    c_np = np.sort(c_np, axis=1)[:, ::-1].copy()
    c_ord = jnp.asarray(c_np)
    d_ord = jnp.asarray(rng.integers(0, 10_000, (bq, cap)).astype(np.int32))
    tot = jnp.asarray(c_np.sum(1).astype(np.int32))
    for t in (0.25, 0.5, 0.97):
        _, _, n_needed = kops.cdf_query(c_ord, d_ord, tot, t, max_items=16)
        mean_items = float(jnp.mean(n_needed.astype(jnp.float32)))
        for chunks in (1, 2) if SMOKE else (1, 2, 4):
            us = _time(lambda: kops.cdf_query(
                c_ord, d_ord, tot, t, max_items=16, chunks=chunks,
                impl="pallas"), n=5 if SMOKE else 15)
            REC.emit("query_cdf",
                     f"B2_chunk_sweep[t={t};chunks={chunks}]", us,
                     f"{mean_items:.1f} mean_items (CDF^-1), C={cap}",
                     threshold=t, chunks=chunks, capacity=cap,
                     mean_items=round(mean_items, 3))
    REC.write("query_cdf")


def bench_sortedness():
    """B3: order quality after each update batch, by sort passes."""
    from repro.core import slab as sl
    for passes in (0, 2) if SMOKE else (0, 1, 2, 4):
        cfg = mc.MCConfig(num_rows=512, capacity=64, sort_passes=passes)
        graph = MarkovGraphSampler(num_nodes=512, out_degree=48, seed=2)
        state = mc.init(cfg)
        fracs = []
        for _ in range(10 if SMOKE else 20):
            s, d = graph.sample_transitions(1024)
            state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                    cfg=cfg)
            fracs.append(float(sl.sorted_fraction(state.slabs.cnt,
                                                  state.slabs.order)))
        REC.emit("sortedness", f"B3_sortedness[passes={passes}]", 0.0,
                 f"{np.mean(fracs[5:]):.4f} sorted fraction steady state",
                 passes=passes, sorted_fraction=round(float(np.mean(fracs[5:])), 5))
    REC.write("sortedness")


def bench_decay():
    """B4: §II.C maintenance-mode sweep (stop-the-world vs rolling decay,
    dst-hash repair on vs off).

    Two claims recorded: rolling per-call cost is *bounded* — it scales with
    ``decay_block_rows``, not ``num_rows`` (``B4_bounded_check``) — and a
    full rolling sweep costs about the same total work as one stop-the-world
    call, just amortised across ``n_blocks`` calls.
    """
    sizes = (512, 1024) if SMOKE else (1024, 4096)
    block = 128 if SMOKE else 256   # fixed block: per-call cost must be flat
    warm_iters = 6 if SMOKE else 20
    rolling_us = {}
    stw_us = {}
    for num_rows in sizes:
        graph = MarkovGraphSampler(num_nodes=num_rows, out_degree=32, seed=3)
        for use_hash in (False, True):
            warm_cfg = mc.MCConfig(num_rows=num_rows, capacity=64,
                                   sort_passes=1, use_dst_hash=use_hash)
            state = mc.init(warm_cfg)
            for _ in range(warm_iters):
                s, d = graph.sample_transitions(num_rows)
                state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                        cfg=warm_cfg)
            live_before = int(jnp.sum(state.slabs.cnt > 0))
            for block_rows in (0, block):
                cfg = dataclasses.replace(warm_cfg,
                                          decay_block_rows=block_rows)
                us = _time(lambda: mc.decay(state, cfg=cfg), n=5)
                mode = "stw" if block_rows == 0 else "rolling"
                hl = "hash" if use_hash else "scan"
                if block_rows == 0:
                    state2 = mc.decay(state, cfg=cfg)
                    live_after = int(jnp.sum(state2.slabs.cnt > 0))
                    derived = (f"evicted {live_before - live_after} of "
                               f"{live_before} edges")
                    stw_us[(num_rows, use_hash)] = us
                else:
                    n_blocks = -(-num_rows // block_rows)
                    derived = (f"1/{n_blocks} of rows per call "
                               f"(block={block_rows})")
                    rolling_us[(num_rows, use_hash)] = us
                REC.emit("decay",
                         f"B4_decay[rows={num_rows};mode={mode};{hl}]", us,
                         derived, num_rows=num_rows, mode=mode,
                         use_dst_hash=use_hash, decay_block_rows=block_rows,
                         live_edges=live_before)
    # bounded-cost check: at a fixed block size, rolling per-call cost must
    # stay ~flat while stop-the-world grows with num_rows
    lo, hi = sizes[0], sizes[-1]
    for use_hash in (False, True):
        roll_ratio = rolling_us[(hi, use_hash)] / rolling_us[(lo, use_hash)]
        stw_ratio = stw_us[(hi, use_hash)] / stw_us[(lo, use_hash)]
        hl = "hash" if use_hash else "scan"
        REC.emit("decay", f"B4_bounded_check[{hl}]", roll_ratio,
                 f"rolling per-call ratio across {hi // lo}x rows "
                 f"(stop-the-world ratio {stw_ratio:.2f})",
                 rolling_ratio=round(roll_ratio, 3),
                 stw_ratio=round(stw_ratio, 3),
                 rows_factor=hi // lo, decay_block_rows=block)
    REC.write("decay")


def bench_hash_vs_scan():
    """B5: dst lookup via per-row hash table vs C-lane slab scan."""
    n = 512 if SMOKE else 1024
    for use_hash, label in ((False, "scan"), (True, "hash")):
        cfg = mc.MCConfig(num_rows=n, capacity=64, sort_passes=1,
                          use_dst_hash=use_hash)
        graph = MarkovGraphSampler(num_nodes=n, out_degree=48, seed=4)
        state = mc.init(cfg)
        for _ in range(4):
            s, d = graph.sample_transitions(n)
            state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                    cfg=cfg)
        s, d = graph.sample_transitions(n)
        s, d = jnp.asarray(s), jnp.asarray(d)
        us = _time(lambda: mc.update_batch(state, s, d, cfg=cfg), n=5)
        REC.emit("hash_vs_scan", f"B5_dst_lookup[{label}]", us,
                 f"update batch {n}", lookup=label)
    REC.write("hash_vs_scan")


def bench_drafter():
    """B6: n-gram drafter acceptance on a structured stream."""
    ncfg = spec.NGramConfig(order=2, mc=mc.MCConfig(num_rows=4096,
                                                    capacity=32,
                                                    sort_passes=1))
    st = spec.init(ncfg)
    rng = np.random.default_rng(5)
    # 80% deterministic successor process
    succ = rng.integers(0, 512, (512,)).astype(np.int32)
    toks = np.empty((8, 512), np.int32)
    toks[:, 0] = rng.integers(0, 512, 8)
    for t in range(1, 512):
        follow = succ[toks[:, t - 1]]
        noise = rng.integers(0, 512, 8)
        toks[:, t] = np.where(rng.random(8) < 0.8, follow, noise)
    toks_j = jnp.asarray(toks)
    st = spec.observe(st, toks_j, cfg=ncfg)   # learn once (and compile)
    # steady-state observe cost, same warmup+median contract as every other
    # recorder (one-shot timing was jit-compile-dominated and run-to-run
    # noise published false regressions in the committed JSON)
    us = _time(lambda: spec.observe(st, toks_j, cfg=ncfg), n=5)
    # drafts where the chain knows the successor
    ctx = jnp.asarray(toks[:, 100:102])
    draft, ok = spec.draft(st, ctx, cfg=ncfg, k=1)
    okm = np.asarray(ok)[:, 0]
    want = succ[np.asarray(ctx)[:, -1]]
    acc = float(np.mean((np.asarray(draft)[:, 0] == want)[okm])) if okm.any() else 0.0
    REC.emit("drafter", "B6_drafter", us,
             f"top-1 draft matches true successor {acc:.0%} of ok-drafts",
             acceptance=round(acc, 4))

    # us_per_draft: the one-dispatch walk kernel (DESIGN.md §8) vs the
    # k-dispatch scan oracle, per draft() call at serving batch size
    k = 4
    ctx_b = jnp.asarray(toks[:, 200:202])
    us_walk, us_scan = _time_paired(
        [lambda: spec.draft(st, ctx_b, cfg=ncfg, k=k),
         lambda: spec.draft_reference(st, ctx_b, cfg=ncfg, k=k)], n=20)
    for name, us_d in (("walk", us_walk), ("scan", us_scan)):
        REC.emit("drafter", f"B6_draft_us[{name}]", us_d,
                 f"k={k} draft per call ({name} path)",
                 us_per_draft=round(us_d, 3), k=k,
                 batch=int(ctx_b.shape[0]), path=name)
    REC.write("drafter")


def bench_sharded_routing():
    """B7: shard-count × batch sweep of the kernel-routed all_to_all path.

    One subprocess per shard count (the fake host device count is fixed at
    first jax init), each sweeping batch sizes: per row the routed-update
    latency (edges/s), the routed threshold-query latency, the drop counters
    — the fixed-capacity approximation must be *measurably* zero at the
    default bucket factor — plus one cross-shard top-n merge timing per
    shard count (``B7_topn``).  Written to ``BENCH_sharded_routing.json``.
    """
    import subprocess
    import textwrap
    shard_counts = (1, 4) if SMOKE else (1, 4, 8)
    batches = (512, 2048) if SMOKE else (2048, 8192)
    rows = 512 if SMOKE else 2048
    iters = 3 if SMOKE else 5
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"   # the child fakes host devices
    for shards in shard_counts:
        script = textwrap.dedent(f"""
            import json, os, time
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count={shards}")
            import jax, jax.numpy as jnp, numpy as np
            from repro import compat
            from repro.core import mcprioq as mc, sharded as sh

            def timeit(fn, n):
                jax.block_until_ready(fn())
                t0 = time.perf_counter()
                for _ in range(n):
                    out = fn()
                jax.block_until_ready(out)
                return (time.perf_counter() - t0) / n * 1e6

            mesh = compat.make_mesh(({shards},), ("shard",))
            scfg = sh.ShardedConfig(
                base=mc.MCConfig(num_rows={rows}, capacity=32, sort_passes=1),
                num_shards={shards}, bucket_factor=2.0)
            rng = np.random.default_rng(0)
            for batch in {batches}:
                state = sh.init_sharded(scfg, mesh)
                upd = sh.make_update_fn(scfg, mesh)
                qry = sh.make_query_fn(scfg, mesh, threshold=0.9,
                                       max_items=8)
                src = jnp.asarray(
                    rng.integers(0, 8192, batch).astype(np.int32))
                dst = jnp.asarray(
                    rng.integers(0, 512, batch).astype(np.int32))
                w = jnp.ones((batch,), jnp.int32)
                state = upd(state, src, dst, w)   # warm + compile
                us = timeit(lambda: upd(state, src, dst, w), {iters})
                q_us = timeit(lambda: qry(state, src), {iters})
                _, _, _, qdrop = qry(state, src)
                print("ROW " + json.dumps({{
                    "name": f"B7_shard_sweep[shards={shards};B={{batch}}]",
                    "us": us,
                    "derived": f"{{batch / (us / 1e6):.0f}} edges/s over "
                               f"{shards} shards (query {{q_us:.0f}} us)",
                    "shards": {shards}, "batch": batch,
                    "edges_per_s": round(batch / (us / 1e6)),
                    "query_us": round(q_us, 1),
                    "dropped": int(jnp.sum(state.route_dropped))
                    + int(jnp.sum(qdrop)),
                }}))
            topn = sh.make_topn_fn(scfg, mesh, 16)
            t_us = timeit(lambda: topn(state), {iters})
            _, _, probs, tdrop = topn(state)
            desc = bool(np.all(np.diff(np.asarray(probs)) <= 0))
            print("ROW " + json.dumps({{
                "name": f"B7_topn[shards={shards}]",
                "us": t_us,
                "derived": f"global top-16 merge, descending={{desc}} "
                           f"(unexposed={{int(tdrop)}})",
                "shards": {shards}, "n": 16,
            }}))
        """)
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=900)
        rows_out = [ln[4:] for ln in out.stdout.splitlines()
                    if ln.startswith("ROW ")]
        if not rows_out:  # keep the grep-able FAILED sentinel in CSV + JSON
            REC.emit("sharded_routing", f"B7_shard_sweep[shards={shards};B=0]",
                     -1.0, f"FAILED {out.stderr[-200:]}", failed=True,
                     shards=shards, batch=0, edges_per_s=-1, dropped=-1)
            continue
        for ln in rows_out:
            row = json.loads(ln)
            us = row.pop("us")
            REC.emit("sharded_routing", row.pop("name"), us,
                     row.pop("derived"), **row)
    REC.write("sharded_routing")


def bench_persist():
    """B8: durability & elasticity (DESIGN.md §10).

    Three recorders: snapshot save/restore latency at chain scale, WAL
    append cost per fsync policy plus full-replay throughput (recovery
    speed), and the N -> M elastic reshard — snapshot at 4 shards, restore
    at 2 and 8, recording re-ingestion edges/s (subprocess with 8 fake
    devices, same pattern as B7).
    """
    import shutil
    import subprocess
    import tempfile
    import textwrap
    from repro.persist import snapshot as snap_io
    from repro.persist.wal import WriteAheadLog

    rows = 512 if SMOKE else 4096
    batch = 256 if SMOKE else 1024
    n_batches = 6 if SMOKE else 20
    cfg = mc.MCConfig(num_rows=rows, capacity=64, sort_passes=1)
    graph = MarkovGraphSampler(num_nodes=rows, out_degree=32, seed=7)
    state = mc.init(cfg)
    batches = []
    for _ in range(n_batches):
        s, d = graph.sample_transitions(batch)
        batches.append((s.astype(np.int32), d.astype(np.int32)))
        state = mc.update_batch(state, jnp.asarray(s), jnp.asarray(d),
                                cfg=cfg)
    live = int(jnp.sum(state.slabs.cnt > 0))

    snap_dir = tempfile.mkdtemp()
    meta = {"wal_seq": n_batches - 1}
    us_save = _time(lambda: snap_io.save_snapshot(state, snap_dir, 0, meta),
                    n=5)
    like = mc.init(cfg)   # template built once: time the restore alone
    us_restore = _time(
        lambda: snap_io.restore_snapshot(like, snap_dir, 0), n=5)
    REC.emit("persist", f"B8_snapshot[rows={rows}]", us_save,
             f"{live} live edges (restore {us_restore:.0f} us)",
             num_rows=rows, live_edges=live,
             restore_us=round(us_restore, 1))
    shutil.rmtree(snap_dir)

    for fsync in ("always", "rotate", "never"):
        wal_dir = tempfile.mkdtemp()
        wal = WriteAheadLog(wal_dir, segment_records=64, fsync=fsync)
        t0 = time.perf_counter()
        for s, d in batches:
            wal.append(s, d)
        wal.close()
        us_append = (time.perf_counter() - t0) / n_batches * 1e6
        # recovery speed: replay every durable batch through update_batch
        replayed = mc.init(cfg)
        n_edges = 0
        t0 = time.perf_counter()
        for _seq, s, d, w in WriteAheadLog(wal_dir).replay():
            replayed = mc.update_batch(replayed, jnp.asarray(s),
                                       jnp.asarray(d), jnp.asarray(w),
                                       cfg=cfg)
            n_edges += s.size
        jax.block_until_ready(replayed.slabs.cnt)
        eps = n_edges / (time.perf_counter() - t0)
        REC.emit("persist", f"B8_wal[fsync={fsync}]", us_append,
                 f"append/batch; replay {eps:.0f} edges/s",
                 fsync=fsync, batches=n_batches,
                 replay_edges_per_s=round(eps))
        shutil.rmtree(wal_dir)

    # N -> M elastic reshard (fake-device subprocess; see B7)
    rows_sub = 256 if SMOKE else 1024
    warm = 4 if SMOKE else 12
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"   # the child fakes host devices
    script = textwrap.dedent(f"""
        import json, os, tempfile, time
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8")
        import numpy as np
        from repro.core import mcprioq as mc, sharded as sh
        from repro.data.synthetic import MarkovGraphSampler
        from repro.serve.engine import ShardedEngine, ShardedServeConfig

        snap_dir = tempfile.mkdtemp()
        base = mc.MCConfig(num_rows={rows_sub}, capacity=32, sort_passes=1)

        def eng(n):
            return ShardedEngine(ShardedServeConfig(
                sharded=sh.ShardedConfig(base=base, num_shards=n,
                                         bucket_factor=2.0),
                decay_threshold=1 << 30, snapshot_dir=snap_dir))

        g = MarkovGraphSampler(num_nodes={rows_sub}, out_degree=16, seed=0)
        e4 = eng(4)
        for _ in range({warm}):
            s, d = g.sample_transitions({batch})
            e4.observe(s, d)
        e4.checkpoint()
        snap = e4.store.acquire()
        try:
            edges = int(np.sum(np.asarray(snap.state.slabs.cnt) > 0))
        finally:
            e4.store.release(snap)
        for m in (2, 8):
            em = eng(m)
            t0 = time.perf_counter()
            info = em.restore()
            dt = time.perf_counter() - t0
            print("ROW " + json.dumps({{
                "name": f"B8_reshard[N=4;M={{m}}]",
                "us": dt * 1e6,
                "derived": f"{{edges / dt:.0f}} edges/s re-ingested "
                           f"(mode={{info['mode']}})",
                "from_shards": 4, "to_shards": m, "edges": edges,
                "edges_per_s": round(edges / dt),
            }}))
    """)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    rows_out = [ln[4:] for ln in out.stdout.splitlines()
                if ln.startswith("ROW ")]
    if not rows_out:  # keep the grep-able FAILED sentinel in CSV + JSON
        REC.emit("persist", "B8_reshard[N=4;M=0]", -1.0,
                 f"FAILED {out.stderr[-200:]}", failed=True, from_shards=4,
                 to_shards=0, edges=-1, edges_per_s=-1)
    for ln in rows_out:
        row = json.loads(ln)
        us = row.pop("us")
        REC.emit("persist", row.pop("name"), us, row.pop("derived"), **row)
    REC.write("persist")


def bench_faults():
    """B9: crash soak — kill/recover/verify loop from tools/chaos/soak.py
    (external SIGKILLs interleaved with kills armed inside the persistence
    failpoints), re-emitted through the recorder so the rows land in the
    shared CSV + ``BENCH_faults.json`` schema."""
    if REPO_ROOT not in sys.path:  # tools/ lives at the repo root
        sys.path.insert(0, REPO_ROOT)
    from tools.chaos.soak import run_soak
    result = run_soak(6 if SMOKE else 20)
    for row in result["rows"]:
        extra = {k: v for k, v in row.items()
                 if k not in ("name", "us_per_call", "derived")}
        REC.emit("faults", row["name"], row["us_per_call"], row["derived"],
                 **extra)
    REC.write("faults")
    if not result["ok"]:
        print("B9_crash_soak: DIVERGED (see rows)", file=sys.stderr)
        REC.failed.append("B9_crash_soak")


def bench_obs():
    """B10: telemetry overhead (DESIGN.md §13).

    Armed-vs-disarmed A/B on the serving hot paths (``_time_paired`` so
    drift hits both arms equally): the armed delta buys spans, histograms
    and traffic vectors; the disarmed path must cost one bool gate.  The
    disarmed gate is also timed in isolation (a tight span+hist loop) so
    the "disarmed is ~free" claim is a measured number, not an inference
    from two large nearly-equal latencies.
    """
    from repro.core import sharded as sh
    from repro.obs import metrics as obs
    from repro.serve.engine import ShardedEngine, ShardedServeConfig

    rows = 512 if SMOKE else 2048
    batch = 256 if SMOKE else 1024
    scfg = sh.ShardedConfig(
        base=mc.MCConfig(num_rows=rows, capacity=32, sort_passes=1),
        num_shards=1, bucket_factor=2.0)
    eng = ShardedEngine(ShardedServeConfig(sharded=scfg,
                                           decay_threshold=1 << 30))
    graph = MarkovGraphSampler(num_nodes=rows, out_degree=16, seed=11)
    s, d = graph.sample_transitions(batch)
    q = (np.arange(256, dtype=np.int32) % rows).astype(np.int32)
    eng.observe(s, d)   # compile both paths before timing
    eng.query(q)

    def observe_with(armed):
        def fn():
            (obs.arm if armed else obs.disarm)()
            eng.observe(s, d)
        return fn

    def query_with(armed):
        def fn():
            (obs.arm if armed else obs.disarm)()
            return eng.query(q)
        return fn

    n = 10 if SMOKE else 40
    try:
        for path, maker in (("observe", observe_with), ("query", query_with)):
            us_dis, us_arm = _time_paired([maker(False), maker(True)], n=n)
            pct = (us_arm - us_dis) / us_dis * 100.0
            REC.emit("obs", f"B10_{path}", us_arm,
                     f"armed {us_arm:.0f} us vs disarmed {us_dis:.0f} us "
                     f"({pct:+.1f}%)",
                     us_armed=round(us_arm, 3), us_disarmed=round(us_dis, 3),
                     overhead_pct=round(pct, 2), batch=batch)

        # the disarmed gate in isolation: per-record cost of a span + a
        # histogram sample while disarmed (both exit on the module bool)
        obs.disarm()
        reg = eng.metrics
        loops = 2000

        def gate():
            for _ in range(loops):
                reg.span("engine.observe")
                reg.hist_record("engine.observe", 0.0)

        us_loop = _time(gate, n=5)
        ns_per_record = us_loop * 1e3 / (2 * loops)
        # an observe() crosses the gate a handful of times (span, traffic
        # check, gauge); express that against the disarmed hot-path cost
        ops_per_observe = 4
        us_dis_obs = _time_paired([observe_with(False)], n=n)[0]
        gate_pct = (ops_per_observe * ns_per_record / 1e3) / us_dis_obs * 100
        REC.emit("obs", "B10_disarmed_gate", us_loop,
                 f"{ns_per_record:.0f} ns/record disarmed -> "
                 f"{gate_pct:.4f}% of a disarmed observe()",
                 ns_per_record=round(ns_per_record, 2),
                 overhead_pct=round(gate_pct, 4))
    finally:
        obs.disarm()
    REC.write("obs")


# ---------------------------------------------------------------------------
# schema validation (CI: BENCH_*.json must stay generatable + well-formed)
# ---------------------------------------------------------------------------

REQUIRED_ROW_KEYS = ("name", "us_per_call", "derived")

# per-bench schema: rows whose name starts with <prefix> must carry these
# extra keys, and each bench must contain at least one row per prefix — so a
# stale pre-sweep BENCH file fails --validate instead of passing vacuously
BENCH_ROW_SCHEMAS = {
    "query_cdf": {
        "B2_query_cdf": ("zipf_s", "threshold", "mean_items"),
        "B2_fused_sweep": ("batch", "fused", "speedup_fused"),
        "B2_fused_check": ("geomean_speedup",),
        "B2_chunk_sweep": ("threshold", "chunks", "capacity", "mean_items"),
    },
    "drafter": {
        "B6_drafter": ("acceptance",),
        "B6_draft_us": ("us_per_draft", "k", "path"),
    },
    "sharded_routing": {
        "B7_shard_sweep": ("shards", "batch", "edges_per_s", "dropped"),
        "B7_topn": ("shards", "n"),
    },
    "persist": {
        "B8_snapshot": ("num_rows", "live_edges", "restore_us"),
        "B8_wal": ("fsync", "batches", "replay_edges_per_s"),
        "B8_reshard": ("from_shards", "to_shards", "edges", "edges_per_s"),
    },
    "faults": {
        "B9_crash_soak": ("kill_mode", "steps", "replayed", "bitexact"),
        "B9_recovery_summary": ("kills", "mean_recovery_us",
                                "max_recovery_us", "bitexact"),
    },
    "obs": {
        "B10_observe": ("us_armed", "us_disarmed", "overhead_pct"),
        "B10_query": ("us_armed", "us_disarmed", "overhead_pct"),
        "B10_disarmed_gate": ("ns_per_record", "overhead_pct"),
    },
}


def validate_bench_files() -> int:
    """Check every BENCH_*.json against the Recorder schema (and the
    per-bench row schemas in ``BENCH_ROW_SCHEMAS``).

    Returns the number of problems found (0 = all good); prints one line per
    problem so CI logs point at the stale file directly.
    """
    problems = []
    paths = sorted(glob.glob(os.path.join(_HERE, "BENCH_*.json")))
    if not paths:
        problems.append("no BENCH_*.json files found (run benchmarks first)")
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            problems.append(f"{name}: unreadable ({e})")
            continue
        if not isinstance(data.get("bench"), str) or not isinstance(
                data.get("rows"), list):
            problems.append(f"{name}: missing 'bench'/'rows' envelope")
            continue
        if not data["rows"]:
            problems.append(f"{name}: empty rows")
            continue
        for i, row in enumerate(data["rows"]):
            missing = [k for k in REQUIRED_ROW_KEYS if k not in row]
            if missing:
                problems.append(
                    f"{name}: row {i} ({row.get('name', '?')}) "
                    f"missing {missing}")
        row_schemas = BENCH_ROW_SCHEMAS.get(data["bench"], {})
        for prefix, extra_keys in row_schemas.items():
            rows = [r for r in data["rows"]
                    if str(r.get("name", "")).startswith(prefix)]
            if not rows:
                problems.append(f"{name}: no '{prefix}*' rows (stale file — "
                                f"re-run benchmarks)")
                continue
            for row in rows:
                missing = [k for k in extra_keys if k not in row]
                if missing:
                    problems.append(f"{name}: row {row['name']} missing "
                                    f"{missing}")
    for p in problems:
        print(f"SCHEMA: {p}")
    if not problems:
        print(f"validated {len(paths)} BENCH_*.json files")
    return len(problems)


BENCHES = (
    ("update", bench_update_throughput),
    ("query_cdf", bench_query_cdf),
    ("sortedness", bench_sortedness),
    ("decay", bench_decay),
    ("hash_vs_scan", bench_hash_vs_scan),
    ("drafter", bench_drafter),
    ("sharded_routing", bench_sharded_routing),
    ("persist", bench_persist),
    ("faults", bench_faults),
    ("obs", bench_obs),
)


def main() -> None:
    global SMOKE
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale sizes; same recorders and JSON schema")
    ap.add_argument("--validate", action="store_true",
                    help="only validate existing BENCH_*.json schemas")
    ap.add_argument("--only", default="",
                    help="comma-separated bench-name substrings to run "
                         "(e.g. --only sharded_routing); default all")
    args = ap.parse_args()
    if args.validate:
        sys.exit(1 if validate_bench_files() else 0)
    SMOKE = args.smoke
    picks = [s.strip() for s in args.only.split(",") if s.strip()]
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if not picks or any(p in name for p in picks):
            fn()
    if REC.failed:
        print(f"FAILED rows: {', '.join(REC.failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
