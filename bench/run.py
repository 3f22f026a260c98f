#!/usr/bin/env python3
"""Chip benchmark of the sharded chain server (``ShardedEngine``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``, read by ``generator.py``) and one reader per
metric (``bench/metrics/<metric>.py``).  The run builds the warm state on
the device from the seed, warms every shape the window uses, measures for
``--seconds``, compares what the window produced with the plain reference
(``compare.py``) and prints one JSON line last on standard output.  With
``--trace 1`` the window runs under the profiler and the line holds the
cell's per-layer metrics; otherwise its end-to-end metrics.

It refuses to run (exit 2, no result) without a TPU or with fewer chips
than the cell needs.  ``--rehearse`` lifts that for a CPU rehearsal at the
sizes given by ``--scale-rows``; ``--control`` also compares the control
(the reference one precision step down) and prints its numbers;
``--sweep r1,r2,..`` measures windows at those rates of the mix's paced
loop after one set-up, without a comparison.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
SETUP = {}                  # set-up phase -> seconds since process start
COMPILES = {}               # compile and cache events during set-up
STATE_COUNTERS = ("dropped_rows", "dropped_probes", "deferred_new",
                  "route_dropped", "route_lost", "write_errors")
READ_COUNTERS = ("degraded_answers",)


def mark(phase: str):
    SETUP[phase] = time.perf_counter() - T_START


def count_compiles(tally: dict):
    """Tally JAX's compile and persistent-cache events into ``tally``: a
    run whose programs all come from the cache shows misses 0."""
    from jax._src import monitoring

    def event(name, **kw):
        if name.startswith("/jax/compilation_cache/cache_"):
            key = name.rsplit("/", 1)[1]
            tally[key] = tally.get(key, 0) + 1

    def duration(name, secs, **kw):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            key = name.rsplit("/", 1)[1] + "_s"
            tally[key] = tally.get(key, 0.0) + secs

    monitoring.register_event_listener(event)
    monitoring.register_event_duration_secs_listener(duration)


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, scale_rows: int = 0):
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if scale_rows:
        cfg["mc"]["num_rows"] = scale_rows
    return spec, cell, cfg, mix


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``metrics/<name>.py``; a metric ``<base>.<cells>`` that reports the
    same quantity for other cells without a file of its own is read by
    ``metrics/<base>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return load_module(path).read


def hbm_peak(kind: str) -> float:
    """HBM bytes/s of one chip from ``peaks.json``; a device that is not in
    the table is an error."""
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return float(peaks[kind]["hbm_bytes_per_s"])


def pct(values, q: float):
    import numpy as np
    v = np.asarray(values, np.float64)
    v = v[~np.isnan(v)]
    return float(np.percentile(v, q)) if v.size else None


# ---------------------------------------------------------------------------


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale-rows", type=int, default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = load_cell(args.workload, args.scale_rows)
    if not (ROOT / "src" / "repro").is_dir():
        fail("the system under test (src/repro) is not beside the benchmark")
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    return args, spec, cell, cfg, mix


def main(argv=None) -> int:
    args, spec, cell, cfg, mix = parse(argv)
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # The TPU runtime pins a host staging buffer as it starts; at its
    # default size that took 6-10 s on a v5e host and swung by seconds from
    # run to run, at 256 MiB about 1.2 s.  The window moves a few KiB a call.
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
    import jax
    mark("import_jax")
    devices = jax.devices()
    mark("devices")
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        fail(f"no TPU: JAX found {dev.platform}; this benchmark runs only on "
             f"the chip")
    if len(devices) < cell["chips"]:
        fail(f"{cell['name']} needs {cell['chips']} chips, JAX found "
             f"{len(devices)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    count_compiles(COMPILES)
    return Run(args, spec, cell, cfg, mix, devices).go()


class Run:
    def __init__(self, args, spec, cell, cfg, mix, devices):
        self.args, self.spec, self.cell = args, spec, cell
        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.shards = cfg["serve"]["num_shards"]
        self.used = devices[:max(self.shards, cell["chips"])]

    # ------------------------------------------------------------------
    def build(self):
        import jax
        import numpy as np
        from repro import compat
        from repro.core import mcprioq as mc
        from repro.core import sharded as sh
        from repro.core.hashtable import HashTable
        from repro.core.slab import Slabs
        from repro.serve.engine import ShardedEngine, ShardedServeConfig
        import warm

        cfg, sv = self.cfg, self.cfg["serve"]
        base = mc.MCConfig(**cfg["mc"])
        scfg = sh.ShardedConfig(base=base, num_shards=self.shards,
                                bucket_factor=sv["bucket_factor"])
        self.mesh = compat.make_mesh((self.shards,), ("shard",),
                                     devices=self.devices[:self.shards])
        self.engine = ShardedEngine(ShardedServeConfig(
            sharded=scfg, decay_threshold=sv["decay_threshold"],
            threshold=sv["threshold"], max_items=sv["max_items"],
            topn=sv["topn"]), mesh=self.mesh)
        mark("engine")
        self.builder = warm.make_builder(cfg, self.mesh,
                                         (mc.MCState, HashTable, Slabs),
                                         compat.shard_map)
        state, row_ids, counts, winners = self.builder(self.args.seed)
        need = warm.sizes(cfg)["h"]
        got = np.asarray(winners)
        if (got < need).any():
            fail(f"warm state: only {got.tolist()} candidate ids per shard "
                 f"for {need} held rows", 1)
        del counts
        self.row_ids = np.asarray(row_ids)
        self.engine.store.publish(state)
        del state
        jnp = jax.numpy
        self.live_fn = jax.jit(lambda c, ev: (
            jnp.sum((c > 0).astype(jnp.int32)), jnp.sum(ev)))

    def live(self):
        snap = self.engine.store.acquire()
        try:
            live, ev = self.live_fn(snap.state.slabs.cnt,
                                    snap.state.evictions)
            return int(live), int(ev)
        finally:
            self.engine.store.release(snap)

    def warm_up(self):
        """Compile (or load from the cache) every program the window runs:
        the update/maintain/counter programs at the batch size, the query
        program at the read width and threshold, top-n when the mix reads
        it, and the live-slot count."""
        import numpy as np
        b = int(self.cfg["batch"])
        pad, zeros = np.full(b, -1, np.int32), np.zeros(b, np.int32)
        self.engine.observe(pad, zeros)
        mark("warm_observe")
        self.pre_observes = [(self.engine.store.version, pad, zeros)]
        rd = self.mix["reads"]
        self.engine.query(np.full(rd["query_width"], -1, np.int32),
                          threshold=float(self.cfg["serve"]["threshold"]))
        if rd.get("topn_every", 0):
            self.engine.topn(int(self.cfg["serve"]["topn"]))
        self.live()

    # ------------------------------------------------------------------
    def go(self) -> int:
        import numpy as np
        from jax._src import monitoring
        from generator import Traffic

        args = self.args
        self.build()
        mark("build")
        self.warm_up()
        mark("warm_up")
        traffic = Traffic(self.mix, self.cfg, self.row_ids, args.seed,
                          args.seconds)
        if args.sweep:
            return self.sweep(traffic)
        reads = traffic.reads()
        opened = (None if self.mix["ingest"]["loop"] == "closed"
                  else traffic.open_events())
        compiles = []
        monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **kw: compiles.append(ev)
            if ev == "/jax/core/compile/jaxpr_to_mlir_module_duration"
            else None)
        before = self.engine.stats_snapshot()
        live0, ev0 = self.live()
        trace_dir = None
        annotate = None
        if args.trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            annotate = jax.profiler.TraceAnnotation
        from window import Window
        win = Window(self.engine, self.cfg, self.mix, traffic, reads,
                     args.seconds, open_events=opened, annotate=annotate)
        n_compiles0 = len(compiles)
        setup_s = time.perf_counter() - T_START
        setup_compiles = dict(COMPILES)
        if trace_dir:
            import jax
            jax.profiler.start_trace(trace_dir)
        win.run(int(self.mix["reads"]["threads"]))
        if trace_dir:
            jax.profiler.stop_trace()
        window_compiles = len(compiles) - n_compiles0
        live1, ev1 = self.live()
        after = self.engine.stats_snapshot()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.used)

        ctx = types.SimpleNamespace(
            cfg=self.cfg, mix=self.mix, cell=self.cell, win=win,
            setup_s=setup_s, reads=reads, trace=None,
            live_delta=live1 - live0, evictions_delta=ev1 - ev0,
            roofline=lambda kernel: load_module(
                BENCH / "roofline" / f"{kernel}.py"),
            hbm_bytes_per_s=lambda: hbm_peak(self.devices[0].device_kind))
        if trace_dir:
            import trace_reduce
            ctx.trace = trace_reduce.reduce_dir(
                trace_dir, window_s=win.t_reads_end - win.t0)
            shutil.rmtree(trace_dir, ignore_errors=True)

        failed = sum(after.get(k, 0) - before.get(k, 0)
                     for k in STATE_COUNTERS + READ_COUNTERS)
        failed += win.failed_events + int(win.read_fail.sum())
        attempted = win.events + int(reads.due.size)

        metrics = {}
        kind = "per_layer" if args.trace else "end_to_end"
        for m in self.spec[kind]:
            if not reports(m, self.cell["name"]):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if not args.trace:
            metrics.pop("setup_s", None)
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}

        checks, extra = self.check(win)
        import jax
        dev = self.devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(self.devices), "memory_peak_bytes": int(peak)}
        result = {"correct": all(c["value"] <= c["limit"]
                                 for c in checks.values()),
                  "attempted": int(attempted), "failed": int(failed),
                  "metrics": metrics, "device": device}
        if ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            result["breakdown"] = ctx.trace["breakdown"]
            print("programs " + json.dumps(ctx.trace["programs"]))
            print("kernels " + json.dumps(ctx.trace["kernels"]))
        info = {"setup": {**{k: round(v, 3) for k, v in SETUP.items()},
                          "cache": setup_compiles},
                "window_compiles": window_compiles, "events": win.events,
                "observes": len(win.obs_calls), "reads": int(reads.due.size),
                "observe_ms": [round((te - ts) * 1e3, 1)
                               for ts, te in win.obs_calls],
                "read_lat_max_ms": (pct(win.read_lat, 100) or 0) * 1e3,
                "gc": [len(win.gc_pauses), round(sum(win.gc_pauses), 4),
                       round(max(win.gc_pauses, default=0.0), 4)],
                "errors": len(win.errors), **extra,
                "stats_delta": {k: after.get(k, 0) - before.get(k, 0)
                                for k in after
                                if isinstance(after.get(k), int)
                                and after.get(k) != before.get(k)}}
        print("info " + json.dumps(info))
        for e in win.errors[:3]:
            print(e, file=sys.stderr)
        result["checks"] = checks
        for name, c in checks.items():
            print(f"check {name} {c['value']} limit {c['limit']}",
                  file=sys.stderr)
        print(json.dumps(result))
        return 0

    # ------------------------------------------------------------------
    def final_state(self):
        import numpy as np
        from compare import ProgramState
        snap = self.engine.store.acquire()
        try:
            st = snap.state
            out = ProgramState(
                cnt=np.asarray(st.slabs.cnt), dst=np.asarray(st.slabs.dst),
                order=np.asarray(st.slabs.order),
                tot=np.asarray(st.slabs.tot),
                tab_keys=np.asarray(st.src_table.keys),
                tab_vals=np.asarray(st.src_table.vals),
                n_rows=np.asarray(st.n_rows),
                evictions=int(np.asarray(st.evictions).sum()))
        finally:
            self.engine.store.release(snap)
        return out

    def check(self, win):
        """Free the program's state, rebuild the warm data for the
        reference, and compare (the control too with ``--control``)."""
        import numpy as np
        import compare

        state = self.final_state()
        self.engine.close()
        self.engine = None
        win.engine = None
        gc.collect()
        built = self.builder(self.args.seed)
        warm_ids, warm_counts = np.asarray(built[1]), np.asarray(built[2])
        del built
        gc.collect()
        limits = load_json(BENCH / "limits.json")
        reads = [win.checked[i] for i in sorted(win.checked)]
        t = time.perf_counter()
        observes = self.pre_observes + win.observes
        got = compare.compare(self.cfg, warm_ids, warm_counts, observes,
                              reads, state)
        extra = {"reference_s": time.perf_counter() - t,
                 "reads_checked": len(reads),
                 "max_row_total": got.pop("max_row_total"),
                 "ref_decay_steps": got.pop("ref_decay_steps"),
                 "ref_evictions": got.pop("ref_evictions")}
        if self.args.control:
            ctl = compare.compare(self.cfg, warm_ids, warm_counts, observes,
                                  reads, state, control=True)
            extra["control"] = {k: ctl[k] for k in limits}
            for k in limits:
                print(f"control {k} {ctl[k]} limit {limits[k]}",
                      file=sys.stderr)
        return {k: {"value": got[k], "limit": limits[k]} for k in limits}, \
            extra

    # ------------------------------------------------------------------
    def sweep(self, traffic) -> int:
        """Windows at each rate of ``--sweep`` (the paced loop: the ingest
        for an open ingester without reads of its own, else the reads).
        ``drain_s`` is how long the ingester ran past the window: a rate is
        sustained while it stays within two observe calls."""
        import numpy as np
        from window import Window
        ing = self.mix["ingest"]
        paced_reads = ing["loop"] == "closed" or "events_per_read" in ing
        for rate in (float(r) for r in self.args.sweep.split(",")):
            mix = json.loads(json.dumps(self.mix))
            if paced_reads:
                mix["reads"]["rate_per_s"] = rate
            else:
                mix["ingest"]["rate_per_s"] = rate
            traffic.mix = mix
            reads = traffic.reads()
            opened = (None if ing["loop"] == "closed"
                      else traffic.open_events())
            win = Window(self.engine, self.cfg, mix, traffic, reads,
                         self.args.seconds, open_events=opened).run(
                int(mix["reads"]["threads"]))
            lag = win.lags if win.lags is not None else np.zeros(1)
            q = reads.is_topn
            row = {"rate": rate, "reads_per_s": traffic.read_rate(),
                   "events": win.events, "observes": len(win.obs_calls),
                   "observe_p50_ms": pct([te - ts for ts, te in
                                          win.obs_calls], 50) * 1e3,
                   "drain_s": win.t_end - win.t0 - self.args.seconds,
                   "learn_lag_p50_ms": pct(lag, 50) * 1e3,
                   "learn_lag_p95_ms": pct(lag, 95) * 1e3,
                   "query_p95_ms": (pct(win.read_lat[~q], 95) or 0) * 1e3,
                   "topn_p95_ms": (pct(win.read_lat[q], 95) or 0) * 1e3,
                   "errors": len(win.errors)}
            print("sweep " + json.dumps(row), flush=True)
        return 0


if __name__ == "__main__":
    sys.exit(main())
