"""Telemetry subsystem tests (DESIGN.md §13): histogram accuracy against
numpy quantiles, lock-free shard merging under thread hammering, flight
recorder ring + incident dump schema, span nesting/exception safety, the
exposition surface (Prometheus text, JSONL, HTTP endpoint), and the engine
integration (consistent stats snapshot, traffic vectors, poison incident).
"""

import errno
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from repro import faults
from repro.core import mcprioq as mc
from repro.core import sharded as sh
from repro.obs import metrics as obs
from repro.obs.export import (MetricsDumper, MetricsServer, render_jsonl,
                              render_prometheus)
from repro.runtime.fault_tolerance import EngineWriteUnavailable, RetryPolicy
from repro.serve.engine import ShardedEngine, ShardedServeConfig

FAST = RetryPolicy(max_attempts=3, base_delay_s=1e-4, max_delay_s=1e-3)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    obs.disarm()
    faults.reset()


def _engine(tmp, *, wal=True, snap=False, **kw):
    scfg = sh.ShardedConfig(base=mc.MCConfig(num_rows=64, capacity=8),
                            num_shards=1, bucket_factor=2.0)
    cfg = ShardedServeConfig(
        sharded=scfg,
        snapshot_dir=os.path.join(tmp, "snap") if snap else None,
        wal_dir=os.path.join(tmp, "wal") if wal else None,
        wal_fsync="always", retry=FAST, **kw)
    return ShardedEngine(cfg)


def _batch(seed=0, n=16, rows=64):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, rows, n).astype(np.int32),
            rng.integers(0, rows, n).astype(np.int32))


# ---------------------------------------------------------------------------
# histogram accuracy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bpo", [1, 4, 16])
def test_histogram_percentiles_track_numpy(bpo):
    """Log-bucket quantile estimates stay within the analytic bound: the
    reported value is the containing bucket's upper edge, so est/true is
    in [1, (B+1)/B] for B buckets per octave (modulo nearest-rank vs
    interpolated-quantile slack on a finite sample)."""
    reg = obs.Registry(buckets_per_octave=bpo)
    rng = np.random.default_rng(0)
    data = rng.lognormal(mean=-6.0, sigma=1.5, size=4000)
    with obs.armed():
        for v in data:
            reg.hist_record("engine.observe", float(v))
    h = reg.snapshot()["histograms"]["engine.observe"]
    assert h["count"] == data.size
    assert h["max"] == pytest.approx(float(data.max()))
    assert h["sum"] == pytest.approx(float(data.sum()), rel=1e-9)
    bound = (bpo + 1.0) / bpo
    for key, q in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
        true = float(np.quantile(data, q))
        assert 0.95 * true <= h[key] <= bound * true * 1.05, (
            f"bpo={bpo} {key}: est {h[key]} vs true {true}")


def test_histogram_extreme_values_clamp_to_edge_buckets():
    reg = obs.Registry()
    with obs.armed():
        reg.hist_record("engine.query", 0.0)
        reg.hist_record("engine.query", -1.0)
        reg.hist_record("engine.query", 1e-30)   # below E_MIN octave
        reg.hist_record("engine.query", 1e9)     # above E_MAX octave
    h = reg.snapshot()["histograms"]["engine.query"]
    assert h["count"] == 4
    assert h["max"] == pytest.approx(1e9)
    # out-of-range samples clamp to the edge octave: the estimate is the
    # top bucket's upper edge (~1024s), while max tracks the exact value
    assert h["p99"] == pytest.approx(1024.0)


# ---------------------------------------------------------------------------
# lock-free shard merge
# ---------------------------------------------------------------------------


def test_concurrent_counter_merge_is_exact():
    """N threads x M increments merge to exactly N*M once writers quiesce
    (each thread owns its shard; nothing is lost to racing increments)."""
    reg = obs.Registry()
    n_threads, m = 8, 5000
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(m):
            reg.counter_add("updates")
    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.snapshot()["counters"]["updates"] == n_threads * m


def test_concurrent_histogram_merge_is_exact():
    reg = obs.Registry()
    n_threads, m = 6, 2000
    with obs.armed():
        def work(seed):
            rng = np.random.default_rng(seed)
            for v in rng.uniform(1e-4, 1e-1, m):
                reg.hist_record("engine.observe", float(v))
        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    h = reg.snapshot()["histograms"]["engine.observe"]
    assert h["count"] == n_threads * m


# ---------------------------------------------------------------------------
# spans + flight recorder
# ---------------------------------------------------------------------------


def test_span_nesting_records_parent():
    reg = obs.Registry()
    with obs.armed():
        with reg.span("engine.observe"):
            with reg.span("engine.apply"):
                pass
    by = {s["name"]: s for s in reg.spans()}
    assert by["engine.apply"]["parent"] == "engine.observe"
    assert by["engine.observe"]["parent"] is None
    snap = reg.snapshot()["histograms"]
    assert snap["engine.observe"]["count"] == 1
    assert snap["engine.apply"]["count"] == 1


def test_span_exception_safety():
    """A raising body still closes the span (recorded with error=True),
    still lands in the histogram, and the exception propagates."""
    reg = obs.Registry()
    with obs.armed():
        with pytest.raises(RuntimeError, match="boom"):
            with reg.span("engine.query"):
                raise RuntimeError("boom")
        with reg.span("engine.topn"):   # stack is clean after the raise
            pass
    by = {s["name"]: s for s in reg.spans()}
    assert by["engine.query"]["error"] is True
    assert by["engine.topn"]["error"] is False
    assert by["engine.topn"]["parent"] is None
    assert reg.snapshot()["histograms"]["engine.query"]["count"] == 1


def test_flight_recorder_ring_wraparound():
    reg = obs.Registry(flight_spans=4)
    with obs.armed():
        for i in range(10):
            with reg.span("engine.query", i=i):
                pass
    spans = reg.spans()
    assert len(spans) == 4
    assert [s["attrs"]["i"] for s in spans] == [6, 7, 8, 9]


def test_disarmed_span_and_hist_are_noops():
    reg = obs.Registry(vectors={"bucket_traffic": 4})
    assert reg.span("engine.query") is obs.NOOP_SPAN
    reg.hist_record("engine.query", 1.0)
    reg.vector_add("bucket_traffic", np.ones(4, np.int64))
    snap = reg.snapshot()
    assert snap["histograms"]["engine.query"]["count"] == 0
    assert sum(snap["vectors"]["bucket_traffic"]) == 0
    assert reg.spans() == []


# ---------------------------------------------------------------------------
# incident dumps
# ---------------------------------------------------------------------------


def test_incident_dump_schema_deltas_and_cap(tmp_path):
    reg = obs.Registry(flight_spans=8, incident_dir=str(tmp_path),
                       max_incidents=2)
    with obs.armed():
        reg.counter_add("updates", 5)
        with reg.span("engine.observe"):
            pass
        path = reg.incident("strike_out", shard=1, error=ValueError("x"))
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == "mcq-incident-v1"
        assert doc["reason"] == "strike_out"
        assert doc["seq"] == 1
        assert doc["ctx"]["shard"] == 1
        assert "ValueError" in doc["ctx"]["error"]
        assert any(s["name"] == "engine.observe" for s in doc["spans"])
        assert doc["deltas"]["updates"] == 5
        # second incident reports only what moved since the first
        reg.counter_add("updates", 3)
        path2 = reg.incident("strike_out")
        with open(path2) as f:
            assert json.load(f)["deltas"]["updates"] == 3
        # past the cap: no file, but the counter still bumps
        assert reg.incident("strike_out") is None
        assert reg.snapshot()["counters"]["incidents"] == 3


def test_incident_without_dir_counts_but_writes_nothing():
    reg = obs.Registry()
    with obs.armed():
        assert reg.incident("poison") is None
    assert reg.snapshot()["counters"]["incidents"] == 1


# ---------------------------------------------------------------------------
# exposition surface
# ---------------------------------------------------------------------------


def _demo_registry():
    reg = obs.Registry(vectors={"bucket_traffic": 4, "shard_traffic": 2})
    with obs.armed():
        reg.counter_add("updates", 2)
        reg.gauge_set("store_version", 7)
        reg.hist_record("engine.observe", 0.01)
        reg.hist_record("engine.query", 0.001)
        reg.vector_add("bucket_traffic", np.array([1, 0, 2, 0]))
        reg.vector_add("shard_traffic", np.array([3, 0]))
    return reg


def test_prometheus_render_series():
    text = render_prometheus(_demo_registry().snapshot())
    assert "# TYPE mcq_updates counter" in text
    assert "mcq_updates 2" in text
    assert "mcq_store_version 7" in text
    assert "# TYPE mcq_engine_observe_seconds summary" in text
    assert 'mcq_engine_observe_seconds{quantile="0.5"}' in text
    assert "mcq_engine_observe_seconds_count 1" in text
    assert 'mcq_bucket_traffic{bucket="2"} 2' in text
    assert 'mcq_shard_traffic{shard="0"} 3' in text


def test_jsonl_render_parses_line_per_metric():
    lines = render_jsonl(_demo_registry().snapshot()).strip().splitlines()
    rows = [json.loads(line) for line in lines]
    by = {(r["type"], r["name"]): r for r in rows}
    assert by[("counter", "updates")]["value"] == 2
    assert by[("histogram", "engine.query")]["count"] == 1
    assert by[("vector", "bucket_traffic")]["nonzero"] == {"0": 1, "2": 2}


def test_metrics_http_endpoint_smoke():
    reg = _demo_registry()
    srv = MetricsServer(reg, port=0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(url + "/metrics").read().decode()
        jbody = urllib.request.urlopen(url + "/metrics.json").read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url + "/nope")
    finally:
        srv.close()
    assert 'mcq_engine_observe_seconds{quantile="0.5"}' in body
    assert 'mcq_engine_query_seconds{quantile="0.99"}' in body
    assert 'mcq_bucket_traffic{bucket="0"} 1' in body
    snap = json.loads(jbody)
    assert snap["counters"]["updates"] == 2


def test_metrics_dumper_writes_final_image(tmp_path):
    reg = _demo_registry()
    path = str(tmp_path / "metrics.jsonl")
    dumper = MetricsDumper(reg, path, every_s=30.0).start()
    dumper.close()   # final image lands even if no cadence tick fired
    rows = [json.loads(line) for line in open(path)]
    assert any(r["name"] == "engine.observe" for r in rows)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def test_engine_telemetry_and_consistent_stats_snapshot(tmp_path):
    with obs.armed():
        eng = _engine(str(tmp_path))
        s, d = _batch()
        eng.observe(s, d)
        eng.query(np.arange(8).astype(np.int32))
        eng.topn()
        snap = eng.metrics.snapshot()
        st = eng.stats_snapshot()
    # one consistent view: host counters + health + device counters
    assert st["updates"] == 1 and st["queries"] == 1
    assert st["shards_down"] == 0
    assert st["n_rows"] > 0
    # spans landed per phase
    hists = snap["histograms"]
    assert hists["engine.observe"]["count"] == 1
    assert hists["engine.apply"]["count"] == 1
    assert hists["engine.query"]["count"] == 1
    assert hists["engine.topn"]["count"] == 1
    assert hists["wal.append"]["count"] == 1
    assert hists["wal.fsync"]["count"] >= 1
    # traffic vectors: every observed item lands in exactly one bucket
    assert sum(snap["vectors"]["bucket_traffic"]) == len(s)
    assert sum(snap["vectors"]["shard_traffic"]) == len(s)
    # gauges + provider merge
    assert snap["gauges"]["store_version"] == eng.store.version
    assert snap["gauges"]["read_epoch_lag"] == 0
    assert snap["provided"]["updates"] == 1


def test_engine_disarmed_still_serves_stats(tmp_path):
    eng = _engine(str(tmp_path), wal=False)
    s, d = _batch()
    eng.observe(s, d)
    st = eng.stats_snapshot()
    assert st["updates"] == 1
    snap = eng.metrics.snapshot()
    assert snap["histograms"]["engine.observe"]["count"] == 0
    assert sum(snap["vectors"]["bucket_traffic"]) == 0


def test_poison_fires_incident_dump(tmp_path):
    inc = str(tmp_path / "inc")
    with obs.armed():
        eng = _engine(str(tmp_path), incident_dir=inc)
        eng.observe(*_batch())
        faults.arm("wal.append.write", OSError(errno.ENOSPC, "disk full"))
        with pytest.raises(EngineWriteUnavailable):
            eng.observe(*_batch(1))
    files = sorted(os.listdir(inc))
    assert files, "poison produced no incident dump"
    with open(os.path.join(inc, files[0])) as f:
        doc = json.load(f)
    assert doc["schema"] == "mcq-incident-v1"
    assert doc["reason"] == "poison"
    assert any(sp["name"] == "engine.observe" for sp in doc["spans"])
    assert doc["deltas"], "incident carries no metric deltas"


# ---------------------------------------------------------------------------
# spans under the profiler (registry disarmed)
# ---------------------------------------------------------------------------

WRITE_CHILDREN = ("engine.route", "engine.apply", "engine.device_wait")
# every span of one call, by name, with its parent's name
CALL_SPANS = {
    "engine.observe": {"engine.route": "engine.observe",
                       "engine.apply": "engine.observe",
                       "engine.dispatch": "engine.apply",
                       "engine.device_wait": "engine.observe"},
    "engine.query": {"engine.dispatch": "engine.query",
                     "engine.query_wait": "engine.query"},
    "engine.topn": {"engine.dispatch": "engine.topn",
                    "engine.topn_wait": "engine.topn"},
}


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """One observe, query and top-n of a small engine inside a profiler
    session with the registry disarmed; returns the engine, the ring's
    records and the trace directory."""
    import jax
    obs.disarm()
    eng = _engine(str(tmp_path_factory.mktemp("eng")), wal=False)
    eng.observe(*_batch())            # compile outside the trace
    eng.query(np.arange(8).astype(np.int32))
    eng.topn()
    assert eng.metrics.spans() == []
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng.observe(*_batch(1))
        eng.query(np.arange(8).astype(np.int32))
        eng.topn()
    finally:
        jax.profiler.stop_trace()
    return eng, eng.metrics.spans(), trace_dir


def _calls(records):
    by = {}
    for r in records:
        by.setdefault(r["call"], []).append(r)
    return by


@pytest.mark.parametrize("top", sorted(CALL_SPANS))
def test_profiled_spans_nest_in_one_call(traced_engine, top):
    _, records, _ = traced_engine
    calls = [c for c in _calls(records).values()
             if any(r["name"] == top for r in c)]
    assert len(calls) == 1
    call = {r["name"]: r for r in calls[0]}
    assert set(call) == {top, *CALL_SPANS[top]}
    assert call[top]["parent"] is None and call[top]["error"] is False
    for child in calls[0]:
        if child is call[top]:
            continue
        parent = call[CALL_SPANS[top][child["name"]]]
        assert child["parent"] == parent["name"]
        assert child["call"] == parent["call"]
        assert child["start_s"] <= child["end_s"]
        assert parent["start_s"] <= child["start_s"]
        assert child["end_s"] <= parent["end_s"]
    # the write cycle launches two programs: update, then maintain
    launches = [r for r in calls[0] if r["name"] == "engine.dispatch"]
    assert len(launches) == (2 if top == "engine.observe" else 1)
    if top == "engine.observe":
        seq = [call[n] for n in WRITE_CHILDREN]
        assert all(a["end_s"] <= b["start_s"] for a, b in zip(seq, seq[1:]))


def test_profiled_spans_are_host_events_of_the_trace(traced_engine):
    import glob
    from jax.profiler import ProfileData
    _, _, trace_dir = traced_engine
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    names = set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    want = {name for top, spans in CALL_SPANS.items()
            for name in (top, *spans)}
    assert want <= names


def test_profiled_spans_leave_histograms_and_stop_with_the_trace(
        traced_engine):
    eng, records, _ = traced_engine
    hists = eng.metrics.snapshot()["histograms"]
    assert hists["engine.observe"]["count"] == 0   # histograms: armed only
    eng.observe(*_batch(2))
    eng.query(np.arange(8).astype(np.int32))
    assert eng.metrics.spans() == records
    assert eng.metrics.span("engine.query") is obs.NOOP_SPAN


def test_incident_dump_writes_only_the_newest_spans(tmp_path):
    from repro.obs import tracing
    reg = obs.Registry(incident_dir=str(tmp_path))
    with obs.armed():
        for i in range(tracing.INCIDENT_SPANS + 10):
            with reg.span("engine.query", i=i):
                pass
        with open(reg.incident("poison")) as f:
            spans = json.load(f)["spans"]
    assert len(reg.spans()) == tracing.INCIDENT_SPANS + 10
    assert [s["attrs"]["i"] for s in spans] == list(
        range(10, tracing.INCIDENT_SPANS + 10))


def test_telemetry_loads_no_jax_and_spans_record_without_it():
    """``repro.obs`` stays free of JAX: with JAX never loaded no profiler
    can record, and an armed span records without its annotation."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "from repro import obs\n"
            "from repro.obs.metrics import NOOP_SPAN\n"
            "reg = obs.Registry(flight_spans=2)\n"
            "assert not obs.tracing.profiling()\n"
            "assert reg.span('wal.append') is NOOP_SPAN\n"
            "with obs.armed():\n"
            "    for _ in range(3):\n"
            "        with reg.span('wal.append'):\n"
            "            pass\n"
            "assert [r['call'] for r in reg.spans()] == [1, 2]\n"
            "assert reg.spans_full()\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
