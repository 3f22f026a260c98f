"""Fault-matrix tests (DESIGN.md §12): every registered failpoint is
injected by at least one test asserting its retry / escalation /
degraded-mode contract, with the correct counters.

``FAULT_MATRIX`` below is the normative site -> injection-test table:
mcqlint rule MCQ-R001 statically requires every ``failpoint("name")``
call site in src/ to be named by this file, and
:func:`test_fault_matrix_is_total` closes the loop at runtime — the
table's keys must equal ``FAILPOINT_CATALOG`` and every named test must
exist here.  Engines run with ``num_shards=1`` (identity all_to_all —
the full routing machinery, single device); multi-shard degradation runs
under a device-count skipif, exercised by the CI multi-device matrix.
"""

import errno
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import faults
from repro.checkpoint import ckpt
from repro.core import mcprioq as mc
from repro.core import sharded as sh
from repro.persist import snapshot as snapshot_io
from repro.persist.wal import SegmentRotationError, WriteAheadLog
from repro.runtime.fault_tolerance import (EngineWriteUnavailable,
                                           FaultEscalated,
                                           RetryBudgetExceeded, RetryPolicy,
                                           ShardDispatchError, ShardHealth,
                                           call_with_retry,
                                           classify_io_error,
                                           shard_from_exception)
from repro.serve.engine import (Engine, ServeConfig, ShardedEngine,
                                ShardedServeConfig)

#: tight backoff so escalation tests finish in milliseconds
FAST = RetryPolicy(max_attempts=3, base_delay_s=1e-4, max_delay_s=1e-3)

#: the fault-matrix table: every FAILPOINT_CATALOG site -> the test that
#: injects it (MCQ-R001 checks src-side sites against this file's text;
#: test_fault_matrix_is_total checks the table itself is closed)
FAULT_MATRIX = {
    "wal.segment_open": "test_wal_segment_open_transient_is_retried",
    "wal.append.write": "test_wal_append_enospc_poisons_write_path",
    "wal.append.fsync": "test_wal_fsync_failure_truncates_then_same_seq",
    "wal.rotate": "test_wal_rotate_failure_policy_dependent",
    "snapshot.meta_write": "test_checkpoint_fault_is_exception_safe",
    "snapshot.arrays_write": "test_checkpoint_fault_is_exception_safe",
    "snapshot.manifest_commit": "test_checkpoint_fault_is_exception_safe",
    "snapshot.io_thread": "test_async_snapshot_worker_death_is_counted",
    "snapshot.restore_read": "test_restore_read_fault_raises_cleanly",
    "engine.apply": "test_apply_exhaustion_poisons_and_restore_heals",
    "engine.publish": "test_publish_transient_fault_retries_transparently",
    "engine.query_dispatch": "test_query_dispatch_fault_degrades_not_raises",
    "engine.topn_dispatch": "test_topn_dispatch_fault_degrades_not_raises",
    "engine.learn": "test_engine_learn_failpoint_cuts_before_publish",
}


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.reset()
    faults.set_observer(None)
    yield
    faults.reset()
    faults.set_observer(None)


def _engine(tmp, *, wal=True, snap=True, shards=1, factor=2.0,
            fsync="always", **kw):
    scfg = sh.ShardedConfig(base=mc.MCConfig(num_rows=64, capacity=8),
                            num_shards=shards, bucket_factor=factor)
    cfg = ShardedServeConfig(
        sharded=scfg,
        snapshot_dir=os.path.join(tmp, "snap") if snap else None,
        wal_dir=os.path.join(tmp, "wal") if wal else None,
        wal_fsync=fsync, retry=FAST, **kw)
    return ShardedEngine(cfg)


def _batch(seed=0, n=16, rows=64):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, rows, n).astype(np.int32),
            rng.integers(0, rows, n).astype(np.int32))


def _query_state(eng, rows=16):
    d, p, n = eng.query(np.arange(rows))
    return np.asarray(d), np.asarray(p), np.asarray(n)


# ---------------------------------------------------------------------------
# the table is total
# ---------------------------------------------------------------------------


def test_fault_matrix_is_total():
    """Every catalog site appears in the matrix and every named test
    exists — a new failpoint cannot land without a fault-matrix entry."""
    assert set(FAULT_MATRIX) == set(faults.FAILPOINT_CATALOG)
    for site, test_name in FAULT_MATRIX.items():
        fn = globals().get(test_name)
        assert callable(fn), f"{site}: matrix names missing test {test_name}"


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_rejects_unknown_sites():
    with pytest.raises(KeyError):
        faults.arm("not.a.site", OSError())


def test_registry_triggers_nth_every_prob_count():
    log = []
    faults.arm("engine.apply", lambda ctx: log.append("nth"),
               trigger=("nth", 2))
    for _ in range(4):
        faults.failpoint("engine.apply")
    assert log == ["nth"]                      # exactly the 2nd hit
    faults.reset()

    faults.arm("engine.apply", lambda ctx: log.append("every"),
               trigger=("every", 2))
    for _ in range(6):
        faults.failpoint("engine.apply")
    assert log.count("every") == 3             # hits 2, 4, 6
    faults.reset()

    faults.arm("engine.apply", lambda ctx: log.append("cap"), count=2)
    for _ in range(5):
        faults.failpoint("engine.apply")
    assert log.count("cap") == 2               # count cap holds
    assert faults.fired("engine.apply") == 2
    assert faults.hits("engine.apply") == 5    # hits keep counting
    faults.reset()

    # prob trigger is deterministic from its seed
    def fires(seed):
        faults.reset()
        got = []
        faults.arm("engine.apply", lambda ctx: got.append(1),
                   trigger=("prob", 0.5, seed))
        for _ in range(32):
            faults.failpoint("engine.apply")
        return len(got)

    assert fires(7) == fires(7)
    assert 0 < fires(7) < 32


def test_registry_zero_cost_when_disarmed():
    """Disarmed, the site is one bool read: no hits recorded at all."""
    faults.failpoint("engine.apply")
    assert faults.hits("engine.apply") == 0
    assert faults.snapshot() == {}


def test_registry_observer_sees_every_hit_before_actions():
    seen = []
    faults.set_observer(lambda name, ctx: seen.append((name, dict(ctx))))
    faults.failpoint("engine.apply", items=3)
    faults.arm("engine.apply", faults.FaultInjected("engine.apply"))
    with pytest.raises(faults.FaultInjected):
        faults.failpoint("engine.apply", items=4)
    assert [s[0] for s in seen] == ["engine.apply", "engine.apply"]
    assert seen[1][1] == {"items": 4}          # observer ran before raise


def test_registry_env_arming_round_trip():
    n = faults.arm_from_env(
        "wal.append.fsync=raise:28@nth:2;engine.apply=sleep:0")
    assert n == 2
    faults.failpoint("wal.append.fsync")       # 1st hit: no fire
    with pytest.raises(faults.FaultInjected) as ei:
        faults.failpoint("wal.append.fsync")   # 2nd hit: fires
    assert ei.value.errno == errno.ENOSPC
    faults.failpoint("engine.apply")           # sleep:0 action runs
    with pytest.raises(ValueError):
        faults.arm_from_env("wal.rotate=explode")
    with pytest.raises(ValueError):
        faults.arm_from_env("wal.rotate")      # missing action


# ---------------------------------------------------------------------------
# retry ladder + health map units
# ---------------------------------------------------------------------------


def test_retry_ladder_classification_and_budget():
    assert classify_io_error(OSError(errno.ENOSPC, "")) == "persistent"
    assert classify_io_error(OSError(errno.EIO, "")) == "transient"
    assert classify_io_error(RuntimeError()) == "transient"

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(errno.EIO, "flake")
        return "ok"

    assert call_with_retry(flaky, policy=FAST, sleep=lambda s: None) == "ok"
    assert len(calls) == 3

    # persistent: no second attempt, escalated with the fault as its cause
    calls.clear()

    def full():
        calls.append(1)
        raise OSError(errno.ENOSPC, "disk full")

    with pytest.raises(FaultEscalated) as ei:
        call_with_retry(full, policy=FAST, sleep=lambda s: None)
    assert len(calls) == 1
    assert not isinstance(ei.value, RetryBudgetExceeded)
    assert ei.value.__cause__.errno == errno.ENOSPC

    # exhausted: RetryBudgetExceeded chains the last fault
    calls.clear()

    def always():
        calls.append(1)
        raise OSError(errno.EIO, "still broken")

    with pytest.raises(RetryBudgetExceeded) as ei:
        call_with_retry(always, policy=FAST, sleep=lambda s: None)
    assert len(calls) == FAST.max_attempts
    assert isinstance(ei.value.__cause__, OSError)

    # delays: capped exponential, deterministic per seed
    a = list(RetryPolicy(max_attempts=5, seed=3).delays())
    b = list(RetryPolicy(max_attempts=5, seed=3).delays())
    assert a == b and len(a) == 4
    assert all(d <= RetryPolicy.max_delay_s for d in a)


def test_shard_health_strikes_defer_and_heal():
    h = ShardHealth(4, strike_limit=2, deferred_cap=8)
    assert not h.record_failure(1)
    assert h.record_failure(1)                 # 2nd strike: down
    assert h.down == frozenset({1}) and h.degraded
    assert list(h.healthy_mask()) == [True, False, True, True]
    h.record_failure(2)
    h.record_success(2)                        # success clears strikes
    assert not h.record_failure(2)

    src = np.arange(5, dtype=np.int32)
    assert h.defer(1, src, src, src)
    assert not h.defer(1, src, src, src)       # 10 > cap of 8: dropped
    assert h.stats() == {"shards_down": 1, "deferred_writes": 5}
    batches = h.heal(1)
    assert len(batches) == 1 and batches[0][0].size == 5
    assert h.stats() == {"shards_down": 0, "deferred_writes": 0}


def test_shard_health_dump_load_requeue_round_trip():
    """The health map is recovery state (A15): dump() -> JSON -> load()
    must reproduce the down-set and the deferred queue in order, and
    requeue() must put a failed heal's remainder back at the FRONT,
    cap-exempt."""
    import json

    h = ShardHealth(4, deferred_cap=64)
    h.mark_down(1)
    h.mark_down(3)
    a = np.arange(3, dtype=np.int32)
    assert h.defer(1, a, a + 1, None)
    assert h.defer(1, a + 10, a + 11, a * 0 + 2)
    assert h.defer(3, a, a, a)
    image = json.loads(json.dumps(h.dump()))   # must survive JSON

    h2 = ShardHealth(4, deferred_cap=64)
    h2.load(image)
    assert h2.down == frozenset({1, 3})
    assert h2.stats() == {"shards_down": 2, "deferred_writes": 9}
    b1 = h2.heal(1)
    assert len(b1) == 2
    np.testing.assert_array_equal(b1[0][0], a)       # arrival order kept
    assert b1[0][2] is None                          # None w round-trips
    np.testing.assert_array_equal(b1[1][2], a * 0 + 2)

    h2.requeue(1, b1[1:])                      # un-applied remainder back
    assert h2.stats()["deferred_writes"] == 6
    again = h2.heal(1)
    assert len(again) == 1
    np.testing.assert_array_equal(again[0][0], a + 10)


# ---------------------------------------------------------------------------
# WAL fsync-failure modes (satellite: replay stops at last durable record)
# ---------------------------------------------------------------------------


def test_wal_fsync_failure_truncates_then_same_seq(tmp_path):
    """fsync (policy=always) raising EIO: the record is scrubbed, the
    retry lands the SAME seq, and replay sees each batch exactly once."""
    wal = WriteAheadLog(str(tmp_path), fsync="always")
    wal.append([1], [2])
    faults.arm("wal.append.fsync", OSError(errno.EIO, "flake"), count=1)
    with pytest.raises(OSError):
        wal.append([3], [4])
    assert wal.append([3], [4]) == 1           # same seq after scrub
    recs = list(wal.replay())
    assert [r[0] for r in recs] == [0, 1]
    assert [int(r[1][0]) for r in recs] == [1, 3]
    wal.close()


def test_wal_append_torn_write_replay_stops_at_durable(tmp_path):
    """A write that lands partial bytes then dies (torn append): replay
    must stop at the last durable record, never crash, and the resumed
    writer continues through the tear."""
    wal = WriteAheadLog(str(tmp_path), fsync="never")
    wal.append([1], [1])

    def tear(ctx):
        ctx["fh"].write(ctx["record"][: len(ctx["record"]) // 2])
        raise OSError(errno.EIO, "died mid-write")

    faults.arm("wal.append.write", tear, count=1)
    with pytest.raises(OSError):
        wal.append([2], [2])
    # fresh handle on the same directory: sees only the durable prefix
    ro = WriteAheadLog(str(tmp_path), fsync="never")
    assert [r[0] for r in ro.replay()] == [0]
    assert ro.next_seq == 1                    # resumes at the torn seq
    ro.append([2], [2])
    assert [r[0] for r in ro.replay()] == [0, 1]
    ro.close()
    wal.close()


def test_wal_append_enospc_abandons_segment_and_recovers(tmp_path):
    """ENOSPC mid-append with the truncate also failing: the segment is
    abandoned; the next append opens a fresh segment at the same seq and
    replay stays contiguous across the two files."""
    wal = WriteAheadLog(str(tmp_path), fsync="never")
    wal.append([1], [1])

    def nospace(ctx):
        ctx["fh"].close()                      # truncate(start) now fails
        raise OSError(errno.ENOSPC, "disk full")

    faults.arm("wal.append.write", nospace, count=1)
    with pytest.raises(OSError):
        wal.append([2], [2])
    assert wal.append([2], [2]) == 1
    segs = sorted(p for p in os.listdir(tmp_path) if p.endswith(".seg"))
    assert len(segs) == 2                      # fresh segment, same seq
    assert [r[0] for r in wal.replay()] == [0, 1]
    wal.close()


def test_wal_rotate_failure_policy_dependent(tmp_path):
    """Rotation failing after an acknowledged append: under 'always' every
    record is already individually durable, so the failure is swallowed
    and counted (raising would make the engine retry an applied batch
    under a new seq); under 'rotate' the rotation fsync IS the segment's
    durability point, so it escalates unretryably instead of silently
    acknowledging a segment that may vanish on power loss."""
    wal = WriteAheadLog(str(tmp_path / "a"), segment_records=1,
                        fsync="always")
    faults.arm("wal.rotate", OSError(errno.EIO, "close failed"), count=1)
    assert wal.append([1], [1]) == 0           # no raise
    assert wal.io_errors == 1
    assert wal.append([2], [2]) == 1
    assert [r[0] for r in wal.replay()] == [0, 1]
    wal.close()
    faults.reset()

    wal = WriteAheadLog(str(tmp_path / "r"), segment_records=1,
                        fsync="rotate")
    faults.arm("wal.rotate", OSError(errno.EIO, "fsync failed"), count=1)
    with pytest.raises(SegmentRotationError) as ei:
        wal.append([1], [1])
    # no retry: the ladder must escalate, never re-log under a new seq
    assert classify_io_error(ei.value) == "persistent"
    assert wal.io_errors == 1
    # the in-cache record is still readable and the seq chain continues
    assert wal.append([2], [2]) == 1
    assert [r[0] for r in wal.replay()] == [0, 1]
    wal.close()


def test_wal_rotate_escalation_poisons_engine_under_rotate_policy(tmp_path):
    """Engine end to end under policy 'rotate': a failed rotation poisons
    the write path (the batch is NOT applied past an uncertain durability
    point) and restore() re-aligns state with what actually survived."""
    src0, dst0 = _batch(0)
    eng = _engine(str(tmp_path), fsync="rotate")
    eng.wal.segment_records = 1
    faults.arm("wal.rotate", OSError(errno.EIO, "fsync failed"), count=1)
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(src0, dst0)
    faults.reset()
    assert not eng.write_available
    assert eng._seq == -1                      # never advanced
    assert eng.stats["updates"] == 0           # nothing applied
    for t in list(eng._io_threads):            # poison checkpoint-now
        t.join()
    eng.restore()                              # replays the durable record
    assert eng.write_available and eng._seq == 0
    healed = _query_state(eng)
    eng.close()

    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(src0, dst0)
    for a, b in zip(healed, _query_state(oracle)):
        np.testing.assert_array_equal(a, b)
    oracle.close()


def test_wal_segment_open_transient_is_retried(tmp_path):
    """segment_open raising is surfaced to the appender (nothing durable,
    nothing applied) and a bare retry succeeds — the caller's ladder owns
    the backoff."""
    wal = WriteAheadLog(str(tmp_path), fsync="never")
    faults.arm("wal.segment_open", OSError(errno.EIO, "transient"),
               count=1)
    with pytest.raises(OSError):
        wal.append([1], [1])
    assert wal.append([1], [1]) == 0
    assert [r[0] for r in wal.replay()] == [0]
    wal.close()


# ---------------------------------------------------------------------------
# engine write-path escalation (satellite: exception safety)
# ---------------------------------------------------------------------------


def test_wal_append_enospc_poisons_write_path(tmp_path):
    """Persistent WAL fault mid-observe: the writer lock is released, no
    half-applied epoch is published — query answers and counter_stats are
    bit-identical to the pre-step state — and writes raise
    EngineWriteUnavailable until restore() heals."""
    eng = _engine(str(tmp_path))
    src, dst = _batch(0)
    eng.observe(src, dst)
    before_q = _query_state(eng)
    before_stats = dict(eng.stats)

    faults.arm("wal.append.write", OSError(errno.ENOSPC, "disk full"))
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(*_batch(1))
    faults.reset()

    assert not eng.write_available
    assert eng._seq == 0                       # never advanced
    after_q = _query_state(eng)
    for a, b in zip(before_q, after_q):
        np.testing.assert_array_equal(a, b)
    for key, val in before_stats.items():
        if key in ("queries",):                # reads above are counted
            continue
        if key == "write_errors":
            assert eng.stats[key] == val + 1
        elif key == "snapshots":
            # poison took a best-effort checkpoint-now
            assert eng.stats[key] >= val
        else:
            assert eng.stats[key] == val, key
    # writer lock was released: further writes fail-fast, reads serve
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(*_batch(2))
    _query_state(eng)

    eng.restore()
    assert eng.write_available
    eng.observe(*_batch(3))                    # writes re-open
    eng.close()


def test_restore_drains_inflight_poison_checkpoint(tmp_path):
    """The poison path's best-effort checkpoint-now commits on a worker
    thread; an immediate restore() must join it rather than scan the
    snapshot directory past a still-committing step."""
    eng = _engine(str(tmp_path))
    eng.observe(*_batch(0))
    faults.arm("wal.append.write", OSError(errno.ENOSPC, "disk full"))
    faults.arm("snapshot.io_thread", 0.3)      # slow the worker's commit
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(*_batch(1))
    faults.reset()                             # worker already mid-sleep
    eng.restore()                              # must join, not FileNotFound
    assert eng.write_available
    eng.observe(*_batch(2))
    eng.close()


def test_wal_transient_fault_is_retried_with_counters(tmp_path):
    """One EIO flake on the append write: the ladder absorbs it — same
    seq, batch applied once, wal_retries counts the backoff round."""
    eng = _engine(str(tmp_path))
    faults.arm("wal.append.write", OSError(errno.EIO, "flake"), count=1)
    eng.observe(*_batch(0))
    assert eng.stats["wal_retries"] == 1
    assert eng.stats["updates"] == 1 and eng._seq == 0
    assert eng.write_available
    eng.close()


def test_apply_exhaustion_poisons_and_restore_heals(tmp_path):
    """Apply faulting past the retry budget AFTER a durable append: the
    record is a ghost (durable, unapplied) — the write path poisons, and
    restore() replays the ghost so the final state equals an engine that
    never faulted."""
    src0, dst0 = _batch(0)
    src1, dst1 = _batch(1)

    eng = _engine(str(tmp_path))
    eng.observe(src0, dst0)
    eng.checkpoint()
    faults.arm("engine.apply", RuntimeError("device lost"))
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(src1, dst1)
    faults.reset()
    assert not eng.write_available
    assert eng.stats["apply_retries"] == FAST.max_attempts - 1
    assert eng._seq == 0 and eng.wal.last_seq == 1  # the ghost record

    result = eng.restore()
    assert result["replayed"] >= 1 and eng._seq == 1
    healed_q = _query_state(eng)
    eng.close()

    # oracle: the same two batches with no fault anywhere
    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(src0, dst0)
    oracle.observe(src1, dst1)
    oracle_q = _query_state(oracle)
    oracle.close()
    for a, b in zip(healed_q, oracle_q):
        np.testing.assert_array_equal(a, b)


def test_apply_fault_without_wal_raises_and_leaves_state(tmp_path):
    """No WAL: an exhausted apply re-raises (nothing is durable, nothing
    forked) and the state is exactly the pre-step state."""
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    before = _query_state(eng)
    faults.arm("engine.apply", RuntimeError("device lost"))
    with pytest.raises(RetryBudgetExceeded):
        eng.observe(*_batch(1))
    faults.reset()
    assert eng.write_available                 # no fork: not poisoned
    for a, b in zip(before, _query_state(eng)):
        np.testing.assert_array_equal(a, b)
    eng.observe(*_batch(1))                    # plain retry by the caller
    eng.close()


def test_publish_transient_fault_retries_transparently(tmp_path):
    """engine.publish cuts before the epoch swap: a one-shot fault there
    is retried by the ladder and the batch lands exactly once (the
    host-side plan is only committed after publish succeeds)."""
    eng = _engine(str(tmp_path))
    faults.arm("engine.publish", RuntimeError("flake"), count=1)
    eng.observe(*_batch(0))
    assert eng.stats["apply_retries"] == 1
    assert eng.stats["updates"] == 1           # applied exactly once
    faulted = _query_state(eng)
    eng.close()

    # the faulted engine's post-retry state matches a no-fault oracle
    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(*_batch(0))
    for a, b in zip(_query_state(oracle), faulted):
        np.testing.assert_array_equal(a, b)
    oracle.close()


def test_engine_learn_failpoint_cuts_before_publish():
    """The unsharded Engine's learn step: a fault at engine.learn aborts
    the whole acquire->observe->publish cycle, so the drafter snapshot
    and stats are untouched."""
    from types import SimpleNamespace
    from repro.core import speculative as spec

    stub = SimpleNamespace(prefill=lambda *a: None,
                           decode_step=lambda *a: None,
                           extend_step=lambda *a: None)
    ncfg = spec.NGramConfig(order=2,
                            mc=mc.MCConfig(num_rows=128, capacity=8))
    eng = Engine(stub, None, ServeConfig(ngram=ncfg))
    history = np.arange(12, dtype=np.int32).reshape(2, 6)
    eng._learn(history)
    version = eng.drafter_store.version
    stats_before = dict(eng.stats)

    faults.arm("engine.learn", RuntimeError("learner fault"))
    with pytest.raises(RuntimeError):
        eng._learn(history)
    faults.reset()
    assert eng.drafter_store.version == version    # nothing published
    assert eng.stats == stats_before
    eng._learn(history)                            # lock was released
    assert eng.drafter_store.version == version + 1


# ---------------------------------------------------------------------------
# snapshot faults (exception safety of checkpoint())
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", ["snapshot.meta_write",
                                  "snapshot.arrays_write",
                                  "snapshot.manifest_commit"])
def test_checkpoint_fault_is_exception_safe(tmp_path, site):
    """A sync checkpoint failing at any stage: the writer lock is
    released, the snapshots counter does not lie, no half-written step is
    ever restorable, and the engine keeps serving and writing."""
    eng = _engine(str(tmp_path))
    eng.observe(*_batch(0))
    path0 = eng.checkpoint()
    snaps = eng.stats["snapshots"]

    faults.arm(site, OSError(errno.EIO, "io fault"))
    with pytest.raises(OSError):
        eng.checkpoint(step=7)
    faults.reset()
    assert eng.stats["snapshots"] == snaps     # failed commit not counted
    # the aborted step is invisible to recovery
    assert snapshot_io.latest_complete_step(eng.cfg.snapshot_dir) == \
        int(os.path.basename(path0).split("_")[1])
    eng.observe(*_batch(1))                    # writer lock was released
    eng.checkpoint()                           # and checkpointing works
    eng.close()


def test_async_snapshot_worker_death_is_counted(tmp_path):
    """snapshot.io_thread faulting kills the worker: on_error counts it
    (snapshot_failures), no step dir is committed, serving continues —
    a silently dead IO thread would look exactly like progress."""
    eng = _engine(str(tmp_path))
    eng.observe(*_batch(0))
    faults.arm("snapshot.io_thread", OSError(errno.EIO, "worker died"))
    eng.checkpoint(sync=False)
    for t in list(eng._io_threads):
        t.join()
    faults.reset()
    assert eng.stats["snapshot_failures"] == 1
    assert snapshot_io.latest_complete_step(eng.cfg.snapshot_dir) is None
    eng.observe(*_batch(1))
    eng.close()


def test_restore_read_fault_raises_cleanly(tmp_path):
    """snapshot.restore_read faulting surfaces to the caller; the engine
    neither publishes a torn state nor loses its current one."""
    eng = _engine(str(tmp_path))
    eng.observe(*_batch(0))
    eng.checkpoint()
    before = _query_state(eng)
    faults.arm("snapshot.restore_read", OSError(errno.EIO, "read fault"))
    with pytest.raises(OSError):
        eng.restore()
    faults.reset()
    for a, b in zip(before, _query_state(eng)):
        np.testing.assert_array_equal(a, b)
    eng.restore()                              # clean retry works
    eng.close()


def test_cadence_snapshot_failure_never_fails_observe(tmp_path):
    """The background-cadence snapshot hitting a fault must cost a
    counter, not the write path."""
    eng = _engine(str(tmp_path), snapshot_every=2)
    faults.arm("snapshot.io_thread", OSError(errno.EIO, "cadence fault"))
    for i in range(4):
        eng.observe(*_batch(i))               # steps 2 and 4 snapshot
    for t in list(eng._io_threads):
        t.join()
    faults.reset()
    assert eng.stats["updates"] == 4
    assert eng.stats["snapshot_failures"] == 2
    eng.close()


# ---------------------------------------------------------------------------
# degraded reads (read path never raises)
# ---------------------------------------------------------------------------


def test_query_dispatch_fault_degrades_not_raises(tmp_path):
    """Exhausted query dispatch: empty answers with degraded_answers
    counted — and the next healthy call serves normally again."""
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    faults.arm("engine.query_dispatch", RuntimeError("device lost"))
    d, p, n = eng.query(np.arange(8))
    faults.reset()
    assert (np.asarray(n) == 0).all()
    assert (np.asarray(d) == -1).all()
    assert eng.stats["degraded_answers"] == 8
    assert eng.stats["dispatch_retries"] == FAST.max_attempts - 1
    d2, p2, n2 = eng.query(np.arange(8))
    assert int(np.asarray(n2).sum()) > 0       # healthy again
    eng.close()


def test_query_dispatch_transient_fault_is_invisible(tmp_path):
    """A one-shot dispatch flake is absorbed by the ladder: answers are
    bit-identical to a fault-free call."""
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    clean = _query_state(eng)
    faults.arm("engine.query_dispatch", RuntimeError("flake"), count=1)
    flaky = _query_state(eng)
    faults.reset()
    for a, b in zip(clean, flaky):
        np.testing.assert_array_equal(a, b)
    assert eng.stats["degraded_answers"] == 0
    eng.close()


def test_topn_dispatch_fault_degrades_not_raises(tmp_path):
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    faults.arm("engine.topn_dispatch", RuntimeError("device lost"))
    srcs, dsts, probs = eng.topn(4)
    faults.reset()
    assert (np.asarray(srcs) == -1).all()
    assert eng.stats["degraded_answers"] == 4
    srcs2, _, probs2 = eng.topn(4)
    assert int(np.asarray(srcs2).max()) >= 0   # healthy again
    eng.close()


@pytest.fixture
def fresh_traces():
    """Drop traced programs before and after, so a program traced under a
    patched dispatch is never reused by another test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _uncompilable_topn_merge(probs, dsts, srcs, *, n, impl):
    """``ops.topn_merge`` routed through a Pallas call that is compiled,
    not interpreted: the CPU backend cannot lower it."""
    from jax.experimental import pallas as pl
    from repro.kernels import ref

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    m_src, m_dst, m_p = ref.topn_merge_ref(probs, dsts, srcs, n)
    m_p = pl.pallas_call(copy, out_shape=jax.ShapeDtypeStruct(
        m_p.shape, m_p.dtype), interpret=False)(m_p)
    return m_src, m_dst, m_p


@pytest.mark.parametrize("op", ["query", "topn"])
def test_lowering_error_raises_unretried(tmp_path, monkeypatch,
                                         fresh_traces, op):
    """A read program that fails to lower is a bug, not a fault of the
    model: it reaches the caller on the first attempt — no retry, no
    degraded empty answer."""
    from repro.kernels import ops

    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    if op == "query":   # the compiled kernels, on a backend without them
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        call = lambda: eng.query(np.arange(8))
    else:
        monkeypatch.setattr(ops, "topn_merge", _uncompilable_topn_merge)
        call = lambda: eng.topn(4)
    with pytest.raises(ValueError, match="interpret mode"):
        call()
    assert eng.stats["dispatch_retries"] == 0
    assert eng.stats["degraded_answers"] == 0
    eng.close()


def test_lowering_error_on_observe_with_wal_poisons_and_restore_replays(
        tmp_path, monkeypatch, fresh_traces):
    """An update program that fails to lower AFTER the batch reached the
    WAL: the error reaches the caller unretried, but the record is a ghost
    (durable, unapplied), so the write path poisons exactly as for an
    escalated fault, and restore() replays the record."""
    from repro.kernels import ops

    src0, dst0 = _batch(0)
    src1, dst1 = _batch(1)
    eng = _engine(str(tmp_path))
    eng.observe(src0, dst0)
    eng.checkpoint()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()      # retrace the update program under the patch
    with pytest.raises(ValueError, match="interpret mode"):
        eng.observe(src1, dst1)
    monkeypatch.undo()
    jax.clear_caches()
    assert eng.stats["apply_retries"] == 0
    assert eng.stats["write_errors"] == 1
    assert not eng.write_available
    assert eng._seq == 0 and eng.wal.last_seq == 1  # the ghost record
    with pytest.raises(EngineWriteUnavailable):
        eng.observe(src1, dst1)                 # seq never moves past it

    result = eng.restore()
    assert result["replayed"] >= 1 and eng._seq == 1
    assert eng.write_available
    healed_q = _query_state(eng)
    eng.close()

    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(src0, dst0)
    oracle.observe(src1, dst1)
    for a, b in zip(healed_q, _query_state(oracle)):
        np.testing.assert_array_equal(a, b)
    oracle.close()


@pytest.mark.parametrize("op", ["query", "topn"])
@pytest.mark.parametrize("fault", [OSError(errno.EIO, "io fault"),
                                   RuntimeError("device lost")])
def test_injected_fault_still_degrades_reads(tmp_path, op, fault):
    """Narrowing the ladder keeps what it models: an injected fault, an
    IO error or any other type, still retries and degrades the read."""
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.observe(*_batch(0))
    faults.arm(f"engine.{op}_dispatch", fault)
    if op == "query":
        d, _, n = eng.query(np.arange(8))
        assert (np.asarray(d) == -1).all() and (np.asarray(n) == 0).all()
        assert eng.stats["degraded_answers"] == 8
    else:
        srcs, _, _ = eng.topn(4)
        assert (np.asarray(srcs) == -1).all()
        assert eng.stats["degraded_answers"] == 4
    faults.reset()
    assert eng.stats["dispatch_retries"] == FAST.max_attempts - 1
    eng.close()


def test_uninjected_runtime_error_is_not_retried():
    """Outside the fault model (not an OSError, not injected, not a shard
    dispatch fault) the ladder re-raises on the first attempt."""
    calls = []

    def broken():
        calls.append(1)
        raise RuntimeError("bug")

    with pytest.raises(RuntimeError, match="bug"):
        call_with_retry(broken, policy=FAST, sleep=lambda s: None)
    assert len(calls) == 1


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (CI multi-device matrix; "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_mark_shard_down_degrades_reads_and_defers_writes(tmp_path):
    """Down shard: its items answer empty (counted), top-n filters its
    rows (survivors stay descending), writes defer bounded, heal_shard
    re-applies them and re-admits the shard."""
    eng = _engine(str(tmp_path), shards=2)
    src = np.arange(16, dtype=np.int32)
    eng.observe(src, (src + 1) % 64)
    own = eng.cfg.sharded.resolved_ownership()
    owner = np.asarray(own.owner_of(jnp.asarray(src)))

    eng.mark_shard_down(1)
    d, p, n = eng.query(src)
    assert (np.asarray(n)[owner == 1] == 0).all()
    assert (np.asarray(n)[owner == 0] > 0).any()
    assert eng.stats["degraded_answers"] >= int((owner == 1).sum())

    ts, td, tp = eng.topn(8)
    live = np.asarray(ts)[np.asarray(ts) >= 0]
    assert (np.asarray(own.owner_of(jnp.asarray(live))) != 1).all()
    p_live = np.asarray(tp)[: live.size]
    assert (np.diff(p_live) <= 1e-6).all()     # survivors stay sorted

    eng.observe(src, (src + 2) % 64)           # shard-1 items defer
    assert eng.stats["deferred_writes"] > 0
    healed = eng.heal_shard(1)
    assert healed == 1
    assert eng.stats["deferred_writes"] == 0
    assert eng.stats["shards_down"] == 0
    d2, p2, n2 = eng.query(src)
    assert (np.asarray(n2) > 0).all()          # everything serves again
    eng.close()


def test_deferred_writes_survive_snapshot_gc_and_crash(tmp_path):
    """A15 regression: a snapshot committing while a shard is down
    persists the deferred queue in its meta; WAL GC may then unlink the
    deferred batches' only log records, and a post-crash restore must
    still reinstate and heal them — never lose them."""
    src0, dst0 = _batch(0)
    src1, dst1 = _batch(1)
    eng = _engine(str(tmp_path))
    eng.wal.segment_records = 1                # every record GC-able
    eng.observe(src0, dst0)
    eng.mark_shard_down(0)
    eng.observe(src1, dst1)                    # defers; WAL seq 1
    assert eng.stats["deferred_writes"] == src1.size
    eng.checkpoint()                           # commit + GC through seq 1
    assert not os.listdir(eng.cfg.wal_dir)     # the WAL copy is GONE
    eng.close()

    eng2 = _engine(str(tmp_path))              # "fresh process"
    eng2.restore()
    assert eng2.stats["shards_down"] == 1      # down-set reinstated
    assert eng2.stats["deferred_writes"] == src1.size
    assert eng2.heal_shard(0) == 1             # the deferred batch healed
    healed = _query_state(eng2)
    # seq authority survives a fully-GC'd WAL: new records must continue
    # after the snapshot's wal_seq, not restart at 0 under it
    eng2.observe(*_batch(2))
    assert eng2.wal.last_seq == 2
    eng2.close()

    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(src0, dst0)
    oracle.observe(src1, dst1)
    for a, b in zip(healed, _query_state(oracle)):
        np.testing.assert_array_equal(a, b)
    oracle.close()


def test_restore_resets_health_map_before_replay(tmp_path):
    """In-process restore(): the live health map is replaced by the
    snapshot's image BEFORE replay, so a tail record owned by a live-down
    shard is applied directly (the snapshot never saw its deferral) —
    keeping it deferred on top of the snapshot image would double-apply
    it on the eventual heal."""
    src0, dst0 = _batch(0)
    src1, dst1 = _batch(1)
    eng = _engine(str(tmp_path))
    eng.observe(src0, dst0)
    eng.checkpoint()                           # healthy image, wal_seq 0
    eng.mark_shard_down(0)
    eng.observe(src1, dst1)                    # defers in memory; seq 1
    assert eng.stats["deferred_writes"] == src1.size

    result = eng.restore()                     # in-process, same engine
    assert result["replayed"] == 1             # seq 1 applied directly
    assert eng.stats["shards_down"] == 0       # snapshot image: healthy
    assert eng.stats["deferred_writes"] == 0
    assert eng.heal_shard(0) == 0              # nothing left to heal
    healed = _query_state(eng)
    eng.close()

    oracle = _engine(str(tmp_path) + "_oracle")
    oracle.observe(src0, dst0)
    oracle.observe(src1, dst1)                 # applied exactly once
    for a, b in zip(healed, _query_state(oracle)):
        np.testing.assert_array_equal(a, b)
    oracle.close()


def test_heal_shard_fault_requeues_remainder(tmp_path):
    """A dispatch fault mid-heal must not drop the already-popped
    remainder: the shard re-marks down, the unapplied batches (failed one
    included) requeue in order, and a clean retry heals them."""
    src0, dst0 = _batch(0)
    src1, dst1 = _batch(1)
    eng = _engine(str(tmp_path), wal=False, snap=False)
    eng.mark_shard_down(0)
    eng.observe(src0, dst0)
    eng.observe(src1, dst1)
    assert eng.stats["deferred_writes"] == src0.size + src1.size

    # first deferred batch applies; the second exhausts the ladder
    faults.arm("engine.apply", RuntimeError("device lost"),
               trigger=lambda hit: hit > 1)
    with pytest.raises(RetryBudgetExceeded):
        eng.heal_shard(0)
    faults.reset()
    assert eng.stats["shards_down"] == 1       # re-marked down
    assert eng.stats["deferred_writes"] == src1.size   # remainder kept

    assert eng.heal_shard(0) == 1              # clean retry applies it
    assert eng.stats["shards_down"] == 0
    assert eng.stats["deferred_writes"] == 0
    healed = _query_state(eng)
    eng.close()

    oracle = _engine(str(tmp_path) + "_oracle", wal=False, snap=False)
    oracle.observe(src0, dst0)
    oracle.observe(src1, dst1)
    for a, b in zip(healed, _query_state(oracle)):
        np.testing.assert_array_equal(a, b)
    oracle.close()


def test_dispatch_strikes_mark_shard_down_automatically(tmp_path):
    """The automatic path to down (no admin call): shard-attributable
    dispatch escalations (ShardDispatchError in the fault chain) strike
    the owner; after health_strikes consecutive escalations the shard is
    down — reads mask it without dispatching into it, writes defer — and
    heal_shard re-admits it."""
    eng = _engine(str(tmp_path), wal=False, snap=False, health_strikes=2)
    eng.observe(*_batch(0))
    assert shard_from_exception(None) is None

    faults.arm("engine.query_dispatch", ShardDispatchError(0, "rpc lost"))
    eng.query(np.arange(8))                    # escalates: strike 1
    assert eng.stats["shards_down"] == 0
    eng.query(np.arange(8))                    # strike 2: auto-down
    faults.reset()
    assert eng.stats["shards_down"] == 1
    assert eng.health.down == frozenset({0})

    d, p, n = eng.query(np.arange(8))          # masked: no dispatch fault
    assert (np.asarray(n) == 0).all()
    eng.observe(*_batch(1))                    # writes defer, not fail
    assert eng.stats["deferred_writes"] > 0
    assert eng.heal_shard(0) == 1
    assert (np.asarray(eng.query(np.arange(8))[2]) > 0).any()
    eng.close()


def test_dispatch_success_breaks_strike_streak(tmp_path):
    """Strikes are CONSECUTIVE failures: a healthy whole-mesh dispatch
    between two escalations resets the streak, so flapping faults never
    accumulate to a spurious down."""
    eng = _engine(str(tmp_path), wal=False, snap=False, health_strikes=2)
    eng.observe(*_batch(0))
    faults.arm("engine.query_dispatch", ShardDispatchError(0, "flap"),
               count=FAST.max_attempts)        # exactly one escalation
    eng.query(np.arange(8))                    # strike 1
    faults.reset()
    eng.query(np.arange(8))                    # healthy: streak broken
    faults.arm("engine.query_dispatch", ShardDispatchError(0, "flap"),
               count=FAST.max_attempts)
    eng.query(np.arange(8))                    # strike 1 again, not 2
    faults.reset()
    assert eng.stats["shards_down"] == 0
    assert not eng.health.down
    eng.close()


# ---------------------------------------------------------------------------
# overflow-retry tier (satellite: route_dropped -> retried/lost)
# ---------------------------------------------------------------------------


def test_route_overflow_prediction_matches_device(tmp_path):
    """The host-side drop predictor must agree bit-exactly with the
    device routing — the tier's correctness rests on it."""
    scfg = sh.ShardedConfig(base=mc.MCConfig(num_rows=64, capacity=8),
                            num_shards=1, bucket_factor=0.5)
    eng = ShardedEngine(ShardedServeConfig(sharded=scfg))
    rng = np.random.default_rng(5)
    for trial in range(5):
        # heavy skew: most items hit a handful of rows
        src = rng.choice([0, 1, 2, 63], size=24,
                         p=[0.6, 0.2, 0.1, 0.1]).astype(np.int32)
        dst = rng.integers(0, 64, 24).astype(np.int32)
        predicted = int(sh.predict_route_overflow(scfg, src).sum())
        before = eng.stats.get("route_dropped", 0)
        eng.observe(src, dst)
        device = eng.stats["route_dropped"] - before
        assert predicted == device, f"trial {trial}"
    eng.close()


def test_route_retry_tier_requeues_and_drains(tmp_path):
    """With the tier on, skew drops are masked before dispatch (device
    route_dropped stays 0), requeued with a bounded budget, and drained
    across later steps; exhausted items count into route_lost."""
    def mk(budget):
        return _engine(str(tmp_path) + f"_{budget}", snap=False,
                       factor=0.5, route_retry_budget=budget,
                       route_retry_slice=8)

    src = np.zeros(24, np.int32)
    dst = np.arange(24, dtype=np.int32)

    eng0 = mk(0)
    eng0.observe(src, dst)
    assert eng0.stats["route_dropped"] > 0     # tier off: device drops
    eng0.close()

    eng = mk(8)
    eng.observe(src, dst)
    assert eng.stats["route_dropped"] == 0     # tier on: masked pre-dispatch
    assert eng.stats["route_retried"] > 0
    assert sum(int(c[0].size) for c in eng._retry_queue) > 0
    steps = 0
    while eng._retry_queue and steps < 64:
        eng.observe(np.full(1, -1, np.int32), np.zeros(1, np.int32))
        steps += 1
    assert not eng._retry_queue                # queue fully drained
    assert eng.stats["route_dropped"] == 0
    applied_or_lost = eng.stats["route_lost"]
    assert applied_or_lost >= 0                # bounded loss, counted
    eng.close()


def test_route_retry_queue_survives_snapshot_restore(tmp_path):
    """The carry-over queue is recovery state: it rides snapshot meta and
    replay re-plans from it deterministically."""
    eng = _engine(str(tmp_path), factor=0.5, route_retry_budget=8,
                  route_retry_slice=8)
    eng.observe(np.zeros(24, np.int32), np.arange(24, dtype=np.int32))
    queued = sum(int(c[0].size) for c in eng._retry_queue)
    assert queued > 0
    eng.checkpoint()
    eng.close()

    eng2 = _engine(str(tmp_path), factor=0.5, route_retry_budget=8,
                   route_retry_slice=8)
    eng2.restore()
    assert sum(int(c[0].size) for c in eng2._retry_queue) == queued
    eng2.close()


def test_query_overflow_retry_answers_skewed_batch(tmp_path):
    """In-call query retry: a skew-dropped query batch is re-dispatched
    round-robin across sender slices until answered; the tier-off call
    answers strictly fewer items."""
    scfg = sh.ShardedConfig(base=mc.MCConfig(num_rows=64, capacity=8),
                            num_shards=1, bucket_factor=0.5)
    src_w = np.arange(32, dtype=np.int32) % 64

    eng0 = ShardedEngine(ShardedServeConfig(sharded=scfg))
    eng0.observe(src_w, (src_w + 1) % 64)
    _, _, n0 = eng0.query(np.zeros(32, np.int32))
    eng0.close()

    eng = ShardedEngine(ShardedServeConfig(sharded=scfg,
                                           query_retry_budget=4,
                                           retry=FAST))
    eng.observe(src_w, (src_w + 1) % 64)
    _, _, n1 = eng.query(np.zeros(32, np.int32))
    assert eng.stats["query_dropped"] > 0
    assert eng.stats["query_retried"] > 0
    answered0 = int((np.asarray(n0) > 0).sum())
    answered1 = int((np.asarray(n1) > 0).sum())
    assert answered1 == 32 - eng.stats["query_lost"]
    assert answered1 > answered0
    eng.close()


def test_soak_parent_never_imports_jax():
    """The crash soak spawns a worker per life and checks recovery in a
    child too, so its parent never holds an accelerator a child needs."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "from tools.chaos.soak import run_soak\n"
            "r = run_soak(1, rows=64, batch=32, min_steps=2, max_steps=2)\n"
            "assert r['ok'], r\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(repo, "src"), repo]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
