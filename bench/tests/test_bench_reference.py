"""The plain reference against the engine, at a tiny size on the CPU with
the Pallas kernels in interpret mode: counts, totals, new rows and slots,
Space-Saving replacement, rolling decay, the threshold query and the
global top-n agree after every batch."""

import numpy as np

import drive  # noqa: F401  (puts the benchmark on the path)
from compare import ProgramState, _state_diff
from reference import ReferenceChain

N, C, THRESH = 48, 8, 60


def engine_and_reference():
    from repro.core import mcprioq as mc
    from repro.core import sharded as sh
    from repro.serve.engine import ShardedEngine, ShardedServeConfig
    base = mc.MCConfig(num_rows=N, capacity=C, sort_passes=1,
                       decay_block_rows=16, impl="pallas")
    eng = ShardedEngine(ShardedServeConfig(
        sharded=sh.ShardedConfig(base=base, num_shards=1, bucket_factor=2.0),
        decay_threshold=THRESH, threshold=0.9, max_items=4, topn=6))
    ref = ReferenceChain(1, N, C, decay_threshold=THRESH, decay_block_rows=16,
                         bucket_factor=2.0)
    return eng, ref


def program_state(eng):
    snap = eng.store.acquire()
    try:
        st = snap.state
        return ProgramState(
            np.asarray(st.slabs.cnt), np.asarray(st.slabs.dst),
            np.asarray(st.slabs.order), np.asarray(st.slabs.tot),
            np.asarray(st.src_table.keys), np.asarray(st.src_table.vals),
            np.asarray(st.n_rows), int(np.asarray(st.evictions).sum()))
    finally:
        eng.store.release(snap)


def test_engine_matches_reference_batch_by_batch():
    eng, ref = engine_and_reference()
    rng = np.random.default_rng(3)
    evictions = decays = 0
    for step in range(14):
        b = 32
        src = (rng.zipf(1.3, b) % 60).astype(np.int32)       # > N sources
        dst = (rng.zipf(1.2, b) % 14).astype(np.int32)       # > C successors
        src[rng.random(b) < 0.1] = -1                         # padding
        eng.observe(src, dst)
        ref.observe(src, dst)
        st = program_state(eng)
        assert _state_diff(ref, st) == 0, step
        q = np.concatenate([np.arange(12), [-1, 70]]).astype(np.int32)
        d, p, n = (np.asarray(x) for x in eng.query(q, threshold=0.9))
        rd, rp, rn = ref.query(q, 0.9, 4)
        assert (d == rd).all() and (n == rn).all()
        np.testing.assert_array_max_ulp(p, rp, maxulp=1)
        s_, d_, p_ = (np.asarray(x) for x in eng.topn(6))
        rs, rdd, rpp = ref.topn(6)
        np.testing.assert_array_max_ulp(p_, rpp, maxulp=1)
        assert (s_ == rs).all() and (d_ == rdd).all()
        evictions, decays = ref.evictions, ref.decay_steps
    stats = eng.stats_snapshot()
    assert stats["dropped_rows"] == ref.dropped_rows > 0
    assert evictions > 0 and decays > 0
