"""The trace reduction on a trace recorded on one v5e chip (a churn window
around one update-program run, pruned to the device's module and op lines
and the benchmark's host spans)."""

import gzip
from pathlib import Path

import pytest

import drive  # noqa: F401  (puts the benchmark on the path)
import trace_reduce

FIXTURE = (Path(__file__).resolve().parents[1] / "testdata"
           / "churn_update_window.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(FIXTURE.read_bytes()))
    return trace_reduce.reduce_profile(pd)


def test_programs_are_classified_by_their_kernels(reduced):
    progs = reduced["programs"]
    assert progs["update"]["runs"] == 1
    assert progs["update"]["device_s"] == pytest.approx(1.5868265, rel=1e-6)
    assert progs["query"]["runs"] == 18
    assert "topn" not in progs and "maintain" not in progs
    assert reduced["collectives"] == {}


def test_kernel_times_and_busy_share(reduced):
    k = reduced["kernels"]
    assert k["oddeven_pallas"]["runs"] == 1
    assert k["oddeven_pallas"]["device_s"] == pytest.approx(0.005429227)
    assert k["cdf_query_fused_pallas"]["runs"] == 18
    assert k["slab_update_pallas"]["runs"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1]
    assert ops[0][0].startswith("%fusion.8 = s32[67108864]")


def test_classify_names():
    assert trace_reduce.base_name("%oddeven_pallas.1 = (s32[8]) custom") \
        == "oddeven_pallas"
    assert trace_reduce.classify("jit_fn(1)", ["%sort.2 = f32[4] sort()"]) \
        == "topn"
    assert trace_reduce.classify("jit_fn(1)", ["%add.1 = s32[] add()"]) \
        == "maintain"
    assert trace_reduce.classify("jit__counter_stack(7)", []) \
        == "jit__counter_stack"
