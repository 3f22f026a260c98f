"""The span readers (``observe_host_ms``, ``observe_wait_ms``,
``query_host_ms``, ``query_wait_ms``) on a CPU rehearsal window run under
the profiler, as a ``--trace 1`` window runs on the chip."""

import types

import pytest

import drive
import run
import window as window_mod

READERS = ("observe_host_ms", "observe_wait_ms", "query_host_ms",
           "query_wait_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Drive a churn rehearsal whose window runs inside a profiler session;
    keep the window, the ring's records and the readings taken before the
    harness frees the engine."""
    import jax
    got = {}
    plain = window_mod.Window.run

    def traced_run(self, threads):
        jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
        try:
            return plain(self, threads)
        finally:
            jax.profiler.stop_trace()
            ctx = types.SimpleNamespace(win=self)
            got["records"] = self.engine.metrics.spans()
            got["values"] = {m: run.load_reader(m)(ctx) for m in READERS}
            got["win"] = self

    window_mod.Window.run = traced_run
    try:
        got["result"] = drive.drive("rec-1m.churn", 3000000021)
    finally:
        window_mod.Window.run = plain
    return got


class _Ring:
    """A window whose engine's ring holds ``records``, ``full`` or not."""

    def __init__(self, win, records, full=False):
        engine = types.SimpleNamespace(metrics=types.SimpleNamespace(
            spans=lambda: list(records), spans_full=lambda: full))
        self.ctx = types.SimpleNamespace(win=types.SimpleNamespace(
            engine=engine, t0=win.t0, t_reads_end=win.t_reads_end))


def test_readers_report_on_a_traced_window(traced):
    assert traced["result"]["correct"] is True
    vals = traced["values"]
    assert all(vals[m] is not None and vals[m] >= 0 for m in READERS), vals
    assert vals["observe_wait_ms"] > 0 and vals["query_wait_ms"] > 0


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_on_a_wrapped_or_empty_ring(traced, metric):
    read = run.load_reader(metric)
    win, records = traced["win"], traced["records"]
    assert read(_Ring(win, records).ctx) is not None
    # full, its oldest record inside the window: the window's may be gone
    assert read(_Ring(win, records[5:], full=True).ctx) is None
    assert read(_Ring(win, []).ctx) is None
    # full, but everything it dropped ended before the window
    before = {"name": "engine.query", "start_s": win.t0 - 2.0,
              "end_s": win.t0 - 1.0, "parent": None, "call": -1,
              "thread": "reader", "error": False}
    assert read(_Ring(win, [before] + records, full=True).ctx) == read(
        _Ring(win, records).ctx)


def test_host_and_wait_account_for_each_observe(traced):
    """Host time plus waiting is the whole observe call: their means add up
    to the mean ``engine.observe`` span, and every launch lies inside its
    call."""
    records, vals = traced["records"], traced["values"]
    tops = [r for r in records
            if r["name"] == "engine.observe" and r["parent"] is None]
    mean = sum(r["end_s"] - r["start_s"] for r in tops) / len(tops) * 1e3
    total = vals["observe_host_ms"] + vals["observe_wait_ms"]
    assert total == pytest.approx(mean, rel=1e-9)
    calls = {r["call"]: r for r in tops}
    launches = [r for r in records if r["name"] == "engine.dispatch"
                and r["call"] in calls]
    assert len(launches) == 2 * len(tops)   # update and maintain
    for r in launches:
        assert r["parent"] == "engine.apply"
        call = calls[r["call"]]
        assert call["start_s"] <= r["start_s"] <= r["end_s"] <= call["end_s"]


def test_observe_spans_match_the_windows_own_times(traced):
    win = traced["win"]
    spans = sorted((r for r in traced["records"]
                    if r["name"] == "engine.observe"),
                   key=lambda r: r["start_s"])
    assert len(spans) == len(win.obs_calls) > 0
    for r, (ts, te) in zip(spans, win.obs_calls):
        assert abs(r["start_s"] - ts) < 1e-3
        assert abs(r["end_s"] - te) < 1e-3
