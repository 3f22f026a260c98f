"""The program's own spans of the measured window, read from the engine's
flight-recorder ring (``engine.metrics.spans()``).

The program records spans while a profiler session records, so they are
there in ``--trace 1`` runs only.  A program that records none, or records
no start and end of a span, gives nothing here.

A call's time splits into waiting on the device and host time.  Waiting
is every ``engine.dispatch`` (a program launch, which blocks while the
runtime holds as many launches in flight as it takes) and the call's
blocking read of a device result (``engine.device_wait``,
``engine.query_wait``); host time is the rest of the top-level span.
"""

from __future__ import annotations


def window_calls(ctx, top: str):
    """Seconds spent in each span of every top-level ``top`` call that
    started in the window, one ``{span name: seconds}`` per call in start
    order (a name that recurs in a call is summed); None when the ring
    holds no record, or when it is full and its oldest record ended inside
    the window (some of the window's records may be gone)."""
    win = ctx.win
    engine = getattr(win, "engine", None)
    ring = engine.metrics.spans() if engine is not None else []
    if not ring or "start_s" not in ring[0]:
        return None
    if engine.metrics.spans_full() and ring[0]["end_s"] >= win.t0:
        return None
    spent, starts = {}, {}
    for r in ring:
        call = spent.setdefault(r["call"], {})
        call[r["name"]] = call.get(r["name"], 0.0) + r["end_s"] - r["start_s"]
        if (r["name"] == top and r["parent"] is None
                and win.t0 <= r["start_s"] <= win.t_reads_end):
            starts[r["call"]] = r["start_s"]
    return [spent[c] for c in sorted(starts, key=starts.get)] or None


def split(call: dict, top: str, wait: str):
    """``(host, wait)`` seconds of one call: ``wait`` is its launches and
    its blocking read ``wait``, ``host`` the rest of ``top``."""
    waited = call.get("engine.dispatch", 0.0) + call.get(wait, 0.0)
    return call[top] - waited, waited
