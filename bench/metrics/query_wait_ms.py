"""Time one query call waits on the device, the median over the window's
calls: the launch of its program (``engine.dispatch``, blocked while the
runtime's launches in flight are at their limit) and
``engine.query_wait``, until the program's drop count reaches the host
(the programs queued ahead of it, then its own)."""

from span_records import split, window_calls


def read(ctx):
    import numpy as np
    calls = window_calls(ctx, "engine.query")
    if calls is None:
        return None
    return float(np.median([split(c, "engine.query", "engine.query_wait")[1]
                            for c in calls])) * 1e3
