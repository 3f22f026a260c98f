"""Host time of one query call, the median over the window's calls: the
``engine.query`` span less its program launch (``engine.dispatch``) and
its ``engine.query_wait`` (snapshot, padding, stats)."""

from span_records import split, window_calls


def read(ctx):
    import numpy as np
    calls = window_calls(ctx, "engine.query")
    if calls is None:
        return None
    return float(np.median([split(c, "engine.query", "engine.query_wait")[0]
                            for c in calls])) * 1e3
