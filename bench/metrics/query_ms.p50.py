"""Median client-side time of one query call, from issue to return."""


def read(ctx):
    import numpy as np
    dur = ctx.win.read_dur[~ctx.reads.is_topn]
    dur = dur[np.isfinite(dur)]
    return float(np.median(dur)) * 1e3 if dur.size else None
