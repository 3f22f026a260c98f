"""Median client-side time of one observe call (host serving layer)."""


def read(ctx):
    import numpy as np
    calls = ctx.win.obs_calls
    if not calls:
        return None
    return float(np.median([e - s for s, e in calls])) * 1e3
