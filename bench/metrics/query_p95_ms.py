"""95th percentile over every query call of the window, each timed from its
due time to its return."""


def read(ctx):
    import numpy as np
    lat = ctx.win.read_lat[~ctx.reads.is_topn]
    lat = lat[np.isfinite(lat)]
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
