"""95th percentile over every event of the window of the time from its due
time to the return of the observe that learned it."""


def read(ctx):
    import numpy as np
    lags = ctx.win.lags
    if lags is None or not np.isfinite(lags).any():
        return None
    return float(np.percentile(lags[np.isfinite(lags)], 95)) * 1e3
