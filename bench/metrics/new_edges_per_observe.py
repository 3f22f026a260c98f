"""Edges that went down the new-edge path per observe call: the change of
the live-slot count (cnt > 0) over the window plus the evictions, over
the observe calls.  An exact count from the state."""


def read(ctx):
    n = len(ctx.win.obs_calls)
    return (ctx.live_delta + ctx.evictions_delta) / n if n else None
