"""Device time of the update program per observe call, from the trace
(runs classified by their kernels)."""


def read(ctx):
    t = ctx.trace
    if t is None or "update" not in t["programs"]:
        return None
    n = len(ctx.win.obs_calls)
    return t["programs"]["update"]["device_s"] / n * 1e3 if n else None
