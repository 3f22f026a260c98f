"""Device time of the query program per query call, from the trace."""


def read(ctx):
    t = ctx.trace
    if t is None or "query" not in t["programs"]:
        return None
    n = int((~ctx.reads.is_topn).sum())
    return t["programs"]["query"]["device_s"] / n * 1e3 if n else None
