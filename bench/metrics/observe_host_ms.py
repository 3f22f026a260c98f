"""Host time of one observe call, a mean over the window's calls (so one
stalled call shows): the ``engine.observe`` span less its program launches
(``engine.dispatch``) and its ``engine.device_wait``; what is left is the
engine's own work (WAL, route plan, publish, locks)."""

from span_records import split, window_calls


def read(ctx):
    calls = window_calls(ctx, "engine.observe")
    if calls is None:
        return None
    host = [split(c, "engine.observe", "engine.device_wait")[0]
            for c in calls]
    return sum(host) / len(host) * 1e3
