"""Time one observe call waits on the device, a mean over the window's
calls: the launches of the update and maintain programs
(``engine.dispatch``, blocked while the runtime's launches in flight are
at their limit) and ``engine.device_wait``, until the programs' counters
reach the host (the programs, and whatever ran on the device ahead of
them)."""

from span_records import split, window_calls


def read(ctx):
    calls = window_calls(ctx, "engine.observe")
    if calls is None:
        return None
    wait = [split(c, "engine.observe", "engine.device_wait")[1]
            for c in calls]
    return sum(wait) / len(wait) * 1e3
