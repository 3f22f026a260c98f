"""Events of the observe calls completed in the window over the window's
time (a closed loop: the window ends when the last observe it started
returns)."""


def read(ctx):
    win = ctx.win
    span = win.t_end - win.t0
    return win.events / span if win.obs_calls and span > 0 else None
