"""Set-up seconds: process start to the window's start (loading, warm state,
warm-up and, in a run that compiles, compilation)."""


def read(ctx):
    return ctx.setup_s
