"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["window_s"]:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
