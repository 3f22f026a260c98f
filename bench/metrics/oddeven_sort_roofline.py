"""Odd-even kernel's share of its roofline: the bytes it must move (read
the counts in order and the order, write both) over the HBM peak, divided
by its mean time per run.  Bytes-bound."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("oddeven_pallas")
    if not k or not k["runs"]:
        return None
    mc = ctx.cfg["mc"]
    least = (ctx.roofline("oddeven_sort").bytes_moved(
        mc["num_rows"], mc["capacity"]) / ctx.hbm_bytes_per_s())
    return least / (k["device_s"] / k["runs"]) * 100
