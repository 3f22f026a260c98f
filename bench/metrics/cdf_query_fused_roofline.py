"""Fused query kernel's share of its roofline: the bytes of the rows a
query needs and the answers it writes, for the real queries a shard
receives per run, over the HBM peak, divided by its mean time per run."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("cdf_query_fused_pallas")
    if not k or not k["runs"]:
        return None
    sv, mc = ctx.cfg["serve"], ctx.cfg["mc"]
    queries = ctx.mix["reads"]["query_width"] / sv["num_shards"]
    least = (ctx.roofline("cdf_query_fused").bytes_moved(
        queries, mc["capacity"], sv["max_items"]) / ctx.hbm_bytes_per_s())
    return least / (k["device_s"] / k["runs"]) * 100
