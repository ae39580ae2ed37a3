"""The whole PageRank iteration's share of the HBM roofline: the least bytes
any implementation of one iteration must move (4 B per edge for the source
index; 16 B per vertex for rank, degree and output), times the iterations
completed in the window, over the device's busy time in the window times
the peak bandwidth.  It counts the same work whatever implements the pull."""

import kernel_cost


def read(ctx):
    t = ctx.trace
    if not t or t["busy_s"] <= 0:
        return None
    done = [r for r in ctx.records if r.ok]
    iters = sum(int(r.params.get("n_iter", 10)) for r in done)
    if not iters:
        return None
    least = kernel_cost.pagerank_iter_bytes(ctx.graph)
    return 100.0 * iters * least / (t["busy_s"] * ctx.peaks["hbm_bytes_per_s"])
