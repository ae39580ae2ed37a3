"""Seconds of set-up spent building the graph and its plan: the sum of the
program's ``plan.build_ms`` histogram before the window (the graph's CSR
sorts, each plan family's first build, each engine exec; nested builds
count once)."""

import program_metrics as pm


def read(ctx):
    ms = pm.hist_sum_before_window(ctx, "plan.build_ms")
    return ms / 1e3 if ms is not None else None
