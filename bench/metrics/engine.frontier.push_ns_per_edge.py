"""Device time per edge of the frontier engine's sparse push rounds: the
device self time of the push rounds' programs (the compaction and the push
step) in the window, over the window's delta of
``engine.frontier.push_edges`` (rows x frontier out-edges of each push
round: the edges the push reads)."""

import program_metrics as pm


def read(ctx):
    return pm.ns_per_edge(ctx, pm.PUSH_PROGRAMS, "engine.frontier.push_edges")
