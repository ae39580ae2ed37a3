"""Device time per edge of the frontier engine's dense pull rounds: the
device self time of the pull step's program in the window, over the
window's delta of ``engine.frontier.pull_edges`` (rows x |E| of each pull
round: every in-edge is read)."""

import program_metrics as pm


def read(ctx):
    return pm.ns_per_edge(ctx, pm.PULL_PROGRAMS, "engine.frontier.pull_edges")
