"""Frontier rounds the engine ran in the window (delta of the
``engine.frontier.rounds`` counter) per query completed."""


def read(ctx):
    done = sum(r.ok for r in ctx.records)
    rounds = ctx.counter_delta("engine.frontier.rounds")
    return rounds / done if done and rounds is not None else None
