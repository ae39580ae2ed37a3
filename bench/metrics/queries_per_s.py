"""Queries completed over the time from window start to the last
completion."""


def read(ctx):
    done = [r for r in ctx.records if r.ok]
    return len(done) / ctx.window_s if done else None
