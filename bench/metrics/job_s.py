"""Window start to the last job's completion, over the jobs completed."""


def read(ctx):
    done = [r for r in ctx.records if r.ok]
    return ctx.window_s / len(done) if done else None
