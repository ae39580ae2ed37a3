"""Process start to the first timed request: generation, publish and plan,
warm-up compiles or cache loads."""


def read(ctx):
    return ctx.setup_s
