"""Executables compiled (or loaded from the persistent cache) inside the
window, counted from JAX's own compile events by the listener ``run.py``
registers.  Warm-up should leave nothing to compile: it should read 0."""


def read(ctx):
    return ctx.compiles_in_window
