"""JAX backend-compile events the harness counted between window open and
close (a load from the persistent cache fires one too). Must be 0."""


def read(ctx):
    return ctx.env.compiles.in_window
