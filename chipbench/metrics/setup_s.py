"""Set-up: process start to the window's start (JAX start-up, tables,
records, weights, compiles or cache loads, warm-up queries)."""


def read(ctx):
    return ctx.setup_s
