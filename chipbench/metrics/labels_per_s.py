"""Oracle labels the queries acquired between the window's start and end
(``Oracle.calls``: scored or served from the label store), per second of
the window."""


def read(ctx):
    return ctx.labels / ctx.window_s if ctx.window_s > 0 else None
