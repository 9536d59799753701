"""Share of the token slots sent to the device that hold no real token:
pad columns up to each block's bucket and pad rows up to the batch, over
all slots of the window's scorer forwards."""


def read(ctx):
    slots = sum(pad * batch for pad, batch, _ in ctx.blocks)
    real = sum(int(lens.sum()) for _, _, lens in ctx.blocks)
    return 100.0 * (1.0 - real / slots) if slots else None
