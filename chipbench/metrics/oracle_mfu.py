"""The scorer's required FLOPs for the real tokens it scored in the window
(``scorer_roofline.required_flops``), over the window's length, over
chips x the bf16 peak: the whole scoring step's share of the chip."""
from chipbench.metrics.scorer_roofline import required_flops


def read(ctx):
    if not ctx.blocks or ctx.peaks is None:
        return None
    flops = sum(required_flops(ctx.config["oracle"], lens)
                for _, _, lens in ctx.blocks)
    return 100.0 * flops / ctx.window_s / (ctx.chips * ctx.peaks["bf16_flops"])
