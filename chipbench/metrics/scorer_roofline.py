"""The scorer forward's share of its roofline: the least time the chip
could take for the window's scoring work (the larger of required FLOPs over
the bf16 peak and required bytes over HBM bandwidth, per block), over the
device time of the scorer's jitted program (``jit_fwd``) in the trace.

Required work is counted by the oracle's family (``chipbench/oracles/``),
the same whatever implements it: padding and the full-vocabulary head that
``models.forward`` computes today are not counted.  At these shapes the
FLOP bound governs."""
from chipbench import oracles

PROGRAM = "jit_fwd"


def required_flops(o: dict, lens) -> float:
    return oracles.load(o["family"]).required_flops(o, lens)


def required_bytes(o: dict, lens) -> float:
    return oracles.load(o["family"]).required_bytes(o, lens)


def read(ctx):
    if ctx.trace is None or not ctx.blocks:
        return None
    busy = ctx.trace.program_s(PROGRAM)
    if busy <= 0:
        return None
    p, o = ctx.peaks, ctx.config["oracle"]
    least = sum(max(required_flops(o, lens) / p["bf16_flops"],
                    required_bytes(o, lens) / p["hbm_bytes_per_s"])
                for _, _, lens in ctx.blocks)
    return 100.0 * least / busy
