"""The scorer forward's share of its roofline: the least time the chip
could take for the window's scoring work (the larger of required FLOPs over
the bf16 peak and required bytes over HBM bandwidth, per block), over the
device time of the scorer's jitted program (``jit_fwd``) in the trace.

Required work counts the same thing whatever implements it: 2 x the
non-embedding parameters per real token, causal attention over each pair's
real length, and a 2-column head at the last real position.  Padding and
the full-vocabulary head that ``models.forward`` computes today are not
counted.  At these shapes the FLOP bound governs."""
import numpy as np

PROGRAM = "jit_fwd"


def dense_params(o: dict) -> int:
    d, hd = o["d_model"], o["head_dim"]
    attn = d * hd * (2 * o["num_heads"] + 2 * o["num_kv_heads"])
    return o["num_layers"] * (attn + 3 * d * o["d_ff"])


def required_flops(o: dict, lens) -> float:
    lens = np.asarray(lens, np.float64)
    per_tok = 2.0 * dense_params(o)
    attn = 4.0 * o["num_layers"] * o["num_heads"] * o["head_dim"] * (
        lens * (lens + 1) / 2)
    head = 2.0 * o["d_model"] * 2
    return float(per_tok * lens.sum() + attn.sum() + head * len(lens))


def required_bytes(o: dict, lens) -> float:
    """bf16 weights read once per block, the real tokens' embedding rows
    and the two head rows."""
    return 2.0 * (dense_params(o) + (float(np.sum(lens)) + 2) * o["d_model"])


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
