"""The routed experts' share of their roofline: the least time the chip
could take for the window's routed expert work, over the device time of
the grouped expert matmul (megablox's ``gmm`` kernel) in the trace.

Per block, the work is 2 x 3 x d_model x moe_d_ff FLOPs for each routed real
row (real tokens x ``num_experts_per_tok`` x MoE layers), and the bytes the
experts' bf16 weights once per MoE layer (every expert is hit at these
token counts); the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth.  Pad tokens, routed nowhere, and the kernel's tile rounding are
not work.  Nothing to read in a run whose oracle has no routed experts."""
KERNEL = "gmm"


def read(ctx):
    o = ctx.config["oracle"]
    if ctx.trace is None or not ctx.blocks or ctx.peaks is None or not o.get("moe_d_ff"):
        return None
    busy = ctx.trace.op_s(KERNEL)
    if busy <= 0:
        return None
    p = ctx.peaks
    layers = o["num_layers"] - o.get("first_k_dense", 0)
    matrices = 3 * o["d_model"] * o["moe_d_ff"]
    flops_per_row = 2.0 * matrices
    weights = 2.0 * o["num_experts"] * matrices * layers
    least = sum(max(flops_per_row * o["num_experts_per_tok"] * layers * float(lens.sum())
                    / p["bf16_flops"], weights / p["hbm_bytes_per_s"])
                for _, _, lens in ctx.blocks)
    return 100.0 * least / busy
