"""The sim_sweep kernel's share of its roofline: 2 n1 n2 d FLOPs per sweep,
and the bytes of both tables in and of the count tiles, top-k and row sums
out, against the bf16 peak and HBM bandwidth (no float32 matrix peak is
published), over the device time of the ``sim_sweep`` kernel in the trace."""
KERNEL = "sim_sweep"


def flops(s: dict) -> float:
    return 2.0 * s["n1"] * s["n2"] * s["d"]


def bytes_moved(s: dict, block_rows: int = 256) -> float:
    tiles = -(-s["n1"] // block_rows) * s["n_bins"] * 4
    return 4.0 * (s["n1"] + s["n2"]) * s["d"] + tiles + s["n1"] * (8 * s["k"] + 4)


def read(ctx):
    if ctx.trace is None or not ctx.sweeps or ctx.peaks is None:
        return None
    busy, n = ctx.trace.op_s(KERNEL), ctx.trace.op_count(KERNEL)
    if busy <= 0 or n == 0:
        return None
    p = ctx.peaks
    # the trace's kernel events, each one sweep of the window's shape
    s = ctx.sweeps[-1]
    least = max(flops(s) / p["bf16_flops"], bytes_moved(s) / p["hbm_bytes_per_s"])
    return 100.0 * n * least / busy
