"""Share of the rows the grouped expert matmul computed that were no routed
real token: 100 x (1 - routed_rows / expert_rows) from the scorer's
counters over the window (tile rounding, and a tile shared by two experts
computed once for each).  Nothing to read where the scorer counts no expert
rows."""


def read(ctx):
    c = ctx.scorer_counters
    if not c.get("expert_rows"):
        return None
    return 100.0 * (1.0 - c["routed_rows"] / c["expert_rows"])
