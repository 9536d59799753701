"""Median estimate-and-interval time of the window's queries (bootstrap-t),
from the engine's ``timings["ci_s"]``."""
import statistics


def read(ctx):
    v = [q["timings"]["ci_s"] for q in ctx.queries if "ci_s" in q["timings"]]
    return statistics.median(v) * 1e3 if v else None
