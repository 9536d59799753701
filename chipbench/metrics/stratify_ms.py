"""Median stratification time of the window's queries, from the query
engine's own ``timings["stratify_s"]`` (sweep or index lookup, threshold,
collection of the blocking regime)."""
import statistics


def read(ctx):
    v = [q["timings"]["stratify_s"] for q in ctx.queries
         if "stratify_s" in q["timings"]]
    return statistics.median(v) * 1e3 if v else None
