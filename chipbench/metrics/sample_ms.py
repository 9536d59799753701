"""Median time a window query spent drawing its stratum samples on the host
(the engine's ``timings["sample_s"]``: the sum of the query's ``sample``
spans, the D0 walk sampler included), in ms."""
import statistics


def read(ctx):
    v = [q["timings"]["sample_s"] for q in ctx.queries
         if "sample_s" in q["timings"]]
    return statistics.median(v) * 1e3 if v else None
