"""Median time a window query spent blocked on the oracle service's answers
(the engine's ``timings["oracle_wait_s"]``: the sum of the query's
``oracle.wait`` spans), in ms."""
import statistics


def read(ctx):
    v = [q["timings"]["oracle_wait_s"] for q in ctx.queries
         if "oracle_wait_s" in q["timings"]]
    return statistics.median(v) * 1e3 if v else None
