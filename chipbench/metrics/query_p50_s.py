"""Median latency of the queries started inside the window, each timed to
its answer (also where that comes after the window closed)."""
import numpy as np


def read(ctx):
    lat = [q["seconds"] for q in ctx.queries]
    return float(np.quantile(lat, 0.5)) if lat else None
