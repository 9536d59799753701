"""90th percentile (linear interpolation) of the same latencies as
query_p50_s."""
import numpy as np


def read(ctx):
    lat = [q["seconds"] for q in ctx.queries]
    return float(np.quantile(lat, 0.9)) if lat else None
