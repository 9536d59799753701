"""Median time a flush waited in the oracle service for its window to be
dispatched (the service's ``service.window.assembly_ms`` series, recorded
by the traced run's tracker during the window)."""
import statistics


def read(ctx):
    if ctx.tracker is None:
        return None
    v = ctx.tracker.series.get("service.window.assembly_ms", [])
    return statistics.median(v) if v else None
