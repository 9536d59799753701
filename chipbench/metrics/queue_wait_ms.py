"""Median time a flush sat in the oracle service's queue before the
dispatcher took it into a window (the service's
``service.window.queue_ms`` series, recorded by the traced run's tracker):
the part of ``window_wait_ms`` spent behind the window already in flight,
not waiting for more clients to join."""
import statistics


def read(ctx):
    if ctx.tracker is None:
        return None
    v = ctx.tracker.series.get("service.window.queue_ms", [])
    return statistics.median(v) if v else None
