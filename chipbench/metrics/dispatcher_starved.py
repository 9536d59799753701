"""Share of the time the window's queries ran in which the oracle service's
dispatcher was starved: waiting on an empty queue with a query attached
(the sum of its ``service.dispatcher.starved_ms`` series, recorded by the
traced run's tracker).  The tracker records from the window's start until
the last query started in it has ended, so the time is that span, from the
first query's start to the last one's end, and not ``window_s``.  Before the
window no query is attached, so no wait is counted from there."""


def read(ctx):
    if ctx.tracker is None or not ctx.queries:
        return None
    v = ctx.tracker.series.get("service.dispatcher.starved_ms", [])
    span_s = (max(q["end"] for q in ctx.queries)
              - min(q["start"] for q in ctx.queries))
    return 100.0 * sum(v) / 1e3 / span_s if v and span_s > 0 else None
