"""queue_wait_ms (ms): mean time a request waited from ``Engine.submit``
to leaving the scheduler for a slot, over the requests admitted in the
window: the engine's ``queue_wait_us`` counter over its
``admitted_requests`` counter, both as differences across the window.  A
program without the counters reads nothing."""

KEYS = ("queue_wait_us", "admitted_requests")


def read(ctx):
    if any(k not in ctx.window.counters1 for k in KEYS):
        return None
    n = ctx.delta("admitted_requests")
    return ctx.delta("queue_wait_us") / n / 1e3 if n else None
