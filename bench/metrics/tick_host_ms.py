"""tick_host_ms (ms): the host's own work per decode tick.  Over the
``engine.tick`` spans that start and end inside the traced window and
hold an ``engine.readback``, the mean of each tick's duration less its
``engine.readback`` and ``engine.prefill_wait`` spans, in which the host
waited for the device (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    ticks = spans.decode_ticks(ctx.window.trace)
    if not ticks:
        return None
    return sum(t.host for t in ticks) / len(ticks) / 1e6
