"""tick_idle_ms (ms): device-idle time inside a decode tick: over the
ticks ``tick_host_ms`` reads, the part of each ``engine.tick`` span in
which no operation ran on the device, averaged per tick and device.  The
part of the host's work the device waited for."""

from bench import spans


def read(ctx):
    tr = ctx.window.trace
    ticks = spans.decode_ticks(tr)
    if not ticks or not tr.ops:
        return None
    return spans.mean_tick_idle_ns(tr, ticks) / 1e6
