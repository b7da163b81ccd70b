"""device_idle_share (%): share of the traced window in which no operation
ran on the device: 1 - (union of device-op intervals / window)."""


def read(ctx):
    tr = ctx.window.trace
    if tr is None or not tr.ops:
        return None
    busy, span = tr.busy_window()
    return 100.0 * (1.0 - busy / span) if span > 0 else None
