"""decode_rows_mean (rows): tokens produced by decode ticks in the window
(every token but a request's first, which prefill produces) over the
engine's ``decode_ticks`` counter across the same steps."""


def read(ctx):
    ticks = ctx.delta("decode_ticks")
    return ctx.window.tally.decode_tokens / ticks if ticks else None
