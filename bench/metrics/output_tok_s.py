"""output_tok_s (tokens/s): output tokens that reached the host in the
window, over the window's seconds.  Host clock."""

from bench import stats


def read(ctx):
    w = ctx.window
    return stats.output_rate(w.records, w.t0, w.t1)
