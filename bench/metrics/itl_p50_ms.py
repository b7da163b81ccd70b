"""itl_p50_ms (ms): median gap between consecutive tokens of one request,
over every gap inside the window.  Host clock."""

from bench import stats


def read(ctx):
    w = ctx.window
    xs = stats.itl_samples(w.records, w.t0, w.t1)
    return stats.percentile(xs, 50) * 1e3 if xs else None
