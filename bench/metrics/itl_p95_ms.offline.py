"""itl_p95_ms.offline (ms): 95th percentile of the same gaps as
itl_p50_ms.  Host clock, over the whole window of the traced run.

In the offline cells this percentile falls among the ticks that also run
prefill chunks (each some 60 ms of device time), so it reads as a whole
number of chunks (four or five, 345 or 388 ms) by the seed's order: too
wide a swing for a bound, so it is a per-layer reading here."""

from bench import stats


def read(ctx):
    w = ctx.window
    xs = stats.itl_samples(w.records, w.t0, w.t1)
    return stats.percentile(xs, 95) * 1e3 if xs else None
