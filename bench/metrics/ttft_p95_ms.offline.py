"""ttft_p95_ms.offline (ms): 95th percentile, over every request due in the
window, of due time to first token on the host; a request still waiting
when the window closes counts with its wait so far.  Host clock, over the
whole window of the traced run.

In the closed offline loop only some forty requests are due in a window,
and which prompts they are follows the seed's order, so this tail swings
by a quarter from seed to seed: too wide for a bound, so it is a
per-layer reading here and not an end-to-end metric."""

from bench import stats


def read(ctx):
    w = ctx.window
    return stats.ttft_ms(w.records, w.t0, w.t1, 95)
