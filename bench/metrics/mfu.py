"""mfu (%): model FLOPs of every token the engine processed in the window
over the window's seconds times the chip's bf16 peak.  The host seconds
spent starting and stopping the profiler in a traced run are left out of
the window, since the engine does no work while they last.

Per token: two FLOPs per weight of the matrix products, plus 4·L·H·d per
key attended.  Decode tokens attend over their exact context.  Prefilled
tokens are the uncached tail of each prompt whose prefill completed in
the window; each prompt is taken to have the window's share of cached
tokens (``prefix_hit_tokens`` over all prompt tokens admitted)."""

from bench import flops


def read(ctx):
    c, t = ctx.config, ctx.window.tally
    prefill = ctx.delta("prefill_tokens")
    hit = ctx.delta("prefix_hit_tokens")
    total = prefill + hit
    miss = prefill / total if total else 0.0
    keys = float(t.decode_ctx)
    for n in t.first_prompt_lens:
        a = n - round(n * miss)               # first uncached position
        keys += (n * (n + 1) - a * (a + 1)) / 2  # sum of (p + 1), p in [a, n)
    work = (flops.matmul_flops_per_token(c) * (prefill + t.decode_tokens)
            + float(flops.attn_flops(c, keys)))
    if work <= 0:
        return None
    seconds = ctx.seconds - ctx.window.profiler_s
    return 100.0 * work / (seconds * ctx.peaks["bf16_flop_s"])
