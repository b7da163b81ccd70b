"""prefill_us_per_tok (us/tok): device time of the compiled prefill-chunk
program in the traced window over the prompt tokens prefilled across the
traced steps (the engine's ``prefill_tokens`` counter, which counts a
request's uncached prompt tokens when its prefill completes).  The
program's name is checked by ``decode_step_ms``'s reader, which reads a
name of the same kind off the same trace."""

#: compiled program, as the device trace names it
NAMES = ("jit__prefill_chunk",)


def read(ctx):
    tr = ctx.window.trace
    if tr is None:
        return None
    secs, n = tr.module_time(NAMES[0])
    toks = ctx.delta("prefill_tokens", traced=True)
    return secs / toks * 1e6 if n and toks > 0 else None
