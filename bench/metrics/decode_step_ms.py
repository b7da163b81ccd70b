"""decode_step_ms (ms): device time of one execution of the compiled decode
program, averaged over its executions in the traced window.  A trace
with device operations, of a window in which decode ticks ran, but no
program of that name means the name is stale: an error, not a silent
gap."""

from bench.harness import BenchError

#: compiled program, as the device trace names it
NAMES = ("jit__decode_step",)


def read(ctx):
    w = ctx.window
    if w.trace is None:
        return None
    secs, n = w.trace.module_time(NAMES[0])
    if not n and w.trace.ops and any(w.trace_tally.step_ctxs):
        raise BenchError(f"decode ran in the traced window but the trace "
                         f"holds no {NAMES[0]} program")
    return secs / n * 1e3 if n else None
