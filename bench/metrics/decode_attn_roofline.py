"""decode_attn_roofline (%): the least time the chip needs for the decode
attention work of the traced ticks, over the summed device time of the
paged decode kernel's events in the traced window.

The work is what the active rows need: each row reads its own context's
K and V once, and q and the output once per layer
(``bench.flops.decode_attn_work``); the least time is the larger of bytes
over HBM bandwidth and operations over the bf16 peak."""

from bench import flops
from bench.harness import BenchError

#: the paged decode kernel, as the device trace names it: the one Pallas
#: call (a ``tpu_custom_call``) in the decode program, read off a chip
#: trace of each arm (``tests/bench/data/decode_trace.xplane.pb.gz``)
NAMES = ('custom_call_target="tpu_custom_call"',)
#: the compiled decode program that runs it
PROGRAM = "jit__decode_step"


def read(ctx):
    w = ctx.window
    if w.trace is None or w.trace_tally is None:
        return None
    secs, n = w.trace.op_time(NAMES, PROGRAM)
    if not n:
        if w.trace.module_time(PROGRAM)[1]:
            raise BenchError(f"the decode program ran in the traced window "
                             f"but no operation is named like {NAMES}")
        return None
    least = 0.0
    for ctxs in w.trace_tally.step_ctxs:
        if ctxs:
            least += flops.least_time(*flops.decode_attn_work(ctx.config, ctxs),
                                      ctx.peaks)
    return 100.0 * least / secs if least else None
