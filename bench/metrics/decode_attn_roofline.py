"""decode_attn_roofline (%): the least time the chip needs for the decode
attention work of the traced ticks, over the summed device time of the
paged decode kernel's events in the traced window.

The work is what the active rows need: each row reads its own context's
K and V once, and q and the output once per layer
(``bench.flops.decode_attn_work``); the least time is the larger of bytes
over HBM bandwidth and operations over the bf16 peak.

The kernel is a Pallas call (a ``tpu_custom_call``) of the decode program
whose name path holds ``jax.named_scope(SCOPE)``.  Where no call carries
that scope (the program as it stands names none), the kernel is the
decode program's one Pallas call: each execution of the program may run
one such op, once per layer of the scan, and an execution that runs two
(an expert kernel beside attention) raises, since attention's could not
be told apart."""

import bisect

from bench import flops
from bench.harness import BenchError

#: a Pallas call, as the device trace names it
NAMES = ('custom_call_target="tpu_custom_call"',)
#: the compiled decode program that runs it
PROGRAM = "jit__decode_step"
#: the named scope around the paged decode kernel's ``pallas_call``
SCOPE = "paged_decode_attention"


def kernel_events(trace):
    """The paged decode kernel's events in the traced window, over all
    devices (see the module's docstring)."""
    per_dev = trace.op_events(NAMES, PROGRAM)
    evs = [e for m in per_dev.values() for e in m]
    scoped = [e for e in evs if SCOPE in e.path]
    if scoped or not evs:
        return scoped
    for dev, ops in per_dev.items():
        runs = sorted(m.start for m in trace.modules.get(dev, [])
                      if m.name.startswith(PROGRAM))
        kinds = {}
        for e in ops:
            kinds.setdefault(bisect.bisect_right(runs, e.start), set()).add(
                e.name)
        if any(len(k) > 1 for k in kinds.values()):
            raise BenchError(f"the decode program runs several Pallas calls "
                             f"and none is under the {SCOPE!r} scope")
    return evs


def read(ctx):
    w = ctx.window
    if w.trace is None or w.trace_tally is None:
        return None
    evs = kernel_events(w.trace)
    if not evs:
        if w.trace.module_time(PROGRAM)[1]:
            raise BenchError(f"the decode program ran in the traced window "
                             f"but no operation is named like {NAMES}")
        return None
    secs = sum(e.end - e.start for e in evs) / 1e9
    least = 0.0
    for ctxs in w.trace_tally.step_ctxs:
        if ctxs:
            least += flops.least_time(*flops.decode_attn_work(ctx.config, ctxs),
                                      ctx.peaks)
    return 100.0 * least / secs if least else None
