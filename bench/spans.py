"""The engine's tick phases in a profiler trace.

The program wraps each phase of ``Engine.step`` in a ``TraceAnnotation``
named ``engine.<phase>``, from the table ``repro.serve.telemetry.PHASES``.
They sit on the Python thread's line (``Trace.host``) beside the
harness's ``bench.step`` around the same call.  A decode tick is an
``engine.tick`` that holds an ``engine.readback``; the host waits for
the device inside ``engine.readback`` and ``engine.prefill_wait``, and
the rest of the tick is the host's own work.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import List, Optional

from bench import trace as tracemod
from bench.harness import BenchError

TICK = "engine.tick"
READBACK = "engine.readback"
PREFILL_WAIT = "engine.prefill_wait"
STEP = "bench.step"


@dataclasses.dataclass(frozen=True)
class Tick:
    start: int       # ns
    end: int         # ns
    waited: int      # ns of engine.readback and engine.prefill_wait in it

    @property
    def host(self) -> int:
        return self.end - self.start - self.waited


def _program_names() -> Optional[set]:
    """The profiler names the program gives its tick phases; None for a
    program that has no such table (one from before the phases)."""
    from repro.serve import telemetry

    table = getattr(telemetry, "PHASES", None)
    return None if table is None else set(table.values())


def decode_ticks(trace) -> Optional[List[Tick]]:
    """The decode ticks of the traced window: ``engine.tick`` spans that
    start and end inside it and hold an ``engine.readback``.  None without
    a trace or where the program has no tick phases.  A program that
    names its phases otherwise, or a traced window in which the engine
    stepped and no ``engine.tick`` shows, is an error: a span was renamed
    or never reached the trace."""
    if trace is None:
        return None
    names = _program_names()
    if names is None:
        return None
    missing = {TICK, READBACK, PREFILL_WAIT} - names
    if missing:
        raise BenchError(f"the program's tick phases name no "
                         f"{sorted(missing)}")
    lo, hi = trace.span()
    inside = [e for e in trace.host if lo <= e.start and e.end <= hi]
    ticks = sorted((e for e in inside if e.name == TICK),
                   key=lambda e: e.start)
    if not ticks:
        if any(e.name == STEP for e in inside):
            raise BenchError(f"the engine stepped in the traced window but "
                             f"the trace holds no {TICK} span")
        return None
    starts = [t.start for t in ticks]
    waited = [0] * len(ticks)
    decode = [False] * len(ticks)
    for e in inside:
        if e.name not in (READBACK, PREFILL_WAIT):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.end > ticks[i].end:
            continue
        waited[i] += e.end - e.start
        decode[i] = decode[i] or e.name == READBACK
    return [Tick(t.start, t.end, w)
            for t, w, d in zip(ticks, waited, decode) if d]


def mean_tick_idle_ns(trace, ticks: List[Tick]) -> float:
    """Device-idle nanoseconds inside each of ``ticks`` (the tick's span
    less the union of device operations in it), averaged over the ticks
    and the devices."""
    lo, hi = trace.span()
    total = 0
    for evs in trace.ops.values():
        busy = tracemod.merged(evs, lo, hi)
        starts = [s for s, _ in busy]
        for t in ticks:
            covered = 0
            j = max(0, bisect.bisect_right(starts, t.start) - 1)
            while j < len(busy) and busy[j][0] < t.end:
                s, e = busy[j]
                covered += max(0, min(e, t.end) - max(s, t.start))
                j += 1
            total += (t.end - t.start) - covered
    return total / len(trace.ops) / len(ticks)
