"""Profiler trace: start, stop, and the reduction from events to numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, and events
with a start and a duration in nanoseconds, all on one clock.  Device
planes are named ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per execution of a compiled program (named
``jit__decode_step(<hash>)`` and so on) and the ``XLA Ops`` line one event
per operation, named by its HLO text (``%copy.88 = bf16[...] copy(...)``).
A Pallas kernel is an op whose text holds
``custom_call_target="tpu_custom_call"``; its kernel's name is not in the
trace.  Op events carry no module of their own: each is given the program
whose execution on the same device holds its start.  The host plane's
Python thread carries the benchmark's ``TraceAnnotation`` spans
(``bench.traced``, ``bench.step``, ``bench.submit``, ``bench.wait``)
beside JAX's own.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
TRACED = "bench.traced"
_CONTROL = ("%while", "%conditional", "%call")   # ops that hold other ops


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int       # ns
    end: int         # ns
    module: str = ""


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> XLA Ops events
    modules: Dict[str, List[Event]]    # device plane -> XLA Modules events
    host: List[Event]                  # the Python thread's spans

    # ---- construction ----
    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops, modules, host = {}, {}, []
        for plane in pd.planes:
            if _DEVICE.match(plane.name):
                raw = []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        raw = [_event(e) for e in line.events]
                    elif line.name == "XLA Modules":
                        modules[plane.name] = [_event(e) for e in line.events]
                ops[plane.name] = _in_modules(raw, modules.get(plane.name, []))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [_event(e) for e in line.events]
                    if any(e.name == TRACED for e in evs):
                        host = evs
        return cls(ops, modules, host)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_serialized_xspace(data))

    # ---- the traced window ----
    def span(self) -> Tuple[int, int]:
        """(start, end) ns of the benchmark's ``bench.traced`` annotation."""
        for e in self.host:
            if e.name == TRACED:
                return e.start, e.end
        raise ValueError("the trace holds no bench.traced span")

    def busy_window(self) -> Tuple[float, float]:
        """(busy seconds averaged over the devices, window seconds): busy
        is the union of the intervals in which an operation ran."""
        lo, hi = self.span()
        if not self.ops:
            raise ValueError("the trace holds no device operations")
        busy = [union_ns(evs, lo, hi) for evs in self.ops.values()]
        return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9

    # ---- programs and kernels ----
    def module_time(self, prefix: str) -> Tuple[float, int]:
        """(seconds, executions) of compiled programs whose name starts
        with ``prefix``, inside the traced window, summed over devices."""
        lo, hi = self.span()
        evs = [e for m in self.modules.values() for e in m
               if e.name.startswith(prefix) and lo <= e.start < hi]
        return sum(e.end - e.start for e in evs) / 1e9, len(evs)

    def op_time(self, names: Sequence[str],
                program: str = "") -> Tuple[float, int]:
        """(seconds, events) of device operations whose name contains any
        of ``names`` and whose program's name starts with ``program``,
        inside the traced window, summed over devices."""
        lo, hi = self.span()
        evs = [e for m in self.ops.values() for e in m
               if lo <= e.start < hi and e.module.startswith(program)
               and any(n in e.name for n in names)]
        return sum(e.end - e.start for e in evs) / 1e9, len(evs)

    # ---- breakdown ----
    def top_ops(self, k: int) -> List[List]:
        """The ``k`` device operations (by program and name) that took
        most time in the window; a loop or call, whose time is that of
        the operations inside it, is left out."""
        lo, hi = self.span()
        tot: Dict[str, int] = collections.Counter()
        for m in self.ops.values():
            for e in m:
                if lo <= e.start < hi and not e.name.startswith(_CONTROL):
                    name = short(e.name)
                    tot[f"{e.module}/{name}" if e.module else name] += (
                        e.end - e.start)
        return [[n, t / 1e9] for n, t in tot.most_common(k)]

    def idle_gaps(self, k: int) -> List[List]:
        """The ``k`` longest stretches of the window with no device
        operation, each named by the innermost harness span open at its
        middle and the innermost host span of any kind there."""
        lo, hi = self.span()
        gaps = []
        for evs in self.ops.values():
            t = lo
            for s, e in merged(evs, lo, hi):
                if s > t:
                    gaps.append((s - t, t, s))
                t = max(t, e)
            if hi > t:
                gaps.append((hi - t, t, hi))
        gaps.sort(reverse=True)
        out = []
        for dur, s, e in gaps[:k]:
            mid = (s + e) // 2
            open_ = [h for h in self.host
                     if h.start <= mid < h.end and h.name != TRACED]
            ours = [h for h in open_ if h.name.startswith("bench.")]
            names = [max(hs, key=lambda h: h.start).name
                     for hs in (ours, open_) if hs]
            label = " > ".join(dict.fromkeys(names)) or "none"
            out.append([label, dur / 1e9])
        return out


def _event(e) -> Event:
    stats = {k: v for k, v in e.stats} if e.stats is not None else {}
    module = stats.get("hlo_module", "")
    start = int(e.start_ns)
    return Event(e.name, start, start + int(e.duration_ns),
                 module if isinstance(module, str) else "")


def _in_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """``ops`` with each one's ``module`` set to the program (name without
    its ``(<hash>)``) whose execution holds the op's start."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if not e.module and i >= 0 and e.start < mods[i].end:
            e = dataclasses.replace(e, module=mods[i].name.split("(")[0])
        out.append(e)
    return out


def short(name: str) -> str:
    """An op's HLO text cut to its name, result shape and, for a custom
    call, its target: ``checkpoint.6 bf16[144,3,64] tpu_custom_call``."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    shape = "tuple" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    out = f"{head.lstrip('%')} {shape}"
    m = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{out} {m.group(1)}" if m else out


def merged(evs: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The intervals of ``evs`` clipped to [lo, hi) and merged."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in evs
                if e.end > lo and e.start < hi)
    out: List[Tuple[int, int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_ns(evs: Sequence[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(evs, lo, hi))


def start(log_dir: str) -> None:
    import jax

    jax.profiler.start_trace(log_dir)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def load(log_dir: str) -> Trace:
    with open(xplane_path(log_dir), "rb") as f:
        return Trace.from_bytes(f.read())
