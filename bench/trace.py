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
trace.  Each op's metadata on the device plane carries its name path
(the ``tf_op`` stat, e.g.
``jit(_decode_step)/while/body/closed_call/checkpoint/pallas_call``, with
any ``jax.named_scope`` around the call in it), which ``ProfileData`` does
not expose: :func:`op_paths` reads it from the serialized trace.  Op
events carry no module of their own: each is given the program whose
execution on the same device holds its start.  The host plane's
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
    path: str = ""   # an op's name path, where the trace gives one


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> XLA Ops events
    modules: Dict[str, List[Event]]    # device plane -> XLA Modules events
    host: List[Event]                  # the Python thread's spans

    # ---- construction ----
    @classmethod
    def from_profile(cls, pd, paths=None) -> "Trace":
        """``paths``: plane -> op name -> name path (:func:`op_paths`)."""
        ops, modules, host = {}, {}, []
        for plane in pd.planes:
            if _DEVICE.match(plane.name):
                raw = []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        named = (paths or {}).get(plane.name, {})
                        raw = [_event(e, named) for e in line.events]
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

        return cls.from_profile(ProfileData.from_serialized_xspace(data),
                                op_paths(data))

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

    def op_events(self, names: Sequence[str],
                  program: str = "") -> Dict[str, List[Event]]:
        """Per device, the operations whose name contains any of ``names``
        and whose program's name starts with ``program``, inside the
        traced window."""
        lo, hi = self.span()
        return {dev: [e for e in m if lo <= e.start < hi
                      and e.module.startswith(program)
                      and any(n in e.name for n in names)]
                for dev, m in self.ops.items()}

    def op_time(self, names: Sequence[str],
                program: str = "") -> Tuple[float, int]:
        """(seconds, events) of :meth:`op_events`, summed over devices."""
        evs = [e for m in self.op_events(names, program).values() for e in m]
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


def _event(e, paths=None) -> Event:
    stats = {k: v for k, v in e.stats} if e.stats is not None else {}
    module = stats.get("hlo_module", "")
    start = int(e.start_ns)
    return Event(e.name, start, start + int(e.duration_ns),
                 module if isinstance(module, str) else "",
                 (paths or {}).get(e.name, ""))


# ---- the serialized trace (tsl/profiler/protobuf/xplane.proto) ----

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message; a length-delimited
    value is a memoryview into ``buf``, so a skipped field costs nothing."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, v


def _map_values(entries) -> Dict[int, memoryview]:
    """A protobuf map<int64, message>: key (field 1) -> value (field 2)."""
    out = {}
    for entry in entries:
        kv = dict(_fields(entry))
        out[kv.get(1, 0)] = kv.get(2, memoryview(b""))
    return out


def op_paths(data: bytes) -> Dict[str, Dict[str, str]]:
    """Device plane -> op name -> the op's name path (the ``tf_op`` stat
    of its event metadata, without the trailing ``:<type>``).  XSpace
    field 1 is its planes; XPlane 2 its name, 4 its event metadata and 5
    its stat metadata; XEventMetadata 2 its name and 5 its stats; an
    XStat holds its metadata id in 1 and a string in 5, or in 7 as the id
    of a stat metadata whose name is the string."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats.append(v)
        if not _DEVICE.match(name):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_values(stats).items()}
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        named = {}
        for meta in _map_values(events).values():
            fields = list(_fields(meta))
            op = next((bytes(v).decode() for f, v in fields if f == 2), "")
            for f, v in fields:
                stat = dict(_fields(v)) if f == 5 else {}
                if tf_op and stat.get(1) == tf_op[0]:
                    path = (bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), ""))
                    named[op] = path.rpartition(":")[0] or path
        out[name] = named
    return out


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
