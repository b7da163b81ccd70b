"""Host-clock arithmetic of the end-to-end metrics.

Every request the harness offers gets a :class:`Record`: when it was due,
when it was handed to the engine, and when each of its tokens reached the
host (stamped in ``Request.on_token``, after the device-to-host readback).
The functions here reduce a list of records and a window ``[t0, t1)`` to
the numbers the benchmark reports.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass(eq=False)
class Record:
    rid: int
    prompt_len: int
    due: float                        # host clock: schedule (open loop)
                                      # or submit time (closed loop)
    submitted: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    truncated: bool = False


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default method): the
    value at rank q/100 · (n - 1) of the sorted sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def due_in(records: Sequence[Record], t0: float, t1: float) -> List[Record]:
    return [r for r in records if t0 <= r.due < t1]


def ttft_samples(records: Sequence[Record], t0: float,
                 t1: float) -> List[float]:
    """Seconds from due to first token, for every request due in the
    window.  A request still waiting for its first token when the window
    closes counts with its wait so far (``t1 - due``), so a stall shows
    instead of dropping out."""
    out = []
    for r in due_in(records, t0, t1):
        first = r.times[0] if r.times else math.inf
        out.append(min(first, t1) - r.due)
    return out


def ttft_ms(records: Sequence[Record], t0: float, t1: float,
            q: float) -> Optional[float]:
    """Percentile ``q`` of :func:`ttft_samples` in milliseconds, or None
    where no request is due in the window."""
    xs = ttft_samples(records, t0, t1)
    return percentile(xs, q) * 1e3 if xs else None


def waiting_at(records: Sequence[Record], t: float) -> int:
    """Requests due by ``t`` that had no first token by ``t``: the queue
    in front of prefill."""
    return sum(1 for r in records
               if r.due <= t and not (r.times and r.times[0] <= t))


def itl_samples(records: Sequence[Record], t0: float,
                t1: float) -> List[float]:
    """Seconds between consecutive tokens of one request, for every gap
    whose both tokens arrived inside the window."""
    out = []
    for r in records:
        ts = r.times
        for a, b in zip(ts, ts[1:]):
            if a >= t0 and b < t1:
                out.append(b - a)
    return out


def tokens_in(records: Sequence[Record], t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.times if t0 <= t < t1)


def output_rate(records: Sequence[Record], t0: float, t1: float) -> float:
    """Output tokens that reached the host in the window, per second."""
    return tokens_in(records, t0, t1) / (t1 - t0)
