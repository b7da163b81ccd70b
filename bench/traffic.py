"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests.

Two loops, chosen by the mix's ``loop`` key:

* ``closed``: ``concurrency`` requests are in flight at all times; the
  harness asks for the next one the moment one finishes.  The first wave
  draws *residual* output lengths (a uniform fraction of a full draw), so
  completions are spread out from the start.
* ``open``: sessions start at ``rate_sessions_s`` on average, the gaps
  between starts exponential (a Poisson process) or, with ``burst_cv``,
  gamma with that coefficient of variation (bursty arrivals).  A session
  has a number of turns; turn k's prompt is the shared system prompt,
  then every earlier message and served answer (without them when
  ``history`` is false), then the new message.  Turn k+1 is due
  ``think`` seconds after turn k finished.  A session ends when its next
  turn would not fit ``max_len``.  With ``shared`` false every prompt's
  tokens are drawn afresh at the same length, so nothing is shared
  between prompts: the prefix cache's control.

Every random size is drawn the same way: in blocks of ``block`` draws,
each block holds the distribution's quantiles at (i + 0.5) / block in an
order shuffled by the seed.  So every seed offers the same set of sizes
and gaps, in another order, and seeds differ in order and token ids, not
in the amount of work.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(eq=False)
class Req:
    """One request as the generator hands it out."""
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new: int
    due: Optional[float]          # seconds after generator start; None: now
    session: int = -1
    turn: int = 0


def quantile(dist: dict, p: float) -> float:
    """Quantile ``p`` of a length or gap distribution, clipped to its
    ``min``/``max``.  ``lognormal`` (``median``, ``sigma``),
    ``exponential`` (``mean``), ``gamma`` (``mean`` and ``cv``, the
    coefficient of variation: 1 is exponential, above 1 bursty),
    ``uniform_int`` (``min``..``max``), and ``mixture`` (``parts``, each a
    distribution with a ``weight``: the share ``weight`` of the quantiles
    comes from that part)."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
    elif kind == "exponential":
        v = -dist["mean"] * math.log1p(-p)
    elif kind == "gamma":
        from scipy.special import gammaincinv

        shape = 1.0 / dist["cv"] ** 2
        v = float(gammaincinv(shape, p)) * dist["mean"] / shape
    elif kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        return float(min(hi, lo + int(p * (hi - lo + 1))))
    elif kind == "mixture":
        total = sum(part["weight"] for part in dist["parts"])
        acc = 0.0
        for part in dist["parts"]:
            w = part["weight"] / total
            if p < acc + w or part is dist["parts"][-1]:
                v = quantile(part, min(max((p - acc) / w, 0.0), 1.0 - 1e-12))
                break
            acc += w
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        v = max(v, dist["min"])
    if "max" in dist:
        v = min(v, dist["max"])
    return v


class Stratified:
    """Endless draws of one distribution, in seed-shuffled blocks of its
    quantiles at (i + 0.5) / block."""

    def __init__(self, dist: dict, block: int, rng: np.random.Generator,
                 integer: bool = True):
        self._vals = [quantile(dist, (i + 0.5) / block) for i in range(block)]
        if integer:
            self._vals = [int(round(v)) for v in self._vals]
        self._rng = rng
        self._buf: List = []

    def __call__(self):
        if not self._buf:
            self._buf = [self._vals[i]
                         for i in self._rng.permutation(len(self._vals))]
        return self._buf.pop()


class Traffic:
    """Seeded request source for one mix.  Times are seconds after the
    generator's start, which the harness sets when the ramp begins."""

    def __init__(self, mix: dict, *, vocab: int, max_len: int, seed: int):
        self.mix = mix
        self.closed = mix["loop"] == "closed"
        if mix["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.vocab, self.max_len = vocab, max_len
        ss = np.random.SeedSequence(seed)
        streams = [np.random.default_rng(s) for s in ss.spawn(8)]
        self._tok = streams[0]
        block = mix.get("block", 64)
        self._prompt = Stratified(mix["prompt"], block, streams[1])
        self._output = Stratified(mix["output"], block, streams[2])
        self._next_id = 0
        if self.closed:
            self.concurrency = mix["concurrency"]
            self._residual = Stratified(
                {"dist": "uniform_int", "min": 1, "max": block}, block,
                streams[3])
            self._block = block
            return
        self.rate = mix["rate_sessions_s"]
        cv = mix.get("burst_cv", 1.0)
        self._gap = Stratified(
            {"dist": "exponential", "mean": 1.0 / self.rate} if cv == 1.0
            else {"dist": "gamma", "mean": 1.0 / self.rate, "cv": cv},
            block, streams[3], integer=False)
        self.history = mix.get("history", True)
        self.shared = mix.get("shared", True)
        self._turns = Stratified(mix["turns"], block, streams[4])
        self._think = Stratified(mix["think_s"], block, streams[5],
                                 integer=False)
        self.system = self._tokens(mix.get("system_prompt", 0))
        self._t_next_session = self._gap()
        self._session = 0
        # requests not yet due; a session's later turns join on finish
        self._pending: List[Req] = []
        self._sessions: Dict[int, dict] = {}

    def _tokens(self, n: int) -> np.ndarray:
        return self._tok.integers(0, self.vocab, n, dtype=np.int32)

    def _new_id(self) -> int:
        self._next_id += 1
        return self._next_id - 1

    def _fit(self, prompt_len: int, want: int) -> int:
        """Cap a closed-loop request so prompt + output fits ``max_len``."""
        return max(1, min(want, self.max_len - prompt_len))

    # ---- closed loop ----
    def first_wave(self) -> List[Req]:
        """``concurrency`` requests with residual output lengths."""
        out = []
        for _ in range(self.concurrency):
            prompt = self._tokens(self._prompt())
            frac = self._residual() / self._block
            want = max(1, math.ceil(frac * self._output()))
            out.append(Req(self._new_id(), prompt,
                           self._fit(len(prompt), want), None))
        return out

    def next_closed(self) -> Req:
        prompt = self._tokens(self._prompt())
        return Req(self._new_id(), prompt,
                   self._fit(len(prompt), self._output()), None)

    # ---- open loop ----
    def _start_session(self, due: float) -> None:
        """Draw a whole session when it arrives (turn count, messages,
        answer lengths, think times), so what a session asks does not
        depend on when earlier requests finished."""
        s = self._session
        self._session += 1
        turns = [(self._tokens(self._prompt()), self._output(), self._think())
                 for _ in range(self._turns())]
        self._sessions[s] = {"history": self.system, "turns": turns, "k": 0}
        self._turn(s, due)

    def _turn(self, session: int, due: float) -> None:
        """Queue the session's next turn, or end the session when the
        turn would not fit ``max_len``."""
        st = self._sessions[session]
        msg, answer, _ = st["turns"][st["k"]]
        prompt = np.concatenate([st["history"], msg])
        if len(prompt) + answer > self.max_len:
            del self._sessions[session]
            return
        st["history"] = prompt if self.history else self.system
        st["k"] += 1
        if not self.shared:
            prompt = self._tokens(len(prompt))
        self._pending.append(Req(self._new_id(), prompt, answer, due,
                                 session, st["k"]))

    def due(self, t: float) -> List[Req]:
        """Open loop: every request due at or before ``t``, in due order."""
        while self._t_next_session <= t:
            self._start_session(self._t_next_session)
            self._t_next_session += self._gap()
        ready = sorted((r for r in self._pending if r.due <= t),
                       key=lambda r: r.due)
        self._pending = [r for r in self._pending if r.due > t]
        return ready

    def next_due(self) -> float:
        pend = min((r.due for r in self._pending), default=math.inf)
        return min(pend, self._t_next_session)

    def on_finish(self, req: Req, t: float, output: List[int]) -> None:
        """Open loop: queue the session's next turn, due ``think`` seconds
        after this one finished at ``t``."""
        st = None if self.closed else self._sessions.get(req.session)
        if st is None:
            return
        if st["k"] == len(st["turns"]):
            del self._sessions[req.session]
            return
        think = st["turns"][st["k"] - 1][2]
        if self.history:
            st["history"] = np.concatenate(
                [st["history"], np.asarray(output, np.int32)])
        self._turn(req.session, t + think)
