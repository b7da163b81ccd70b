"""Serve one cell through the program's ``Engine`` and measure it.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<mix>.json``.  Each metric is read by
``bench/metrics/<metric>.py``.  Adding any of them is adding a file and
an entry; nothing here names a cell, a mix or a metric.

The program is reached only through ``repro.launch.serve.build_engine``,
``repro.configs.get_config``, ``repro.serve.engine.EngineConfig`` and
``Request`` (with ``on_token``), and ``Engine.submit``, ``step``,
``stats`` and ``params``.

One run: build the engine (set-up), put the benchmark's seeded weights
into it in place of the program's own (``weights.place``), ramp the
traffic up, measure for ``seconds``, free the engine, then compare a
sample of the served requests with the plain reference.  With ``trace``
a profiler trace covers a few seconds in the middle of the window and
the per-layer metrics are read from it.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import check, stats, traffic, weights

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

RAMP_LIMIT_S = 300.0     # a ramp that has not filled the slots by then fails
TRACE_S = 10.0           # traced seconds in the middle of the window: long
                         # enough that some prefills complete in it


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad spec, stuck ramp)."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve a cell of ``root/BENCHMARK.json`` to its files."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(name=name, config_name=w["config"],
                config=_json(root / cfg["file"]),
                traffic_name=w["traffic"],
                mix=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                chips=w["chips"],
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The module ``bench/metrics/<metric>.py``; its ``read(ctx)`` returns
    the metric's value, or None when the run has nothing to read."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    table = _json(root / "bench" / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def _dotted(obj, path: str):
    for part in path.split("."):
        obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
    return obj


def _check_program(c: dict) -> None:
    """The program's model must be the configuration as stated: each key
    of the configuration's ``program_fields`` (a dotted path into the
    configuration) equals the program's field it names, and each field
    of ``program_block`` holds the value given there."""
    from repro.configs import get_config

    p = get_config(c["program"])
    if c.get("program_reduced"):
        p = p.reduced()
    odd = {}
    for key, field in c["program_fields"].items():
        if _dotted(c, key) != _dotted(p, field):
            odd[key] = (_dotted(c, key), _dotted(p, field))
    for field, want in c["program_block"].items():
        if _dotted(p, field) != want:
            odd[field] = (want, _dotted(p, field))
    if odd:
        raise BenchError(f"the program's {c['program']!r} differs from the "
                         f"configuration: {odd}")


def build_engine(c: dict, seed: int):
    """The engine as the configuration deploys it, holding the
    benchmark's seeded weights."""
    from repro.launch.serve import build_engine as program_build
    from repro.serve.engine import EngineConfig

    _check_program(c)
    e = c["engine"]
    small_seed = int(weights.key_data(seed)[0] & 0x7FFFFFFF)
    eng = program_build(
        c["program"],
        EngineConfig(max_batch=e["max_batch"], max_len=e["max_len"],
                     tick_budget=e["tick_budget"],
                     prefix_cache=e["prefix_cache"], greedy=True,
                     warmup=e["warmup"]),
        reduced=bool(c.get("program_reduced")), seed=small_seed)
    weights.place(c, seed, eng)
    return eng


# ---------------------------------------------------------------------------
# Driving the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tally:
    """Work the engine did over a stretch of steps, as the host saw it."""
    decode_tokens: int = 0         # tokens produced by decode ticks
    decode_ctx: int = 0            # keys those tokens attended over
    # prompt lengths of the requests whose prefill produced a first token
    first_prompt_lens: List[int] = dataclasses.field(default_factory=list)
    # per step, the keys each decode-produced token attended over
    step_ctxs: List[List[int]] = dataclasses.field(default_factory=list)

    def add(self, other: "Tally", keep_ctxs: bool) -> None:
        self.decode_tokens += other.decode_tokens
        self.decode_ctx += other.decode_ctx
        self.first_prompt_lens += other.first_prompt_lens
        if keep_ctxs:
            self.step_ctxs += other.step_ctxs


class _Compiles:
    """Counts compilations (and persistent-cache loads) as JAX reports
    them through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(name, _dur, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[stats.Record]
    tally: Tally
    counters0: Dict[str, Any]
    counters1: Dict[str, Any]
    compiles: int
    cache_hits: int
    lateness: List[float]
    finished: List[Any]            # records with prompt/output attached
    trace: Optional[Any] = None    # bench.trace.Trace
    trace_tally: Optional[Tally] = None
    trace_counters0: Optional[Dict[str, Any]] = None
    trace_counters1: Optional[Dict[str, Any]] = None
    profiler_s: float = 0.0        # host seconds of the window spent
                                   # starting and stopping the profiler


def drive(eng, gen: traffic.Traffic, seconds: float, *,
          trace_dir: Optional[str] = None, compiles: Optional[_Compiles] = None,
          min_finished: int = 0, log=print) -> Window:
    """Ramp up, then measure ``seconds``.  Closed loop: the first wave
    fills every slot, and the window opens once each of its requests has
    its first token.  Open loop: the schedule runs ``ramp_s`` seconds
    before the window opens.  With ``min_finished`` the window also stays
    open until that many requests have finished, the ramp's included."""
    import jax
    from repro.serve.engine import Request

    from bench import trace as tracemod

    clock = time.perf_counter
    records: Dict[int, stats.Record] = {}
    reqs: Dict[int, traffic.Req] = {}
    inflight: Dict[int, stats.Record] = {}
    finished: List[Any] = []
    cur = Tally()
    lateness: List[float] = []
    win = {"open": False}

    def on_token(rec, _req, _tok):
        rec.times.append(clock())
        i = len(rec.times) - 1
        if i == 0:
            cur.first_prompt_lens.append(rec.prompt_len)
        else:
            cur.decode_tokens += 1
            cur.decode_ctx += rec.prompt_len + i
            if cur.step_ctxs:
                cur.step_ctxs[-1].append(rec.prompt_len + i)

    def submit(req: traffic.Req, due: float):
        rec = stats.Record(req.rid, len(req.prompt), due)
        records[req.rid], reqs[req.rid], inflight[req.rid] = rec, req, rec
        with jax.profiler.TraceAnnotation("bench.submit"):
            eng.submit(Request(req.rid, req.prompt, max_new_tokens=req.max_new,
                               on_token=lambda r, t, rec=rec: on_token(rec, r, t)))
        rec.submitted = clock()
        if win["open"] and not gen.closed:
            lateness.append(rec.submitted - due)

    def step() -> List[Any]:
        cur.step_ctxs.append([])
        with jax.profiler.TraceAnnotation("bench.step"):
            done = eng.step()
        now = clock()
        for r in done:
            rec = inflight.pop(r.request_id)
            rec.finished = now
            rec.truncated = bool(r.truncated)
            req = reqs.pop(r.request_id)
            finished.append(_Done(r.request_id, req.prompt, list(r.output)))
            gen.on_finish(req, now - g0, list(r.output))
            if gen.closed:
                submit(gen.next_closed(), clock())
        return done

    def offer(now: float):
        if not gen.closed:
            for req in gen.due(now - g0):
                submit(req, g0 + req.due)

    def idle_until(t: float):
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(max(0.0, t - clock()))

    g0 = clock()
    if gen.closed:
        for req in gen.first_wave():
            submit(req, clock())
        while any(not r.times for r in inflight.values()):
            if clock() - g0 > RAMP_LIMIT_S:
                raise BenchError("the ramp did not fill the slots")
            step()
    else:
        ramp_end = g0 + gen.mix["ramp_s"]
        while clock() < ramp_end:
            offer(clock())
            if inflight:
                step()
            else:
                idle_until(min(g0 + gen.next_due(), ramp_end))

    counters0 = eng.stats()
    c0 = (compiles.compiles, compiles.cache_hits) if compiles else (0, 0)
    t0 = clock()
    t1 = t0 + seconds
    win["open"] = True
    cur.__init__()
    tally = Tally()
    trace_tally = None
    trace_state = "off" if trace_dir is None else "pending"
    t_trace = t0 + max(0.0, (seconds - TRACE_S) / 2)
    tc0 = tc1 = None
    traced_span = None
    profiler_s = 0.0
    while True:
        now = clock()
        if now >= t1 and len(finished) >= min_finished:
            break
        if trace_state == "pending" and now >= t_trace:
            tracemod.start(trace_dir)
            profiler_s += min(clock(), t1) - now
            tc0 = eng.stats()
            traced_span = jax.profiler.TraceAnnotation("bench.traced")
            traced_span.__enter__()
            trace_state, t_trace_end, trace_tally = "on", clock() + TRACE_S, Tally()
        elif trace_state == "on" and now >= t_trace_end:
            traced_span.__exit__(None, None, None)
            tc1 = eng.stats()
            tracemod.stop()
            profiler_s += max(0.0, min(clock(), t1) - now)
            trace_state = "done"
        offer(now)
        cur.__init__()
        if inflight:
            step()
        else:
            idle_until(min(g0 + gen.next_due(), t1))
        tally.add(cur, keep_ctxs=False)
        if trace_state == "on":
            trace_tally.add(cur, keep_ctxs=True)
    if trace_state == "on":
        traced_span.__exit__(None, None, None)
        tc1 = eng.stats()
        tracemod.stop()
    counters1 = eng.stats()
    c1 = (compiles.compiles, compiles.cache_hits) if compiles else (0, 0)
    w = Window(t0=t0, t1=t1, records=list(records.values()), tally=tally,
               counters0=counters0, counters1=counters1,
               compiles=c1[0] - c0[0], cache_hits=c1[1] - c0[1],
               lateness=lateness, finished=finished,
               trace_tally=trace_tally, trace_counters0=tc0,
               trace_counters1=tc1, profiler_s=profiler_s)
    if trace_dir is not None and trace_state != "pending":
        w.trace = tracemod.load(trace_dir)
    return w


@dataclasses.dataclass
class _Done:
    rid: int
    prompt: np.ndarray
    output: List[int]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """What a metric reader gets."""
    cell: Cell
    config: dict
    peaks: dict
    setup_s: float
    window: Window

    @property
    def seconds(self) -> float:
        return self.window.t1 - self.window.t0

    def delta(self, key: str, traced: bool = False) -> float:
        w = self.window
        a, b = ((w.trace_counters0, w.trace_counters1) if traced
                else (w.counters0, w.counters1))
        return b[key] - a[key]


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {len(devs)} {d.platform} "
                         f"device(s); this benchmark runs only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        ms = d.memory_stats() or {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             peaks: Optional[dict] = None, log=None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object the last line
    prints.  ``t_start`` is the process's start on the host clock."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    device = device_info(cell.chips, require_tpu)
    if peaks is None:
        peaks = load_peaks(device["kind"])
    compiles = _Compiles()
    c = cell.config
    eng = build_engine(c, seed)
    gen = traffic.Traffic(cell.mix, vocab=c["vocab_size"],
                          max_len=c["engine"]["max_len"], seed=seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (compiles so far {compiles.compiles}, "
        f"cache loads {compiles.cache_hits})")
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        win = drive(eng, gen, seconds, trace_dir=tdir if trace else None,
                    compiles=compiles, log=log)
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    del eng
    gc.collect()

    ctx = Ctx(cell=cell, config=c, peaks=peaks, setup_s=setup_s, window=win)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    due = stats.due_in(win.records, win.t0, win.t1)
    failed = sum(1 for r in due if r.truncated)
    waiting = sum(1 for r in due if not r.times)
    done_in = sum(1 for r in due if r.finished is not None)
    diag = {
        "compiles_in_window": win.compiles,
        "cache_loads_in_window": win.cache_hits,
        "requests_due": len(due), "requests_finished": done_in,
        "requests_waiting_first_token": waiting,
        "requests_unfinished": len(due) - done_in,
        "memory_peak_bytes": device["memory_peak_bytes"],
        "tokens_in_window": stats.tokens_in(win.records, win.t0, win.t1),
        "decode_ticks": ctx.delta("decode_ticks"),
        "kv_pages_in_use_at_close": win.counters1.get("pages_in_use"),
        "kv_pages_high_water": win.counters1.get("high_water_pages"),
    }
    if win.lateness:
        diag["generator_late_ms"] = {
            "p50": stats.percentile(win.lateness, 50) * 1e3,
            "p95": stats.percentile(win.lateness, 95) * 1e3,
            "max": max(win.lateness) * 1e3}
    print(json.dumps({"diagnostics": diag}), flush=True)

    breakdown = None
    if trace and win.trace is not None and win.trace.ops:
        busy, span = win.trace.busy_window()
        device["busy_s"] = busy
        device["window_s"] = span
        breakdown = {"device_ops": win.trace.top_ops(10),
                     "idle_gaps": win.trace.idle_gaps(10)}

    t_ref = time.perf_counter()
    seqs = check.sample(win.finished, seed)
    w = weights.make(c, seed)
    gap, _, n_tok = check.compare(c, w, seqs)
    limit = c["check"]["max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "tokens_compared": {"value": n_tok, "limit": 1},
        "requests_failed": {"value": failed, "limit": 0},
    }
    correct = (bool(seqs) and gap <= limit and n_tok >= 1 and failed == 0)
    log(f"reference check {time.perf_counter() - t_ref:.1f} s over "
        f"{len(seqs)} requests")
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    out = {"correct": correct, "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
