"""Serve-path telemetry (DESIGN.md §16): span tracer, Chrome trace
export, crash flight recorder, metrics registry.

Covers the observability contract end to end:

* span-tree well-formedness for every terminal state the engine can
  reach — finish, cancel while queued / mid-prefill, truncated
  (cancel-while-decoding), and the pool-dry pause/resume path;
* flight-recorder dump on the no-progress error path (the dump exists,
  carries the reason, and the exception message points at it);
* the tick phases as ``engine.*`` profiler spans (on the Python thread's
  line of a CPU profiler trace, nested, one ``engine.tick`` per step)
  and as the same Tracer spans;
* Chrome trace-event schema validation + the module CLI as a hard gate;
* disabled-mode zero overhead: ``telemetry=None`` constructs NO tracer
  and emits NO events (proven by making every Tracer constructor blow
  up for the duration of the run);
* bounded reservoir histograms replacing the unbounded latency lists,
  with the engine's ``*_p50`` / ``*_p99`` / ``latency_samples`` stats
  surface intact, latency stamped after the readback, and the
  admission queue-wait counters;
* the telemetry emit path itself stays transfer-free and LANE004-clean
  (``audit_telemetry_file`` + the lane lint).
"""

import json

import numpy as np
import pytest

from repro.serve import telemetry
from repro.serve.telemetry import (REQ_TID_BASE, Histogram, MetricsRegistry,
                                   TelemetryConfig, Tracer, make_tracer,
                                   to_chrome_trace, validate_chrome_trace,
                                   write_trace)


def _mk_engine(serve_model, **kw):
    from repro.serve.engine import Engine, EngineConfig

    cfg, api, params = serve_model
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("telemetry", True)
    return Engine(api, params, EngineConfig(**kw))


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 127, n).astype(np.int32) for n in lens]


def _validated(eng):
    doc = to_chrome_trace(eng.tel)
    v = validate_chrome_trace(doc)
    assert v["ok"], v["errors"]
    return doc, v


def _names(eng, ph=None):
    return [e[2] for e in eng.tel.events if ph is None or e[1] == ph]


# ---------------------------------------------------------------------------
# span trees per terminal state
# ---------------------------------------------------------------------------

def test_span_tree_finish(serve_model):
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, tick_budget=12)
    for i, p in enumerate(_prompts(30, (3, 17, 40))):
        eng.submit(Request(i, p, max_new_tokens=6))
    done = eng.run_to_completion()
    assert len(done) == 3

    doc, v = _validated(eng)
    s = v["summary"]
    assert s["requests"] == 3
    assert s["admitted"] == 3
    assert s["terminals"] == {"finish": 3}
    assert s["ticks"] > 1
    # tick phase attribution made it onto the engine track
    names = set(_names(eng))
    assert {"tick", "prefill_pass", "scheduler", "decode_step",
            "table_upload"} <= names
    # the 40-token prompt really prefilled in chunk batches (X events
    # with a duration)
    chunk_evs = [e for e in eng.tel.events
                 if e[1] == "X" and e[2] == "prefill_chunks"]
    assert chunk_evs and all("_dur" in e[5] for e in chunk_evs)
    # kernel/plan provenance rode along: engine meta + first-seen-bucket
    # instants carry the registry's interpret decision
    assert doc["otherData"]["meta"]["engine"]["family"]
    buckets = [e for e in eng.tel.events if e[2] == "decode_bucket"]
    assert buckets and all("interpret" in e[5] for e in buckets)


def test_span_tree_cancel_queued_and_mid_prefill(serve_model):
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, max_batch=1, tick_budget=8,
                     prefix_cache=False)
    long_p, queued_p = _prompts(31, (40, 6))
    eng.submit(Request(0, long_p, max_new_tokens=4))
    eng.submit(Request(1, queued_p, max_new_tokens=4))
    eng.step()
    assert eng.admitting                      # request 0 is mid-prefill
    assert eng.cancel(1)                      # still queued
    assert eng.cancel(0)                      # mid-prefill unwind

    doc, v = _validated(eng)
    assert v["summary"]["terminals"] == {"cancel": 2}
    by_track = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "i" and e["name"] == "cancel":
            by_track[e["tid"]] = e["args"]["where"]
    assert by_track == {REQ_TID_BASE + 0: "prefill",
                        REQ_TID_BASE + 1: "queued"}


def test_span_tree_truncated_on_decode_cancel(serve_model):
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model)
    [p] = _prompts(32, (6,))
    eng.submit(Request(0, p, max_new_tokens=30))
    for _ in range(3):
        eng.step()
    assert eng.active                         # decoding now
    assert eng.cancel(0)                      # -> _finish(truncated=True)
    _, v = _validated(eng)
    assert v["summary"]["terminals"] == {"truncated": 1}


def test_span_tree_pool_dry_pause_resume(serve_model):
    """The backpressure path (pool-dry pause, later resume) shows up as
    paired paused/resumed instants on the request's own track, and the
    trace still validates — the pause does not tear the span tree."""
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, max_batch=2, num_pages=10,
                     prefix_cache=False, tick_budget=16)
    blocker_p, late_p = _prompts(33, (32, 40))
    eng.submit(Request(0, blocker_p, max_new_tokens=12))
    eng.step()
    eng.submit(Request(1, late_p, max_new_tokens=3))
    done = eng.run_to_completion()
    assert eng.stats()["paused_prefills"] > 0
    assert sorted(r.request_id for r in done) == [0, 1]

    _, v = _validated(eng)
    # both requests reached a terminal (the blocker may legitimately
    # truncate when the dry pool hard-stops its decode growth)
    assert sum(v["summary"]["terminals"].values()) == 2
    late_tid = REQ_TID_BASE + 1
    late = [(e[1], e[2]) for e in eng.tel.events if e[4] == late_tid]
    assert ("i", "paused") in late
    assert ("i", "resumed") in late
    # pause instants land strictly inside the prefill span
    order = [n for ph, n in late if (ph, n) in
             (("B", "prefill"), ("E", "prefill"), ("i", "paused"),
              ("i", "resumed"))]
    assert order[0] == "prefill" and order[-1] == "prefill"


def test_eviction_and_cow_instants(serve_model):
    """Prefix-cache traffic under page pressure leaves eviction (and the
    CoW forks the cache makes possible) visible in the timeline."""
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, max_batch=2, num_pages=12,
                     prefix_cache=True)
    shared = _prompts(34, (16,))[0]
    rid = 0
    for tail_len in (8, 10, 12, 14):
        tail = _prompts(35 + tail_len, (tail_len,))[0]
        eng.submit(Request(rid, np.concatenate([shared, tail]),
                           max_new_tokens=4))
        rid += 1
    eng.run_to_completion()
    _, v = _validated(eng)
    names = set(_names(eng, ph="i"))
    s = eng.stats()
    if s["evictions"]:
        assert "eviction" in names
    if s["forked_pages"]:
        assert "cow_fork" in names
    # at minimum the cache-on run re-used the shared prefix
    assert s["prefix_hit_tokens"] > 0


# ---------------------------------------------------------------------------
# tick phases on the profiler's clock
# ---------------------------------------------------------------------------

#: each tick phase's allowed innermost enclosing phase (None: top level)
_PHASE_PARENTS = {
    "tick": {None}, "grow": {"tick"}, "prefill_pass": {"tick"},
    "scheduler": {"prefill_pass"},
    "prefill_wait": {"scheduler", "prefill_pass"},
    "decode_step": {"tick"}, "dispatch": {"decode_step"},
    "readback": {"decode_step"}, "emit": {"tick"},
    "finish": {"grow", "scheduler", "prefill_pass", "emit"},
}


def test_tick_phases_on_the_profiler_clock(serve_model, tmp_path):
    """Every ``engine.<phase>`` span sits on the Python thread's line (the
    one holding the caller's own annotation), nests as ``PHASES`` says,
    one ``engine.tick`` per ``step()``, and the Tracer records the same
    phase names."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, tick_budget=12)
    for i, p in enumerate(_prompts(41, (3, 17, 9))):
        eng.submit(Request(i, p, max_new_tokens=3))
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("test.steps"):
            while eng.active or eng.admitting or len(eng.scheduler):
                eng.step()
                steps += 1
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            if any(n == "test.steps" for n, _, _ in evs):
                lines[f"{plane.name}/{line.name}"] = evs
    assert len(lines) == 1
    [evs] = lines.values()
    spans = sorted(((n[len("engine."):], a, b) for n, a, b in evs
                    if n.startswith("engine.")),
                   key=lambda s: (s[1], -s[2]))
    names = [n for n, _, _ in spans]
    assert set(names) <= set(telemetry.PHASES)
    assert {"tick", "grow", "prefill_pass", "scheduler", "prefill_wait",
            "decode_step", "dispatch", "readback", "emit",
            "finish"} <= set(names)
    assert names.count("tick") == steps
    for i, (name, a, b) in enumerate(spans):
        holders = [s for s in spans[:i] if s[1] <= a and b <= s[2]]
        parent = holders[-1][0] if holders else None
        assert parent in _PHASE_PARENTS[name], (name, parent)
    # the same phases, by the same names, in the Tracer
    traced = [e[2] for e in eng.tel.events
              if e[1] == "B" and e[4] == telemetry.TID_ENGINE]
    assert sorted(traced) == sorted(names)
    _validated(eng)


def test_phase_spans_are_entered_with_telemetry_off(monkeypatch):
    """Off means no Tracer, not no spans: the profiler annotation is
    entered either way, the Tracer's events only when tracing."""
    entered = []

    class Ann:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(telemetry, "TraceAnnotation", Ann)
    with telemetry.phase(None, "tick", n=1):
        with telemetry.phase(None, "readback"):
            pass
    tr = Tracer()
    with telemetry.phase(tr, "emit"):
        pass
    assert entered == ["engine.tick", "engine.readback", "engine.emit"]
    assert [(e[1], e[2]) for e in tr.events] == [("B", "emit"),
                                                ("E", "emit")]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_dump_on_no_progress(serve_model, tmp_path):
    from repro.serve.engine import Engine, EngineConfig, Request

    cfg, api, params = serve_model
    flight = tmp_path / "FLIGHT_test.json"
    eng = Engine(api, params, EngineConfig(
        max_batch=2, max_len=64,
        telemetry=TelemetryConfig(trace=False, flight_path=str(flight))))
    assert eng.tel is not None and eng.tel.events is None   # ring only
    # simulate a leak: something outside the engine holds every slot
    assert eng.alloc.claim(990) is not None
    assert eng.alloc.claim(991) is not None
    eng.submit(Request(0, _prompts(36, (4,))[0]))
    with pytest.raises(RuntimeError, match="cannot make progress") as ei:
        eng.run_to_completion()
    assert f"[flight recorder: {flight}]" in str(ei.value)

    doc = json.loads(flight.read_text())
    other = doc["otherData"]
    assert other["flight"] is True
    assert "cannot make progress" in other["reason"]
    assert doc["traceEvents"]                 # the last ticks are there
    # a flight dump legitimately opens mid-span: the validator relaxes
    # balance/terminal checks but still type-checks every event
    v = validate_chrome_trace(doc)
    assert v["ok"], v["errors"]
    assert v["summary"]["flight"] is True


def test_flight_ring_is_bounded():
    tr = Tracer(trace=False, ring=16)
    for i in range(100):
        tr.instant("e", n=i)
    assert len(tr.ring) == 16
    assert tr.dropped == 84
    assert tr.events is None


# ---------------------------------------------------------------------------
# Chrome trace schema + CLI
# ---------------------------------------------------------------------------

def test_trace_export_schema(serve_model, tmp_path):
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model)
    eng.submit(Request(0, _prompts(37, (9,))[0], max_new_tokens=4))
    eng.run_to_completion()
    path = tmp_path / "trace.json"
    write_trace(eng.tel, path)

    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    # track-naming metadata leads the stream
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name",
            "thread_sort_index"} <= {e["name"] for e in meta}
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "req 0" for e in meta)
    for e in evs:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["tid"], int) and isinstance(e["pid"], int)
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float))
    assert doc["otherData"]["schema"] == telemetry.SCHEMA
    assert doc["otherData"]["flight"] is False

    # the module CLI is the CI hard gate: 0 on a valid trace
    assert telemetry.main([str(path), "--quiet"]) == 0


def test_cli_rejects_malformed_trace(serve_model, tmp_path, capsys):
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model)
    eng.submit(Request(0, _prompts(38, (9,))[0], max_new_tokens=4))
    eng.run_to_completion()
    doc = to_chrome_trace(eng.tel)
    # drop the request's terminal instant + root close: now a request
    # track never terminates and holds an unclosed span
    tid = REQ_TID_BASE + 0
    doc["traceEvents"] = [
        e for e in doc["traceEvents"]
        if not (e.get("tid") == tid
                and (e["name"] in telemetry.TERMINALS
                     or (e["ph"] == "E" and e["name"] == "request")))]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    assert telemetry.main([str(bad), "--quiet"]) == 1
    err = capsys.readouterr().err
    assert "TRACE INVALID" in err and "terminal" in err


def test_validator_catches_misnesting():
    tr = Tracer()
    tr.begin("a")
    tr.begin("b")
    tr.end("a")                               # misnested: b still open
    tr.end("b")
    v = validate_chrome_trace(to_chrome_trace(tr))
    assert not v["ok"]
    assert any("does not match innermost" in e for e in v["errors"])


def test_validator_catches_backwards_time():
    tr = Tracer()
    tr._emit(100.0, "i", "late", "tick", 0, None)
    tr._emit(50.0, "i", "early", "tick", 0, None)
    v = validate_chrome_trace(to_chrome_trace(tr))
    assert not v["ok"]
    assert any("goes backwards" in e for e in v["errors"])


# ---------------------------------------------------------------------------
# disabled mode: zero events, zero allocation
# ---------------------------------------------------------------------------

def test_disabled_mode_constructs_no_tracer(serve_model, monkeypatch,
                                            greedy_ref):
    """``telemetry=None`` (the default) must never touch the telemetry
    module at runtime: any Tracer construction during the run fails the
    test, and outputs match the oracle."""
    from repro.serve.engine import Request

    def boom(*a, **kw):
        raise AssertionError("Tracer constructed with telemetry disabled")

    monkeypatch.setattr(telemetry.Tracer, "__init__", boom)
    eng = _mk_engine(serve_model, telemetry=None)
    assert eng.tel is None
    [p] = _prompts(39, (9,))
    eng.submit(Request(0, p, max_new_tokens=5))
    done = eng.run_to_completion()
    assert done[0].output == greedy_ref(p, 5)
    # the stats surface is tracer-independent (histograms still fill)
    s = eng.stats()
    assert s["latency_samples"]["ttft_ms"] == 1


def test_make_tracer_specs():
    assert make_tracer(None) is None
    assert make_tracer(True).events == []
    c = make_tracer(TelemetryConfig(trace=False, ring=7, flight_path="x"))
    assert c.events is None and c.ring.maxlen == 7 and c.flight_path == "x"
    for bad in ("bogus", False, "on", "flight", Tracer()):
        with pytest.raises(ValueError):
            make_tracer(bad)


# ---------------------------------------------------------------------------
# metrics registry + bounded histograms (satellite: the unbounded-list fix)
# ---------------------------------------------------------------------------

def test_histogram_bounded_reservoir():
    h = Histogram(capacity=64)
    for i in range(10_000):
        h.record(float(i))
    assert h.count == 10_000
    assert len(h._vals) == 64                 # memory stays fixed
    assert h.max == 9999.0 and h.min == 0.0   # extremes are exact
    assert h.mean == pytest.approx(4999.5)
    # the reservoir is a uniform sample: p50 lands well inside the range
    assert 1000.0 < h.percentile(50) < 9000.0
    snap = h.snapshot()
    assert snap["count"] == 10_000 and snap["reservoir"] == 64


def test_histogram_exact_below_capacity():
    h = Histogram(capacity=512)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.record(v)
    assert h.percentile(50) == pytest.approx(2.5)
    assert len(h) == 4
    with pytest.raises(ValueError):
        Histogram(capacity=0)


def test_metrics_registry():
    m = MetricsRegistry()
    m.counter("x")
    m.counter("x", 2)
    assert m.counters["x"] == 3
    assert m.histogram("h") is m.histogram("h")   # get-or-create
    m.histogram("h").record(5.0)
    snap = m.snapshot()
    assert snap["counters"] == {"x": 3}
    assert snap["histograms"]["h"]["count"] == 1


def test_engine_latency_stats_surface_bounded(serve_model):
    """The ``*_p50``/``*_p99``/``latency_samples`` keys survive the
    list->histogram swap, and engine latency memory is now bounded."""
    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, telemetry=None)
    for i, p in enumerate(_prompts(40, (4, 7, 11))):
        eng.submit(Request(i, p, max_new_tokens=5))
    eng.run_to_completion()
    s = eng.stats()
    for k in ("ttft_ms", "itl_ms", "queued_ticks"):
        assert f"{k}_p50" in s and f"{k}_p99" in s
        assert s[f"{k}_p99"] >= s[f"{k}_p50"] >= 0.0
        assert eng._lat[k].capacity == 512    # bounded, not a list
    assert s["latency_samples"]["ttft_ms"] == 3
    assert s["latency_samples"]["itl_ms"] == 3 * 4   # n_new - 1 per req


def test_engine_latency_stamped_after_readback(serve_model):
    """TTFT and ITL samples are taken once the token is on the host: with
    every prefill and decode dispatch taking ``delay`` before its
    readback, no sample is shorter than that.  Stamped at the tick's
    start, a first token read back in its admission tick would show a
    TTFT of about 0 and the next token, decoded in that same tick, an
    ITL of 0."""
    import time

    from repro.serve.engine import Request

    delay = 0.02

    def slow(fn):
        def run(*args):
            out = fn(*args)
            time.sleep(delay)
            return out
        return run

    eng = _mk_engine(serve_model, telemetry=None)
    eng._jit_prefill_chunk = slow(eng._jit_prefill_chunk)
    eng._jit_decode = slow(eng._jit_decode)
    for i, p in enumerate(_prompts(42, (5, 7))):
        eng.submit(Request(i, p, max_new_tokens=4))
    eng.run_to_completion()
    lat = eng._lat
    assert lat["ttft_ms"].count == 2 and lat["itl_ms"].count == 2 * 3
    assert lat["ttft_ms"].min >= delay * 1e3
    assert lat["itl_ms"].min >= delay * 1e3


def test_queue_wait_counters_advance_on_admission(serve_model):
    """``admitted_requests`` counts requests as they leave the scheduler
    for a slot, and ``queue_wait_us`` adds each one's wait since
    ``submit``: a request held back behind a full slot pool waits at
    least the ticks it stayed queued."""
    import time

    from repro.serve.engine import Request

    eng = _mk_engine(serve_model, max_batch=1, telemetry=None)
    first, second = _prompts(43, (4, 6))
    s = eng.stats()
    assert (s["admitted_requests"], s["queue_wait_us"]) == (0, 0)
    t_submit = time.perf_counter()
    eng.submit(Request(0, first, max_new_tokens=3))
    eng.submit(Request(1, second, max_new_tokens=3))
    time.sleep(0.01)
    eng.step()
    t_step = (time.perf_counter() - t_submit) * 1e6
    s = eng.stats()
    assert s["admitted_requests"] == 1            # one slot: one admitted
    assert 10_000 <= s["queue_wait_us"] <= t_step
    eng.run_to_completion()
    t_end = (time.perf_counter() - t_submit) * 1e6
    s2 = eng.stats()
    assert s2["admitted_requests"] == 2
    # the second request stayed queued through the first tick
    assert t_step <= s2["queue_wait_us"] - s["queue_wait_us"] <= t_end


# ---------------------------------------------------------------------------
# the emit path is audited transfer-free + LANE004-clean
# ---------------------------------------------------------------------------

def test_telemetry_sync_audit_transfer_free():
    from repro.analysis.serve_static import audit_telemetry_file

    audit = audit_telemetry_file()
    assert audit["ok"], audit
    assert audit["unallowlisted"] == []
    assert audit["per_tick"] == {"h2d": 0, "d2h": 0}


def test_telemetry_module_is_lane004_clean():
    from repro.analysis.lint import lint_paths

    assert lint_paths([telemetry.__file__]) == []
