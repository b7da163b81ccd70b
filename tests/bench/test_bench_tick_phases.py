"""The readers of the engine's tick phases: ``tick_host_ms`` and
``tick_idle_ms`` over the ``engine.*`` spans the program puts on the host
line of a profiler trace, and ``queue_wait_ms`` over its admission
counters.  On made-up events laid out as a TPU trace lays them out, and
on a ``--trace 1`` run on the CPU."""

import types

import pytest

from bench import harness
from bench import trace as tracemod
from bench.trace import Event, Trace


@pytest.mark.parametrize("metric", ["tick_host_ms", "tick_idle_ms"])
def test_tick_readers_find_nothing_without_a_trace(metric):
    ctx = types.SimpleNamespace(window=types.SimpleNamespace(
        trace=None, trace_tally=None))
    assert harness.load_reader(metric).read(ctx) is None


def _tick_trace(host_names=True):
    """Engine tick phases as the program annotates them, in a 1,000 us
    traced window: two decode ticks (100-400 and 450-700 us), a tick that
    only prefills, and a decode tick that ends outside the window."""
    us = 1000
    dev = "/device:TPU:0"
    spans = [(tracemod.TRACED, 0, 1000),
             ("bench.step", 95, 405), ("engine.tick", 100, 400),
             ("engine.grow", 100, 120), ("engine.prefill_pass", 120, 200),
             ("engine.scheduler", 120, 200),
             ("engine.prefill_wait", 150, 190),
             ("engine.decode_step", 200, 380), ("engine.dispatch", 200, 230),
             ("engine.readback", 230, 380), ("engine.emit", 380, 400),
             ("prefix.py:84 <genexpr>", 385, 395),
             ("bench.step", 445, 705), ("engine.tick", 450, 700),
             ("engine.readback", 520, 680),
             ("bench.step", 745, 805), ("engine.tick", 750, 800),
             ("engine.prefill_wait", 760, 790),
             ("bench.step", 945, 1000), ("engine.tick", 950, 1050),
             ("engine.readback", 960, 1040)]
    if not host_names:
        spans = [s for s in spans if not s[0].startswith("engine.")]
    host = [Event(n, a * us, b * us) for n, a, b in spans]
    ops = [Event("fusion.1", a * us, b * us) for a, b in
           ((120, 150), (230, 370), (390, 460), (500, 690), (800, 900))]
    return Trace({dev: ops}, {dev: []}, host)


def _tick_ctx(trace, counters0=None, counters1=None):
    cell = harness.load_cell("smollm-135m.inhibitor.offline")
    win = types.SimpleNamespace(trace=trace, trace_tally=None,
                                counters0=counters0, counters1=counters1)
    return harness.Ctx(cell=cell, config=cell.config, peaks={}, setup_s=0.0,
                       window=win)


def test_tick_readers_on_a_synthetic_trace():
    """The decode ticks are the first two: the prefill-only tick holds no
    readback and the last ends outside the window.  Host work: 300 - 150
    - 40 and 250 - 160 us; device idle: 300 - 180 and 250 - 200 us (the
    op across the two ticks is split between them)."""
    ctx = _tick_ctx(_tick_trace())
    read = lambda m: harness.load_reader(m).read(ctx)
    assert read("tick_host_ms") == pytest.approx((110 + 90) / 2 / 1e3)
    assert read("tick_idle_ms") == pytest.approx((120 + 50) / 2 / 1e3)


def test_tick_readers_refuse_a_stale_span_name(monkeypatch):
    from repro.serve import telemetry

    for m in ("tick_host_ms", "tick_idle_ms"):
        reader = harness.load_reader(m)
        # the engine stepped in the window, and no engine.tick shows
        with pytest.raises(harness.BenchError, match="engine.tick"):
            reader.read(_tick_ctx(_tick_trace(host_names=False)))
    monkeypatch.setitem(telemetry.PHASES, "tick", "engine.step")
    with pytest.raises(harness.BenchError, match="engine.tick"):
        harness.load_reader("tick_host_ms").read(_tick_ctx(_tick_trace()))


def test_tick_readers_read_nothing_from_a_program_without_phases(
        monkeypatch):
    """A program from before the tick phases has no ``PHASES`` table and
    no ``engine.*`` spans: the readers find nothing, and do not raise."""
    from repro.serve import telemetry

    monkeypatch.delattr(telemetry, "PHASES")
    for m in ("tick_host_ms", "tick_idle_ms"):
        reader = harness.load_reader(m)
        assert reader.read(_tick_ctx(_tick_trace(host_names=False))) is None


def test_queue_wait_reads_the_admission_counters():
    read = harness.load_reader("queue_wait_ms").read
    c0 = {"queue_wait_us": 1_000, "admitted_requests": 4}
    c1 = {"queue_wait_us": 7_000, "admitted_requests": 8}
    assert read(_tick_ctx(None, c0, c1)) == pytest.approx(1.5)
    assert read(_tick_ctx(None, c0, dict(c0))) is None   # none admitted
    assert read(_tick_ctx(None, {}, {"decode_ticks": 3})) is None


def test_traced_run_reports_the_tick_phases(monkeypatch):
    """A ``--trace 1`` run on the CPU: the engine's tick phases on the
    host line and its admission counters are read, though the CPU has no
    device plane for ``tick_idle_ms``."""
    from bench_helpers import serve, tiny_cell

    monkeypatch.setattr(harness, "TRACE_S", 3.0)
    cell = tiny_cell("inhibitor", "offline")
    res = serve(cell, seconds=harness.TRACE_S + 2.0, trace=True)
    assert res["correct"] is True
    assert {"tick_host_ms", "queue_wait_ms"} <= set(res["metrics"])
    assert res["metrics"]["tick_host_ms"]["value"] > 0
    assert res["metrics"]["queue_wait_ms"]["value"] >= 0
