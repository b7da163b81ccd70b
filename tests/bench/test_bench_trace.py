"""The reduction from a profiler trace to numbers: on made-up events laid
out as the TPU's trace lays them out (``XLA Ops`` and ``XLA Modules``
lines on ``/device:TPU:<n>``, the harness's spans on the host), and on a
trace recorded on a TPU v5e (``data/decode_trace.xplane.pb.gz``: 0.5 s of
the inhibitor offline cell, six decode ticks of 48 rows)."""

import gzip
import pathlib
import types

import pytest

from bench import harness
from bench import trace as tracemod
from bench.trace import Event, Trace


def test_union_merges_overlaps_and_clips_to_the_window():
    evs = [Event("a", 0, 10), Event("b", 5, 15), Event("c", 20, 30),
           Event("d", 28, 40), Event("e", 50, 60)]
    assert tracemod.merged(evs, 2, 55) == [(2, 15), (20, 40), (50, 55)]
    assert tracemod.union_ns(evs, 2, 55) == 13 + 20 + 5
    assert tracemod.union_ns(evs, 100, 200) == 0


def _synthetic():
    dev = "/device:TPU:0"
    ops = [Event("fusion.1", 100, 200, "jit__decode_step"),
           Event("_paged_attention_kernel", 210, 400, "jit__decode_step"),
           Event("fusion.2", 700, 750, "jit__prefill_chunk")]
    mods = [Event("jit__decode_step(1)", 100, 400),
            Event("jit__prefill_chunk(2)", 700, 750)]
    host = [Event(tracemod.TRACED, 0, 1000), Event("bench.step", 50, 450),
            Event("bench.wait", 450, 690), Event("bench.step", 690, 800)]
    return Trace({dev: ops}, {dev: mods}, host)


def test_synthetic_trace_reduces_to_known_numbers():
    tr = _synthetic()
    busy, span = tr.busy_window()
    assert (busy, span) == pytest.approx((340e-9, 1000e-9))
    assert tr.module_time("jit__decode_step") == (pytest.approx(300e-9), 1)
    assert tr.op_time(["_paged_attention_kernel"]) == (
        pytest.approx(190e-9), 1)
    top = tr.top_ops(2)
    assert top[0][0] == "jit__decode_step/_paged_attention_kernel"
    gaps = tr.idle_gaps(3)
    assert gaps[0] == ["bench.wait", pytest.approx(300e-9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_readers_find_nothing_without_a_trace():
    for metric in ("decode_step_ms", "prefill_us_per_tok",
                   "decode_attn_roofline", "device_idle_share"):
        ctx = types.SimpleNamespace(window=types.SimpleNamespace(
            trace=None, trace_tally=None))
        assert harness.load_reader(metric).read(ctx) is None


def test_trace_readers_on_a_synthetic_tick():
    """One decode tick of 64 rows at 1,000 keys each: 20 ms of paged
    kernel inside a 30 ms decode program, in a 40 ms traced window."""
    from bench import flops

    ms = 1_000_000
    dev = "/device:TPU:0"
    kernel = ('%checkpoint.6 = bf16[384,3,64]{2,1,0} custom-call(s32[64]), '
              'custom_call_target="tpu_custom_call"')
    ops = [Event("fusion.1", 0, 10 * ms), Event(kernel, 10 * ms, 30 * ms),
           Event(kernel, 32 * ms, 34 * ms)]       # outside the decode program
    mods = [Event("jit__decode_step(1)", 0, 30 * ms)]
    host = [Event(tracemod.TRACED, 0, 40 * ms), Event("bench.step", 0, 35 * ms)]
    cell = harness.load_cell("smollm-135m.inhibitor.offline")
    tally = harness.Tally(step_ctxs=[[1000] * 64])
    ops = tracemod._in_modules(ops, mods)
    win = types.SimpleNamespace(trace=Trace({dev: ops}, {dev: mods}, host),
                                trace_tally=tally)
    ctx = harness.Ctx(cell=cell, config=cell.config,
                      peaks=harness.load_peaks("TPU v5 lite"), setup_s=0.0,
                      window=win)
    read = lambda m: harness.load_reader(m).read(ctx)
    assert read("decode_step_ms") == pytest.approx(30.0)
    assert read("device_idle_share") == pytest.approx(20.0)
    nbytes, nops = flops.decode_attn_work(cell.config, [1000] * 64)
    assert nbytes == 64 * 1000 * 23040 + 64 * 30 * 2 * 9 * 64 * 2
    least = flops.least_time(nbytes, nops, ctx.peaks)
    assert least == pytest.approx(nbytes / 819e9)
    assert read("decode_attn_roofline") == pytest.approx(100 * least / 0.020)


def test_traced_run_reports_per_layer_metrics_and_checks_last(monkeypatch):
    """A ``--trace 1`` run on the CPU (the look for a chip skipped), with a
    shorter trace than the chip's: the profiler starts and stops inside
    the window, its stall is kept out of ``mfu``'s seconds, and the line
    carries per-layer metrics only.  The CPU has no device plane, so the
    trace readers find nothing; the host-clock tails are read."""
    from bench_helpers import serve, tiny_cell

    monkeypatch.setattr(harness, "TRACE_S", 3.0)
    cell = tiny_cell("inhibitor", "offline")
    res = serve(cell, seconds=harness.TRACE_S + 2.0, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"mfu", "decode_rows_mean", "ttft_p95_ms.offline",
            "itl_p95_ms.offline"} <= set(res["metrics"])
    assert 0 < res["metrics"]["mfu"]["value"] < 100
    assert list(res)[-1] == "checks"


def _two_kernel_ctx(paths, names=("%checkpoint.6", "%gmm.2")):
    """One 30 ms decode execution holding two Pallas calls, 20 ms under
    the first name and 5 ms under the second, with the name paths given;
    a 40 ms traced window of 64 rows at 1,000 keys."""
    ms = 1_000_000
    dev = "/device:TPU:0"
    text = ' = bf16[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    ops = [Event(names[0] + text, 0, 20 * ms, path=paths[0]),
           Event(names[1] + text, 20 * ms, 25 * ms, path=paths[1])]
    mods = [Event("jit__decode_step(1)", 0, 30 * ms)]
    host = [Event(tracemod.TRACED, 0, 40 * ms)]
    cell = harness.load_cell("smollm-135m.inhibitor.offline")
    win = types.SimpleNamespace(
        trace=Trace({dev: tracemod._in_modules(ops, mods)}, {dev: mods}, host),
        trace_tally=harness.Tally(step_ctxs=[[1000] * 64]))
    return harness.Ctx(cell=cell, config=cell.config,
                       peaks=harness.load_peaks("TPU v5 lite"), setup_s=0.0,
                       window=win)


def test_attention_roofline_reads_only_the_scoped_kernel():
    """Beside the paged kernel, an expert kernel in the decode program:
    only the op whose name path holds the scope is attention's."""
    from bench import flops

    reader = harness.load_reader("decode_attn_roofline")
    base = "jit(_decode_step)/while/body/closed_call/checkpoint/"
    ctx = _two_kernel_ctx([base + reader.SCOPE + "/pallas_call",
                           base + "pallas_call"])
    least = flops.least_time(*flops.decode_attn_work(ctx.config, [1000] * 64),
                             ctx.peaks)
    assert reader.read(ctx) == pytest.approx(100 * least / 0.020)
    swapped = _two_kernel_ctx([base + "pallas_call",
                               base + reader.SCOPE + "/pallas_call"])
    assert reader.read(swapped) == pytest.approx(100 * least / 0.005)


def test_attention_roofline_raises_on_two_kernels_without_the_scope():
    base = "jit(_decode_step)/while/body/closed_call/checkpoint/pallas_call"
    ctx = _two_kernel_ctx([base, base])
    with pytest.raises(harness.BenchError, match="paged_decode_attention"):
        harness.load_reader("decode_attn_roofline").read(ctx)


def test_attention_roofline_reads_one_unscoped_kernel_in_every_execution():
    """A program that names no scope: one Pallas op per execution, as
    another table-width bucket's program compiles it under another name,
    is the paged kernel, as before the scope existed."""
    ms = 1_000_000
    dev = "/device:TPU:0"
    text = ' = bf16[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    ops = [Event("%checkpoint.6" + text, 0, 10 * ms),
           Event("%checkpoint.6" + text, 10 * ms, 20 * ms),
           Event("%checkpoint.13" + text, 30 * ms, 38 * ms)]
    mods = [Event("jit__decode_step(1)", 0, 25 * ms),
            Event("jit__decode_step(2)", 30 * ms, 40 * ms)]
    tr = Trace({dev: tracemod._in_modules(ops, mods)}, {dev: mods},
               [Event(tracemod.TRACED, 0, 50 * ms)])
    evs = harness.load_reader("decode_attn_roofline").kernel_events(tr)
    assert sum(e.end - e.start for e in evs) == 28 * ms and len(evs) == 3


RECORDED = pathlib.Path(__file__).parent / "data" / "decode_trace.xplane.pb.gz"


def test_recorded_chip_trace_reduces_to_what_it_holds():
    """The numbers below were read off the recorded trace by listing its
    events by name, apart from this code: six ``jit__decode_step``
    executions of 465.243 ms in all, each holding 30 paged-kernel calls
    (``tpu_custom_call``) of 164.755 ms in all, and no prefill."""
    tr = Trace.from_bytes(gzip.decompress(RECORDED.read_bytes()))
    assert list(tr.ops) == ["/device:TPU:0"]
    secs, n = tr.module_time("jit__decode_step")
    assert (secs, n) == (pytest.approx(0.465243, abs=1e-6), 6)
    assert tr.module_time("jit__prefill_chunk") == (0.0, 0)
    kernel = harness.load_reader("decode_attn_roofline")
    ksecs, kn = tr.op_time(kernel.NAMES, kernel.PROGRAM)
    assert (ksecs, kn) == (pytest.approx(0.164755, abs=1e-6), 180)
    # the op's name path is kept; this program (recorded before the scope)
    # names none, so the reader takes its one Pallas call
    evs = kernel.kernel_events(tr)
    assert {e.path for e in evs} == {
        "jit(_decode_step)/while/body/closed_call/checkpoint/pallas_call"}
    assert not any(kernel.SCOPE in e.path for m in tr.ops.values() for e in m)
    assert sum(e.end - e.start for e in evs) == pytest.approx(ksecs * 1e9)
    busy, span = tr.busy_window()
    assert secs <= busy <= span == pytest.approx(0.502829, abs=1e-6)
    top = tr.top_ops(3)
    assert top[0] == ["jit__decode_step/checkpoint.6 bf16[144,3,64] "
                      "tpu_custom_call", pytest.approx(ksecs)]
    assert all(not n.split("/")[-1].startswith("while") for n, _ in top)
    gaps = tr.idle_gaps(10)
    assert len(gaps) == 10 and all(g[0].startswith("bench.step") for g in gaps)
    assert sum(g[1] for g in gaps) <= span - busy


def test_short_names_keep_the_op_its_shape_and_the_call_target():
    assert tracemod.short("%copy.88 = bf16[1,6145,3,16,64]{1,4,3,2,0} "
                          "copy(bf16[1] %b)") == "copy.88 bf16[1,6145,3,16,64]"
    assert tracemod.short('%cc.1 = f32[2]{0} custom-call(), '
                          'custom_call_target="tpu_custom_call"') == (
        "cc.1 f32[2] tpu_custom_call")
    assert tracemod.short("%while.2 = (s32[], f32[2]) while(%t)") == (
        "while.2 tuple")
    assert tracemod.short("jit__decode_step(1)") == "jit__decode_step(1)"


def _pb(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_op_paths_read_string_and_interned_name_paths():
    """A device plane whose ops carry their ``tf_op`` stat as a string
    (XStat field 5) and as a reference to an interned string (field 7);
    a host plane's ops are not read."""
    def stat_meta(i, name):
        return _pb(5, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)))

    def op(i, name, stat):
        return _pb(4, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)
                                     + _pb(5, stat)))

    device = (_pb(2, "/device:TPU:0") + _pb(3, _pb(1, 9))
              + stat_meta(7, "tf_op") + stat_meta(8, "jit(f)/pallas_call:")
              + op(1, "%a = f32[2] custom-call()",
                   _pb(1, 7) + _pb(5, "jit(f)/dot:"))
              + op(2, "%b = f32[2] custom-call()", _pb(1, 7) + _pb(7, 8))
              + op(3, "%c = f32[2] add()", _pb(1, 3) + _pb(5, "ignored")))
    host = _pb(2, "/host:CPU") + stat_meta(7, "tf_op") + op(
        1, "x", _pb(1, 7) + _pb(5, "y"))
    space = _pb(1, device) + _pb(1, host)
    assert tracemod.op_paths(space) == {"/device:TPU:0": {
        "%a = f32[2] custom-call()": "jit(f)/dot",
        "%b = f32[2] custom-call()": "jit(f)/pallas_call"}}
