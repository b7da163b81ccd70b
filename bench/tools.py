"""Chip-side tools that set the benchmark up; the benchmark's runs never
call them.  Each runs in one process, which holds the chip.

    python3 bench/tools.py calibrate --workload W --seeds 1,2,3 --seconds 20 [--control]
    python3 bench/tools.py sweep --workload W --rates 0.5,1,2 --seconds 30
    python3 bench/tools.py trace --workload W --seed 5 --seconds 2 --out DIR

``calibrate`` serves the cell once per seed with one engine (the weights
swapped per seed, leftover requests cancelled between seeds; the window
stays open until a full sample has finished) and prints,
per seed, the compared number and, with ``--control``, the control's
number at the same positions: the readings the limits are set from.
``sweep`` offers a chat mix at each session rate, each rate to an engine
built afresh, so every reading starts from a cold prefix cache
as a benchmark run does, and prints how the queue of requests waiting
for their first token moved through the window: it finds the knee.
``trace`` keeps a short profiler trace and lists its planes, lines and
busiest event names.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def _jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _drain(eng, win) -> None:
    """Cancel what is still queued or in flight after a window."""
    for r in win.records:
        if r.finished is None:
            eng.cancel(r.rid)


def _summary(win) -> dict:
    from bench import stats

    recs, t0, t1 = win.records, win.t0, win.t1
    itl = stats.itl_samples(recs, t0, t1)
    half = (t0 + t1) / 2
    due = stats.due_in(recs, t0, t1)
    c0, c1 = win.counters0, win.counters1
    return {
        "output_tok_s": stats.output_rate(recs, t0, t1),
        "ttft_p50_ms": stats.ttft_ms(recs, t0, t1, 50),
        "ttft_p95_ms": stats.ttft_ms(recs, t0, t1, 95),
        "ttft_p50_first_half_ms": stats.ttft_ms(recs, t0, half, 50),
        "ttft_p50_second_half_ms": stats.ttft_ms(recs, half, t1, 50),
        "itl_p50_ms": stats.percentile(itl, 50) * 1e3 if itl else None,
        "itl_p95_ms": stats.percentile(itl, 95) * 1e3 if itl else None,
        "due": len(due),
        "waiting_first_token_at_close": sum(1 for r in due if not r.times),
        "finished": sum(1 for r in due if r.finished is not None),
        "compiles_in_window": win.compiles,
        "cache_loads_in_window": win.cache_hits,
        "prefix_hit_tokens": c1["prefix_hit_tokens"] - c0["prefix_hit_tokens"],
        "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
    }


def calibrate(args) -> None:
    from bench import check, harness, traffic, weights

    _jax()
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    c = cell.config
    compiles = harness._Compiles()
    eng = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        if eng is None:
            eng = harness.build_engine(c, seed)
        else:
            weights.place(c, seed, eng)
        setup = time.perf_counter() - t0
        gen = traffic.Traffic(cell.mix, vocab=c["vocab_size"],
                              max_len=c["engine"]["max_len"], seed=seed)
        win = harness.drive(eng, gen, args.seconds, compiles=compiles,
                            min_finished=check.SAMPLE)
        _drain(eng, win)
        seqs = check.sample(win.finished, seed)
        t_ref = time.perf_counter()
        gap, ctrl, n = check.compare(c, weights.make(c, seed), seqs,
                                     control=args.control)
        _emit({"seed": seed, "setup_s": setup, "max_logit_gap": gap,
               "control_gap": ctrl, "tokens_compared": n,
               "requests_compared": len(seqs),
               "reference_s": time.perf_counter() - t_ref,
               **_summary(win)})


#: a queue whose fitted line rises by this many requests over the window
#: is a backlog
BACKLOG = 4.0


def _queue(win) -> dict:
    """The queue of requests due and still without a first token, read
    once a second through the window, and its least-squares growth over
    the window: a backlog grows, a sustained load only wanders."""
    from bench import stats

    n = int(win.t1 - win.t0)
    ts = [win.t0 + i for i in range(n + 1)]
    q = [stats.waiting_at(win.records, t) for t in ts]
    mt, mq = sum(range(n + 1)) / (n + 1), sum(q) / (n + 1)
    slope = (sum((i - mt) * (v - mq) for i, v in enumerate(q))
             / sum((i - mt) ** 2 for i in range(n + 1)))
    k = max(1, (n + 1) // 4)
    return {"queue_first_quarter": sum(q[:k]) / k,
            "queue_last_quarter": sum(q[-k:]) / k,
            "queue_max": max(q), "queue_growth": slope * n,
            "backlog": slope * n >= BACKLOG}


def sweep(args) -> None:
    from bench import harness, traffic

    _jax()
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    c = cell.config
    compiles = harness._Compiles()
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        eng = harness.build_engine(c, args.seed)
        build_s = time.perf_counter() - t0
        mix = dict(cell.mix, rate_sessions_s=rate)
        gen = traffic.Traffic(mix, vocab=c["vocab_size"],
                              max_len=c["engine"]["max_len"], seed=args.seed)
        win = harness.drive(eng, gen, args.seconds, compiles=compiles)
        _drain(eng, win)
        del eng
        gc.collect()
        _emit({"rate_sessions_s": rate, "build_s": build_s, **_summary(win),
               **_queue(win)})


def trace(args) -> None:
    from bench import harness, traffic
    from bench import trace as tracemod

    jax = _jax()
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    c = cell.config
    eng = harness.build_engine(c, args.seed)
    gen = traffic.Traffic(cell.mix, vocab=c["vocab_size"],
                          max_len=c["engine"]["max_len"], seed=args.seed)
    out = pathlib.Path(args.out)
    if out.exists():
        shutil.rmtree(out)
    harness.TRACE_S = args.seconds
    win = harness.drive(eng, gen, args.seconds, trace_dir=str(out))
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(tracemod.xplane_path(str(out)))
    for plane in pd.planes:
        for line in plane.lines:
            names, count = {}, 0
            for e in line.events:
                name = tracemod.short(e.name)
                names[name] = names.get(name, 0) + e.duration_ns
                count += 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            _emit({"plane": plane.name, "line": line.name, "events": count,
                   "top": [[n, d / 1e9] for n, d in top]})
    tr = win.trace
    with open(out / "traced_steps.json", "w") as f:
        json.dump({"workload": args.workload,
                   "step_ctxs": win.trace_tally.step_ctxs,
                   "counters0": win.trace_counters0,
                   "counters1": win.trace_counters1}, f, default=str)
    _emit({"span_s": (tr.span()[1] - tr.span()[0]) / 1e9,
           "busy_window": tr.busy_window() if tr.ops else None,
           "decode_module": tr.module_time("jit__decode_step"),
           "prefill_module": tr.module_time("jit__prefill_chunk"),
           "top_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10),
           "traced_steps": len(win.trace_tally.step_ctxs),
           "device_kind": jax.devices()[0].device_kind})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("calibrate")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--control", action="store_true")
    p = sub.add_parser("sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=30.0)
    p = sub.add_parser("trace")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    {"calibrate": calibrate, "sweep": sweep, "trace": trace}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
