"""Serving launcher: continuous-batching engine over a trained/initialized
model.

    python -m repro.launch.serve --arch smollm-135m --requests 16

Serves the model at its published widths; ``--reduced`` swaps in the
per-family CPU-scale config (2 layers, d_model 64).  Loads params from
--ckpt-dir if given (falls back to random init), then drives the engine
with synthetic ragged prompt traffic and reports throughput plus the
paged-cache accounting (prefill compile count, page-pool high-water
mark) and the shared-prefix cache counters (hit tokens, CoW forks,
evictions).  ``--allocator contiguous`` selects
the dense per-slot baseline; the default is the paged block-table cache
with the radix prefix index on.  ``--shared-prefix N`` makes every
synthetic prompt share an N-token prefix (system-prompt traffic) so the
cache has something to hit; ``--scheduler prefix`` admits
resident-prefix requests first.  ``--tick-budget N`` turns on chunked
prefill-decode interleaving (DESIGN.md §15): each tick spends at most N
padded prefill tokens between decode steps, so long prompts admit over
several ticks instead of stalling every in-flight stream;
``--chunk-tokens`` (alias of ``--prefill-chunk``) sets the chunk width.

Observability (DESIGN.md §16): ``--trace-out trace.json`` records the
full span timeline (request lifecycles, tick phases, kernel/plan
provenance) as Chrome trace-event JSON — load it at ui.perfetto.dev or
validate/summarize with ``python -m repro.serve.telemetry trace.json``.
``--metrics-json`` dumps the engine's counter + histogram registry;
``--log-json`` prints one JSON line of tick stats per engine tick and
arms the flight recorder, whose ring-buffer dump path is logged when
the engine dies (no-progress, soundness cross-check).
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time

import jax
import numpy as np


def build_engine(name: str, ecfg, *, reduced: bool = False, seed: int = 0,
                 ckpt_dir=None):
    """Model ``name`` (``arch`` or ``arch@mechanism``) behind an
    :class:`~repro.serve.engine.Engine` configured by ``ecfg``: params
    from ``ckpt_dir`` when given, else a random init from ``seed``.  The
    engine owns state layout: per-slot cursors always (ragged continuous
    batching), paged block tables when the family supports it."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn.module import unbox
    from repro.serve.engine import Engine

    cfg = get_config(name)
    if reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    params = unbox(api.init(jax.random.PRNGKey(seed)))
    if ckpt_dir:
        from repro.checkpoint import restore
        params, _ = restore(ckpt_dir, (params, None))[0]
    return Engine(api, params, ecfg, seed=seed)


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--attention", default=None)
    ap.add_argument("--reduced", action="store_true", default=False,
                    help="use the reduced per-family config (CPU scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--allocator", choices=("paged", "contiguous"),
                    default="paged")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged pool size (default: full capacity)")
    ap.add_argument("--prefill-chunk", "--chunk-tokens", type=int,
                    default=32, dest="prefill_chunk",
                    help="prefill chunk width in tokens (page-aligned; "
                         "--chunk-tokens is an alias)")
    ap.add_argument("--tick-budget", type=int, default=None,
                    help="max (padded) prefill tokens executed per engine "
                         "tick — enables chunked prefill-decode "
                         "interleaving (DESIGN.md §15); default: whole-"
                         "prompt admission")
    ap.add_argument("--scheduler", choices=("fifo", "priority", "prefix"),
                    default="fifo", help="admission policy")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=True,
                    help="disable the shared-prefix radix KV cache")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of common prompt prefix across requests")
    ap.add_argument("--sample", action="store_true",
                    help="temperature sampling instead of greedy decode")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON timeline here "
                         "(Perfetto-loadable; validate with "
                         "python -m repro.serve.telemetry PATH)")
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine metrics registry (counters + "
                         "bounded histograms) plus stats() here")
    ap.add_argument("--log-json", action="store_true",
                    help="one JSON line of tick stats per engine tick on "
                         "stdout; also arms the crash flight recorder")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    log = logging.getLogger("repro.launch.serve")

    from repro.serve.engine import EngineConfig, Request
    from repro.serve.telemetry import TelemetryConfig, write_trace

    # telemetry is opt-in: full span tracing when a trace sink is given,
    # flight-recorder-only (bounded ring, no event list) under
    # --log-json, and no Tracer otherwise — the engine hooks are
    # `if tel is None` guarded, so off means zero events and zero
    # allocation (proven by the analyzer's telemetry sync audit); the
    # tick phases' profiler annotations are entered either way.
    telemetry = None
    if args.trace_out:
        telemetry = TelemetryConfig(trace=True)
    elif args.log_json:
        telemetry = TelemetryConfig(trace=False)

    name = args.arch if not args.attention else f"{args.arch}@{args.attention}"
    eng = build_engine(
        name,
        EngineConfig(max_batch=args.max_batch,
                     max_len=args.max_len,
                     allocator=args.allocator,
                     page_size=args.page_size,
                     num_pages=args.num_pages,
                     prefill_chunk=args.prefill_chunk,
                     tick_budget=args.tick_budget,
                     prefix_cache=args.prefix_cache,
                     scheduler=args.scheduler,
                     greedy=not args.sample,
                     temperature=args.temperature,
                     telemetry=telemetry),
        reduced=args.reduced, seed=args.seed, ckpt_dir=args.ckpt_dir)
    cfg = eng.api.cfg

    rng = np.random.default_rng(args.seed)
    plen = max(1, min(args.prompt_len, args.max_len - 1))
    shared_len = max(0, min(args.shared_prefix, plen - 1))
    shared = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)
    t0 = time.perf_counter()
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            (plen - shared_len,)).astype(np.int32)
        prompt = np.concatenate([shared, tail])
        eng.submit(Request(i, prompt, max_new_tokens=args.new_tokens))

    on_tick = None
    if args.log_json:
        def on_tick(e, finished):
            # one line per tick, stable keys — cheap counter reads only,
            # never a full stats() (which walks the allocator)
            print(json.dumps({
                "tick": e._tick, "active": len(e.active),
                "admitting": len(e.admitting),
                "queued": len(e.scheduler), "finished": len(finished),
                "finished_total": e.counters["finished_requests"],
                "generated_tokens": e.counters["generated_tokens"],
                "prefill_tokens": e.counters["prefill_tokens"],
                "table_uploads": e.counters["table_uploads"],
                "paused_prefills": e.counters["paused_prefills"],
            }, sort_keys=True), flush=True)
    try:
        done = eng.run_to_completion(on_tick=on_tick)
    except RuntimeError as err:
        # _dump_on_error already wrote the flight recorder and embedded
        # its path in the message; restate it loudly for log scrapers
        log.error("engine aborted: %s", err)
        if "[flight recorder:" in str(err):
            path = str(err).rsplit("[flight recorder: ", 1)[1].rstrip("]")
            print(f"FLIGHT RECORDER: {path}", file=sys.stderr)
        return 1
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    log.info("served %d requests, %d tokens in %.2fs (%.1f tok/s)",
             len(done), total_tokens, dt, total_tokens / dt)
    log.info("prefill compiles: %d (buckets: %s)", eng.prefill_compiles,
             sorted(eng._prefill_buckets))
    if eng.paged:
        log.info("page pool: high-water %d / %d pages (page_size=%d)",
                 eng.alloc.high_water_pages, eng.alloc.num_pages - 1,
                 eng.alloc.page_size)
    stats = eng.stats()
    log.info("scheduler=%s prefill_tokens=%d prefix_hit_tokens=%d "
             "(%d request hits) forked_pages=%d evictions=%d "
             "cached_pages=%d", stats["scheduler"], stats["prefill_tokens"],
             stats["prefix_hit_tokens"], stats["prefix_hit_requests"],
             stats["forked_pages"], stats["evictions"],
             stats["cached_pages"])
    log.info("latency: ttft p50=%.1fms p99=%.1fms | itl p50=%.2fms "
             "p99=%.2fms | queued_ticks p99=%.0f | paused_prefills=%d",
             stats["ttft_ms_p50"], stats["ttft_ms_p99"],
             stats["itl_ms_p50"], stats["itl_ms_p99"],
             stats["queued_ticks_p99"], stats["paused_prefills"])
    for r in done[:3]:
        log.info("req %d -> %s...", r.request_id, r.output[:8])
    if args.trace_out:
        write_trace(eng.tel, args.trace_out)
        log.info("trace: wrote %s (%d events) — load at ui.perfetto.dev "
                 "or run `python -m repro.serve.telemetry %s`",
                 args.trace_out, len(eng.tel.events), args.trace_out)
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump({"stats": stats, "metrics": eng.metrics.snapshot()},
                      f, indent=2, sort_keys=True)
        log.info("metrics: wrote %s", args.metrics_json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
