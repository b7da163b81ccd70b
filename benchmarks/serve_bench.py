"""Serving benchmark: paged vs contiguous KV-cache allocators, the
shared-prefix radix-cache arm, plus the decode-tick kernel-vs-gather arm.

Drives the continuous-batching engine over the same synthetic ragged
workload under both allocators and reports, per arm:

  * decode-tick throughput (tokens/s over the serving loop)
  * prefill compile count (bucketed single-row prefill: bounded by the
    number of buckets, not the number of distinct prompt lengths)
  * cache-memory high-water mark in bytes (pages actually held for the
    paged arm; the full up-front reservation for the contiguous arm)

and asserts greedy-output parity between the arms.  The **shared-prefix
arm** re-runs a workload where most prompt tokens are a common prefix
(system-prompt traffic) under prefix-cache on / off / contiguous
(which can never hit) and gates on: identical outputs across all three,
``prefix_hit_tokens > 0``, strictly fewer prefill tokens computed with
the cache on, a prefill compile count no higher than cache-off, and
leak-free page accounting (``pages_in_use`` returns to exactly the
resident cached pages, and to zero after ``PrefixIndex.clear``) —
written to ``BENCH_serve_prefix.json``.  A second,
attention-level microbench times one paged decode tick under the
``paged`` backend (contiguous block-table gather) against the
``paged_pallas`` backend (block-table-native kernel, DESIGN.md §10) over
the same ragged pool, asserts numerical parity, and reports wall time
plus the analytic per-tick KV HBM traffic of each arm
(``BENCH_serve_decode.json``).  On hosts where the paged kernel family
has no native lowering the kernel arm runs in Pallas interpret mode —
its wall time is not meaningful, and the JSON says so **per arm** via
``kernel.interpret`` (the gather arm is plain XLA and always records
``interpret: false``), so the trend table can refuse to compare an
interpreted timing against a real one; the HBM-traffic model is
platform-independent.

``--sustained`` runs the sustained-load decode arm instead
(``BENCH_serve_sustained.json``): long decode streams at batch 1 vs the
full batch per allocator, gated on tok/s·batch *scaling* and on the
hard paged >= contiguous throughput requirement (DESIGN.md §14).

``--latency`` runs the Poisson open-loop latency arm instead
(``BENCH_serve_latency.json``, DESIGN.md §15): mixed long/short traffic
arrives on a pre-sampled Poisson schedule (tick-indexed, so both arms
see the bit-identical workload) and the same stream is served under
whole-prompt admission (``tick_budget=None``) vs chunked interleaved
admission (``tick_budget`` set).  Reports p50/p99 time-to-first-token
and inter-token latency per arm and hard-gates on (a) greedy output
parity across the two modes and (b) interleaved admission cutting the
in-flight p99 inter-token latency to <= half of whole-prompt admission
— the "one long prompt stalls every stream" failure mode.

Results are printed as CSV rows (same shape as benchmarks.run) and
written to ``BENCH_serve_*.json`` so CI records the serving perf
trajectory.

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke
  PYTHONPATH=src python benchmarks/serve_bench.py --smoke --sustained
  PYTHONPATH=src python benchmarks/serve_bench.py --smoke --latency
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def run_arm(api, params, cfg, *, allocator, prompts, new_tokens,
            engine_kw, prefix_cache=False):
    from repro.serve.engine import Engine, EngineConfig, Request

    eng = Engine(api, params, EngineConfig(allocator=allocator,
                                           prefix_cache=prefix_cache,
                                           **engine_kw))
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=new_tokens))
    ticks = 0
    done = []
    while eng.active or eng.queue:
        done.extend(eng.step())
        ticks += 1
        if ticks > 100_000:
            raise RuntimeError("engine did not drain")
    wall = time.perf_counter() - t0

    import numpy as np

    mcfg = api.cfg
    a = mcfg.attention
    itemsize = np.dtype(mcfg.cdtype).itemsize
    row_bytes = 2 * a.num_kv_heads * a.head_dim * itemsize  # k + v
    if allocator == "paged":
        hw_rows = eng.alloc.high_water_pages * eng.cfg.page_size
    else:
        hw_rows = engine_kw["max_batch"] * engine_kw["max_len"]
    from repro.analysis.serve_static import engine_desc

    tokens = sum(len(r.output) for r in done)
    stats = eng.stats()
    decode_ticks = max(stats["decode_ticks"], 1)
    telemetry = {}
    if eng.tel is not None and eng.tel.events is not None:
        # validate the recorded span tree in-process: the tracing arm's
        # gate is not just "it didn't crash" but "the trace is
        # well-formed Chrome trace-event JSON with balanced spans"
        from repro.serve.telemetry import (to_chrome_trace,
                                           validate_chrome_trace)
        v = validate_chrome_trace(to_chrome_trace(eng.tel))
        telemetry = {"telemetry_events": len(eng.tel.events),
                     "trace_valid": v["ok"],
                     "trace_errors": v["errors"][:5]}
    return {
        **telemetry,
        "allocator": allocator,
        "requests": len(done),
        "tokens": tokens,
        "decode_ticks": ticks,
        "wall_s": round(wall, 4),
        "tok_per_s": round(tokens / wall, 2),
        "prefill_compiles": eng.prefill_compiles,
        "decode_compiles": eng.decode_compiles,
        # the effective (post-clamp) engine config: the analyzer's
        # --check-bench re-derives the proven compile budget from this
        # record alone (repro.analysis.serve_static.cross_check_bench)
        "engine": engine_desc(eng),
        "retrace_budget": stats["retrace_budget"],
        # S1 gate material: batched block-table flushes, at most one per
        # decode tick no matter how many slots grew — and at most one per
        # prefill (not per chunk): the mirror is pushed once before the
        # chunk loop, so the prefill-side ratio is bounded by 1 even for
        # single-chunk prompts
        "table_uploads": stats["table_uploads"],
        "table_uploads_decode": stats["table_uploads_decode"],
        "table_uploads_prefill": stats["table_uploads_prefill"],
        "prefill_chunks": stats["prefill_chunks"],
        "table_uploads_per_tick": round(
            stats["table_uploads_decode"] / decode_ticks, 4),
        "table_uploads_per_prefill_chunk": round(
            stats["table_uploads_prefill"]
            / max(stats["prefill_chunks"], 1), 4),
        "cache_high_water_bytes": mcfg.num_layers * hw_rows * row_bytes,
        "prefill_tokens": stats["prefill_tokens"],
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "forked_pages": stats["forked_pages"],
        "evictions": stats["evictions"],
        "cached_pages": stats["cached_pages"],
        "pages_in_use_after_drain": stats.get("pages_in_use", 0),
    }, {r.request_id: r.output for r in done}


def prefix_workload(cfg, rng, *, n_req, shared_len, max_suffix):
    """Prompts dominated by one shared prefix: every request is
    ``prefix ++ private_suffix`` with ``len(suffix) <= max_suffix <=
    shared_len`` — at least half of all prompt tokens are shared."""
    import numpy as np

    prefix = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)
    prompts = []
    for _ in range(n_req):
        sl = int(rng.integers(1, max_suffix + 1))
        prompts.append(np.concatenate(
            [prefix, rng.integers(0, cfg.vocab_size, (sl,)).astype(np.int32)]))
    return prompts


def run_prefix_bench(api, params, cfg, *, rng, n_req, shared_len,
                     max_suffix, new_tokens, engine_kw):
    """Shared-prefix workload under cache-on / cache-off / contiguous.

    Returns the (gated) result dict for ``BENCH_serve_prefix.json``.
    The contiguous arm simply never hits — it is the no-paging baseline
    the parity assert extends over.
    """
    prompts = prefix_workload(cfg, rng, n_req=n_req, shared_len=shared_len,
                              max_suffix=max_suffix)
    shared_tokens = n_req * shared_len
    total_tokens = sum(len(p) for p in prompts)

    arms, outputs = {}, {}
    for name, allocator, cache in (("cache_on", "paged", True),
                                   ("cache_off", "paged", False),
                                   ("contiguous", "contiguous", False)):
        res, outs = run_arm(api, params, cfg, allocator=allocator,
                            prompts=prompts, new_tokens=new_tokens,
                            engine_kw=engine_kw, prefix_cache=cache)
        arms[name] = res
        outputs[name] = outs

    on, off = arms["cache_on"], arms["cache_off"]
    gates = {
        # exactness: cached-prefix reuse must not change a single token
        "parity": (outputs["cache_on"] == outputs["cache_off"]
                   == outputs["contiguous"]),
        # the cache actually fired and saved prefill compute
        "hit_tokens_positive": on["prefix_hit_tokens"] > 0,
        "fewer_prefill_tokens": on["prefill_tokens"] < off["prefill_tokens"],
        # suffix buckets are a subset of the cold buckets (chunk | page)
        "compiles_no_higher": (on["prefill_compiles"]
                               <= off["prefill_compiles"]),
        # refcounted release: everything not cached went back to the free
        # list (cache-off must drain to zero)
        "no_leak_on": (on["pages_in_use_after_drain"] == on["cached_pages"]),
        "no_leak_off": off["pages_in_use_after_drain"] == 0,
    }
    return {
        "requests": n_req,
        "shared_prefix_len": shared_len,
        "shared_token_fraction": round(shared_tokens / total_tokens, 3),
        "prompt_tokens_total": total_tokens,
        "arms": arms,
        "gates": gates,
        "ok": all(gates.values()),
    }


def decode_kernel_bench(*, batch, page_size, pages_per_slot, num_heads,
                        num_kv_heads, head_dim, iters, seed=0):
    """One paged decode tick: block-table gather vs block-table-native
    kernel over the same ragged page pool.  Returns the result dict
    (parity-gated) for ``BENCH_serve_decode.json``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.mechanism import (AttnShapes, MechanismParams,
                                      PagedLayout, Structural, execute_plan,
                                      plan_attention)
    from repro.kernels.ops import registry

    rng = np.random.default_rng(seed)
    num_pages = batch * pages_per_slot + 1
    pool_shape = (num_pages, num_kv_heads, page_size, head_dim)
    k_pool = jnp.asarray(rng.normal(size=pool_shape).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=pool_shape).astype(np.float32))
    q = jnp.asarray(rng.normal(
        size=(batch, 1, num_heads, head_dim)).astype(np.float32))
    # ragged cursors over a shared pool: distinct physical pages per row,
    # unmapped tail entries on the trash page 0 (exactly the engine layout)
    max_len = pages_per_slot * page_size
    lengths = rng.integers(1, max_len + 1, (batch,)).astype(np.int32)
    perm = rng.permutation(np.arange(1, num_pages))
    tables = np.zeros((batch, pages_per_slot), np.int32)
    nxt = 0
    for b in range(batch):
        used = -(-int(lengths[b]) // page_size)
        tables[b, :used] = perm[nxt:nxt + used]
        nxt += used
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(lengths)

    class _Cfg:
        mechanism = "inhibitor"
        causal = True
        sliding_window = None

    shapes = AttnShapes(
        batch=batch, n_q=1, n_k=pages_per_slot * page_size,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        has_cache=True, scalar_cursor=False, paged=True)
    params = MechanismParams(signed=True)
    layout = PagedLayout(tables, page_size)

    def arm(backend):
        cfg = _Cfg()
        cfg.backend = backend
        plan = plan_attention(cfg, shapes)
        structural = Structural(causal=True, window=None,
                                q_offset=lengths - 1, kv_valid_len=lengths)
        if backend == "paged_pallas":
            def tick(q_, kp, vp):
                return execute_plan(plan, q_, kp, vp, params=params,
                                    structural=structural, paged=layout)
        else:
            kj = jnp.arange(pages_per_slot * page_size)[None, :]
            mask = (kj < lengths[:, None])[:, None, None, :]

            def tick(q_, kp, vp):
                return execute_plan(plan, q_, kp, vp, params=params,
                                    mask=mask, paged=layout)
        # eager (un-jitted) warmup with concrete operands: on TPU this is
        # what triggers the kernel registry's per-shape autotune pass
        jax.block_until_ready(tick(q, k_pool, v_pool))
        fn = jax.jit(tick)
        out = jax.block_until_ready(fn(q, k_pool, v_pool))   # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(q, k_pool, v_pool))
        wall = (time.perf_counter() - t0) / iters
        # analytic FLOPs/bytes from walking the tick's jaxpr with the
        # shared platform cost table — replaces hand-computed traffic
        from repro.analysis import costmodel
        static = costmodel.roofline(
            costmodel.jaxpr_costs(jax.make_jaxpr(tick)(q, k_pool, v_pool)))
        return plan, out, wall, static

    plan_g, out_g, wall_g, static_g = arm("paged")
    plan_k, out_k, wall_k, static_k = arm("paged_pallas")
    parity = bool(np.allclose(np.asarray(out_g), np.asarray(out_k),
                              rtol=1e-4, atol=1e-5))

    # analytic per-tick KV-read HBM traffic (k + v, all kv heads):
    # the gather touches every block-table entry incl. the trash-page
    # tail; the kernel walks only pages below each row's cursor
    row_bytes = 2 * num_kv_heads * head_dim * 4      # f32 k + v per KV row
    gather_rows = batch * pages_per_slot * page_size
    kernel_rows = int(sum(-(-int(l) // page_size) * page_size
                          for l in np.asarray(lengths)))
    return {
        "batch": batch,
        "page_size": page_size,
        "pages_per_slot": pages_per_slot,
        "platform": registry.platform,
        "parity": parity,
        "gather": {
            "plan": plan_g.backend, "reason": plan_g.reason,
            # the gather arm is plain XLA — it never interprets anything
            "interpret": False,
            "tick_us": round(1e6 * wall_g, 1),
            "tok_per_s": round(batch / wall_g, 1),
            "kv_hbm_bytes_per_tick": gather_rows * row_bytes,
            "static": static_g,
        },
        "kernel": {
            "plan": plan_k.backend, "reason": plan_k.reason,
            # per-arm, per-family: True anywhere the paged kernel family
            # has no native lowering — the trend table refuses to compare
            # an interpret-mode timing against a real one
            "interpret": bool(registry.interpret_for("paged")),
            "tick_us": round(1e6 * wall_k, 1),
            "tok_per_s": round(batch / wall_k, 1),
            "kv_hbm_bytes_per_tick": kernel_rows * row_bytes,
            "static": static_k,
        },
    }


def sustained_bench(api, params, cfg, *, engine_kw, seed=0):
    """Sustained-load decode: long decode streams (tiny prompts, deep
    generations) per allocator at batch 1 vs the full batch, with enough
    queued requests that slots stay continuously occupied.

    Two gate families (DESIGN.md §14):

      * **scaling** — per allocator, full-batch tok/s must reach at least
        ``SCALING_MIN``x the batch-1 tok/s.  Batched decode amortizes the
        per-tick fixed costs (dispatch, the one table upload, the one d2h
        readback) across rows; an engine whose throughput does NOT scale
        with batch has reintroduced per-slot work into the tick.
      * **paged >= contiguous** — at full batch, the paged allocator must
        meet or beat contiguous tok/s.  This is the hard form of the
        ROADMAP "close the gather gap" claim: with the all-layer fused
        gather + clamped table buckets, paged attention reads the
        bucketed high-water window while contiguous always walks the full
        ``max_len`` buffer — on the provisioned-for-the-tail serving
        regime this bench models, paging must win outright, on the CPU
        fused-gather path, not just trail within tolerance.

    Outputs are parity-gated between allocators at each batch size.
    """
    import numpy as np

    SCALING_MIN = 1.5
    full_batch = engine_kw["max_batch"]
    rng = np.random.default_rng(seed)
    prompt_len = 4
    new_tokens = max(8, min(48, engine_kw["max_len"] - prompt_len - 2))

    arms: dict = {}
    outputs: dict = {}
    for allocator in ("contiguous", "paged"):
        arms[allocator] = {}
        outputs[allocator] = {}
        for name, batch in (("single", 1), ("full", full_batch)):
            kw = {**engine_kw, "max_batch": batch}
            n_req = 2 * batch
            prompts = [rng.integers(0, cfg.vocab_size,
                                    (prompt_len,)).astype(np.int32)
                       for _ in range(n_req)]
            res, outs = run_arm(api, params, cfg, allocator=allocator,
                                prompts=prompts, new_tokens=new_tokens,
                                engine_kw=kw)
            res["batch"] = batch
            arms[allocator][name] = res
            outputs[allocator][name] = outs
        # reseed so both allocators see identical prompt streams
        rng = np.random.default_rng(seed)

    # ---- tracing-overhead arm (DESIGN.md §16) ----
    # the identical paged full-batch workload served twice: telemetry
    # absent (eng.tel is None — every hook is a single None check) vs
    # full span tracing on.  Both must produce bit-identical outputs,
    # the enabled trace must validate as well-formed Chrome trace-event
    # JSON, and the enabled arm must keep TRACING_BUDGET of the disabled
    # throughput — the declared instrumentation budget; the measured
    # overhead % is also trend-tracked warn-only so drift is visible
    # long before the hard gate trips.
    TRACING_BUDGET = 0.60
    rng = np.random.default_rng(seed + 1)
    tkw = {**engine_kw, "max_batch": full_batch}
    tprompts = [rng.integers(0, cfg.vocab_size,
                             (prompt_len,)).astype(np.int32)
                for _ in range(2 * full_batch)]
    tracing_arms: dict = {}
    tracing_outs: dict = {}
    for name, extra in (("off", {}), ("on", {"telemetry": True})):
        tracing_arms[name], tracing_outs[name] = run_arm(
            api, params, cfg, allocator="paged", prompts=tprompts,
            new_tokens=new_tokens, engine_kw={**tkw, **extra})
    t_off = tracing_arms["off"]["tok_per_s"]
    t_on = tracing_arms["on"]["tok_per_s"]

    gates = {
        # exactness first: scaling numbers mean nothing off a wrong model
        "parity_single": (outputs["paged"]["single"]
                          == outputs["contiguous"]["single"]),
        "parity_full": (outputs["paged"]["full"]
                        == outputs["contiguous"]["full"]),
        # tok/s·batch scaling per allocator
        "scaling_contiguous": (
            arms["contiguous"]["full"]["tok_per_s"]
            >= SCALING_MIN * arms["contiguous"]["single"]["tok_per_s"]),
        "scaling_paged": (
            arms["paged"]["full"]["tok_per_s"]
            >= SCALING_MIN * arms["paged"]["single"]["tok_per_s"]),
        # the hard throughput gate: paged meets/beats contiguous
        "paged_beats_contiguous": (
            arms["paged"]["full"]["tok_per_s"]
            >= arms["contiguous"]["full"]["tok_per_s"]),
        # observability contract: tracing changes nothing but wall time,
        # the recorded timeline is well-formed, and the cost of tracing
        # stays inside the declared budget
        "tracing_parity": tracing_outs["off"] == tracing_outs["on"],
        "tracing_trace_valid": bool(tracing_arms["on"].get("trace_valid")),
        "tracing_enabled_budget": t_on >= TRACING_BUDGET * t_off,
        "tracing_disabled_noise": (
            t_off >= TRACING_BUDGET * arms["paged"]["full"]["tok_per_s"]),
    }
    return {
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "full_batch": full_batch,
        "scaling_min": SCALING_MIN,
        "arms": arms,
        "scaling": {
            alloc: round(arms[alloc]["full"]["tok_per_s"]
                         / max(arms[alloc]["single"]["tok_per_s"], 1e-9), 3)
            for alloc in ("contiguous", "paged")},
        "tracing": {
            "budget_ratio": TRACING_BUDGET,
            "off": {"tok_per_s": t_off},
            "on": {"tok_per_s": t_on,
                   "events": tracing_arms["on"].get("telemetry_events"),
                   "trace_valid": tracing_arms["on"].get("trace_valid")},
            "overhead_pct": round(100.0 * (1.0 - t_on / max(t_off, 1e-9)),
                                  2),
        },
        "gates": gates,
        "ok": all(gates.values()),
    }


def _latency_arm(api, params, cfg, *, tick_budget, prompts, new_tokens,
                 arrivals, engine_kw):
    """Serve one pre-sampled open-loop arrival schedule to completion.

    Arrivals are indexed by engine tick, not wall clock: request ``i``
    is submitted just before the first ``step()`` whose tick index is
    ``>= arrivals[i]``, whether or not the engine has caught up.  That
    keeps the offered workload bit-identical across arms (same prompts,
    same admission order, same queue pressure) so the output-parity
    gate is meaningful, while TTFT/ITL are still measured in wall-clock
    ms by the engine's per-tick timestamps.
    """
    from repro.serve.engine import Engine, EngineConfig, Request

    eng = Engine(api, params, EngineConfig(tick_budget=tick_budget,
                                           allocator="paged", **engine_kw))
    done = []
    tick = 0
    nxt = 0
    n = len(prompts)
    while nxt < n or eng.active or eng.admitting or len(eng.scheduler):
        while nxt < n and arrivals[nxt] <= tick:
            eng.submit(Request(nxt, prompts[nxt],
                               max_new_tokens=new_tokens[nxt]))
            nxt += 1
        done.extend(eng.step())
        tick += 1
        if tick > 200_000:
            raise RuntimeError("latency arm did not drain")

    from repro.analysis.serve_static import engine_desc

    s = eng.stats()
    lat = {
        k: {"p50": round(s[f"{k}_p50"], 3),
            "p99": round(s[f"{k}_p99"], 3),
            "max": round(eng._lat[k].max, 3),
            "samples": eng._lat[k].count}
        for k in ("ttft_ms", "itl_ms", "queued_ticks")
    }
    return {
        "tick_budget": tick_budget,
        "ticks": tick,
        "requests": len(done),
        "tokens": sum(len(r.output) for r in done),
        "inflight_peak": engine_kw["max_batch"],
        "paused_prefills": s["paused_prefills"],
        "prefill_chunks": s["prefill_chunks"],
        "engine": engine_desc(eng),
        "retrace_budget": s["retrace_budget"],
        **lat,
    }, {r.request_id: r.output for r in done}


def latency_bench(api, params, cfg, *, engine_kw, smoke, seed=0):
    """Poisson open-loop latency arm (DESIGN.md §15).

    Mixed traffic — a stream of short chat-sized prompts with long
    decodes, plus a few long prompts dropped into the middle of the
    stream — arrives on one pre-sampled Poisson (exponential
    inter-arrival) schedule.  The identical schedule is served twice:

      * **whole** — ``tick_budget=None``: an admission runs the full
        prefill schedule inside one tick, so every in-flight decode
        stream stalls for the entire long prompt.
      * **interleaved** — ``tick_budget`` set: prefill advances at most
        a budget's worth of (padded) chunk tokens per tick, between
        decode ticks, so victims keep streaming while the long prompt
        admits.

    Hard gates: greedy outputs bit-identical across the two modes
    (chunked admission may not change the model), and the interleaved
    arm's in-flight p99 inter-token latency must be <= ``ITL_P99_MAX``
    of the whole-prompt arm's — the headline continuous-batching claim.
    """
    import numpy as np

    ITL_P99_MAX = 0.5  # interleaved p99 ITL must be <= half of whole's

    if smoke:
        n_short, long_plen, budget = 8, 160, 16
        short_new, long_new, mean_gap = 24, 4, 3.0
    else:
        n_short, long_plen, budget = 24, 640, 2 * engine_kw["prefill_chunk"]
        short_new, long_new, mean_gap = 32, 8, 3.0

    rng = np.random.default_rng(seed)
    prompts, new_tokens = [], []
    # long prompts sit a third and two-thirds of the way into the
    # arrival order so short streams are mid-decode when they land
    long_at = {n_short // 3, (2 * n_short) // 3}
    for i in range(n_short):
        if i in long_at:
            prompts.append(rng.integers(0, cfg.vocab_size,
                                        (long_plen,)).astype(np.int32))
            new_tokens.append(long_new)
        prompts.append(rng.integers(0, cfg.vocab_size,
                                    (int(rng.integers(4, 13)),))
                       .astype(np.int32))
        new_tokens.append(short_new)
    gaps = rng.exponential(mean_gap, len(prompts))
    arrivals = np.floor(np.cumsum(gaps)).astype(int).tolist()

    arms: dict = {}
    outputs: dict = {}
    for name, tb in (("whole", None), ("interleaved", budget)):
        arms[name], outputs[name] = _latency_arm(
            api, params, cfg, tick_budget=tb, prompts=prompts,
            new_tokens=new_tokens, arrivals=arrivals, engine_kw=engine_kw)

    whole_p99 = arms["whole"]["itl_ms"]["p99"]
    inter_p99 = arms["interleaved"]["itl_ms"]["p99"]
    gates = {
        "parity": outputs["whole"] == outputs["interleaved"],
        "itl_p99_cut": inter_p99 <= ITL_P99_MAX * whole_p99,
    }
    return {
        "requests": len(prompts),
        "long_plen": long_plen,
        "tick_budget": budget,
        "mean_gap_ticks": mean_gap,
        "itl_p99_max_ratio": ITL_P99_MAX,
        "itl_p99_ratio": round(inter_p99 / max(whole_p99, 1e-9), 4),
        "arms": arms,
        "gates": gates,
        "ok": all(gates.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model/workload for CI")
    ap.add_argument("--sustained", action="store_true",
                    help="run ONLY the sustained-load decode arm "
                         "(batch-scaling + hard paged>=contiguous gates; "
                         "writes BENCH_serve_sustained.json)")
    ap.add_argument("--latency", action="store_true",
                    help="run ONLY the Poisson open-loop latency arm "
                         "(interleaved-vs-whole admission TTFT/ITL SLOs; "
                         "writes BENCH_serve_latency.json)")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--json", default=None,
                    help="output path (default BENCH_serve_<mode>.json)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import numpy as np
    import jax

    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn.module import unbox

    # max_len is deliberately ~8x the longest sequence the workload
    # reaches: contiguous decode always attends over (and rewrites) the
    # full max_len buffer, while paged decode clamps its block tables to
    # the bucketed high-water width — the serving regime (capacity
    # provisioned for the tail, typical sequences far shorter) where
    # paging earns its keep.  warmup="serve" pre-traces both arms'
    # ladders at engine construction, before the timed window opens.
    if args.smoke:
        cfg = get_config(args.arch).reduced(num_layers=2, d_model=32,
                                            d_ff=64, vocab_size=128)
        engine_kw = dict(max_batch=4, max_len=512, page_size=8,
                         prefill_chunk=8, warmup="serve")
        n_req, new_tokens, max_plen = args.requests or 10, 24, 40
    else:
        cfg = get_config(args.arch).reduced()
        engine_kw = dict(max_batch=8, max_len=1024, page_size=16,
                         prefill_chunk=32, warmup="serve")
        n_req, new_tokens, max_plen = args.requests or 32, 32, 160

    api = get_model(cfg)
    params = unbox(api.init(jax.random.PRNGKey(args.seed)))

    if args.latency:
        latency = latency_bench(api, params, cfg, engine_kw=engine_kw,
                                smoke=args.smoke, seed=args.seed)
        with open("BENCH_serve_latency.json", "w") as f:
            json.dump(latency, f, indent=2, sort_keys=True)
        for name in ("whole", "interleaved"):
            r = latency["arms"][name]
            print(f"serve_latency_{name},{r['itl_ms']['p99'] * 1e3:.1f},"
                  f"ttft_p50={r['ttft_ms']['p50']}ms;"
                  f"ttft_p99={r['ttft_ms']['p99']}ms;"
                  f"itl_p50={r['itl_ms']['p50']}ms;"
                  f"itl_p99={r['itl_ms']['p99']}ms;"
                  f"paused={r['paused_prefills']}", flush=True)
        print(f"serve_latency_gates,0,"
              f"{'OK' if latency['ok'] else 'FAIL ' + str(latency['gates'])}"
              f";itl_p99_ratio={latency['itl_p99_ratio']}"
              f" -> BENCH_serve_latency.json", flush=True)
        return 0 if latency["ok"] else 1

    if args.sustained:
        sustained = sustained_bench(api, params, cfg, engine_kw=engine_kw,
                                    seed=args.seed)
        with open("BENCH_serve_sustained.json", "w") as f:
            json.dump(sustained, f, indent=2, sort_keys=True)
        for alloc in ("contiguous", "paged"):
            for armname in ("single", "full"):
                r = sustained["arms"][alloc][armname]
                print(f"serve_sustained_{alloc}_{armname},"
                      f"{1e6 * r['wall_s'] / max(r['tokens'], 1):.1f},"
                      f"tok_per_s={r['tok_per_s']};batch={r['batch']}",
                      flush=True)
        tr = sustained["tracing"]
        print(f"serve_tracing,{tr['overhead_pct']:.2f},"
              f"off={tr['off']['tok_per_s']}tok/s;"
              f"on={tr['on']['tok_per_s']}tok/s;"
              f"events={tr['on']['events']};"
              f"trace_valid={tr['on']['trace_valid']}", flush=True)
        print(f"serve_sustained_gates,0,"
              f"{'OK' if sustained['ok'] else 'FAIL ' + str(sustained['gates'])}"
              f" -> BENCH_serve_sustained.json", flush=True)
        return 0 if sustained["ok"] else 1

    rng = np.random.default_rng(args.seed)
    lens = rng.integers(1, max_plen, (n_req,))
    prompts = [rng.integers(0, cfg.vocab_size, (int(l),)).astype(np.int32)
               for l in lens]

    results = {}
    outputs = {}
    print("name,us_per_call,derived")
    for allocator in ("contiguous", "paged"):
        res, outs = run_arm(api, params, cfg, allocator=allocator,
                            prompts=prompts, new_tokens=new_tokens,
                            engine_kw=engine_kw)
        results[allocator] = res
        outputs[allocator] = outs
        us_per_tok = 1e6 * res["wall_s"] / max(res["tokens"], 1)
        print(f"serve_{allocator},{us_per_tok:.1f},"
              f"tok_per_s={res['tok_per_s']};"
              f"compiles={res['prefill_compiles']};"
              f"hwm_bytes={res['cache_high_water_bytes']}", flush=True)

    parity = outputs["paged"] == outputs["contiguous"]
    results["parity"] = bool(parity)
    results["distinct_prompt_lens"] = int(len(set(map(int, lens))))
    # S1 gate (parity-checked above): the batched table flush means at
    # most ONE block-table upload per decode tick — regression here is
    # the per-slot upload loop coming back.  Same discipline on the
    # prefill side: one upload per admission, bounded by one per chunk
    upload_gate = (results["paged"]["table_uploads_per_tick"] <= 1.0
                   and results["paged"]["table_uploads_per_prefill_chunk"]
                   <= 1.0)
    results["table_upload_gate"] = bool(upload_gate)
    # the hard throughput gate (parity-checked above): with the
    # all-layer fused gather + clamped table buckets + warmed ladder,
    # paged serving must meet/beat the contiguous baseline on this
    # host's fused-gather path — warn-only trend tracking is over
    throughput_gate = (results["paged"]["tok_per_s"]
                       >= results["contiguous"]["tok_per_s"])
    results["throughput_gate"] = bool(throughput_gate)
    # measured-vs-proven compile soundness, computed from the recorded
    # configs the same way CI's --check-bench pass does
    compile_gate = all(
        arm["prefill_compiles"] <= arm["retrace_budget"]["prefill_proven"]
        and arm["decode_compiles"] <= arm["retrace_budget"]["decode_proven"]
        for arm in (results["paged"], results["contiguous"]))
    results["compile_gate"] = bool(compile_gate)
    path = args.json or f"BENCH_serve_{'smoke' if args.smoke else 'full'}.json"
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"serve_parity,0,{'OK' if parity else 'MISMATCH'} -> {path}",
          flush=True)
    print(f"serve_table_uploads,0,"
          f"per_tick={results['paged']['table_uploads_per_tick']};"
          f"per_prefill_chunk="
          f"{results['paged']['table_uploads_per_prefill_chunk']};"
          f"{'OK' if upload_gate else 'FAIL'}", flush=True)
    print(f"serve_throughput,0,"
          f"paged={results['paged']['tok_per_s']}tok/s vs "
          f"contiguous={results['contiguous']['tok_per_s']}tok/s;"
          f"{'OK' if throughput_gate else 'FAIL'}", flush=True)
    print(f"serve_compile_budget,0,"
          f"paged={results['paged']['decode_compiles']}/"
          f"{results['paged']['retrace_budget']['decode_proven']};"
          f"{'OK' if compile_gate else 'SOUNDNESS-FAIL'}", flush=True)

    # ---- shared-prefix radix-cache arm (DESIGN.md §11) ----
    if args.smoke:
        prefix_kw = dict(n_req=8, shared_len=24, max_suffix=12, new_tokens=6,
                         engine_kw=engine_kw)
    else:
        # page_size == prefill_chunk so suffix buckets are a subset of the
        # cold buckets (the compile-count gate)
        prefix_kw = dict(n_req=24, shared_len=96, max_suffix=48,
                         new_tokens=16,
                         engine_kw={**engine_kw, "page_size": 32})
    prefix_res = run_prefix_bench(api, params, cfg,
                                  rng=np.random.default_rng(args.seed + 1),
                                  **prefix_kw)
    with open("BENCH_serve_prefix.json", "w") as f:
        json.dump(prefix_res, f, indent=2, sort_keys=True)
    for name in ("cache_on", "cache_off", "contiguous"):
        r = prefix_res["arms"][name]
        us_per_tok = 1e6 * r["wall_s"] / max(r["tokens"], 1)
        print(f"serve_prefix_{name},{us_per_tok:.1f},"
              f"tok_per_s={r['tok_per_s']};"
              f"prefill_tokens={r['prefill_tokens']};"
              f"hit_tokens={r['prefix_hit_tokens']};"
              f"compiles={r['prefill_compiles']}", flush=True)
    print(f"serve_prefix_gates,0,"
          f"{'OK' if prefix_res['ok'] else 'FAIL ' + str(prefix_res['gates'])}"
          f" -> BENCH_serve_prefix.json", flush=True)

    # ---- decode-tick kernel-vs-gather arm (attention-level microbench) ----
    a = cfg.attention
    if args.smoke:
        decode_kw = dict(batch=4, page_size=8, pages_per_slot=8, iters=3)
    else:
        decode_kw = dict(batch=8, page_size=16, pages_per_slot=16, iters=20)
    decode = decode_kernel_bench(
        num_heads=a.num_heads, num_kv_heads=a.num_kv_heads,
        head_dim=a.head_dim, seed=args.seed, **decode_kw)
    with open("BENCH_serve_decode.json", "w") as f:
        json.dump(decode, f, indent=2, sort_keys=True)
    for armname in ("gather", "kernel"):
        r = decode[armname]
        print(f"serve_decode_{armname},{r['tick_us']:.1f},"
              f"tok_per_s={r['tok_per_s']};"
              f"kv_hbm_bytes={r['kv_hbm_bytes_per_tick']}", flush=True)
    print(f"serve_decode_parity,0,"
          f"{'OK' if decode['parity'] else 'MISMATCH'} -> "
          f"BENCH_serve_decode.json", flush=True)
    return 0 if (parity and decode["parity"] and prefix_res["ok"]
                 and upload_gate and compile_gate
                 and throughput_gate) else 1


if __name__ == "__main__":
    sys.exit(main())
