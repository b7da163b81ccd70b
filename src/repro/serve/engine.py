"""Batched serving engine with continuous batching.

Design (vLLM-style scheduling on a slot pool, TPU-friendly static shapes):

  * A fixed pool of ``max_batch`` slots backs one layer-stacked KV cache
    with **per-slot cursors** (ragged decode is exact — each row attends
    over its own valid prefix only).  The cache is **paged** by default:
    KV rows live in a shared page pool behind per-slot block tables
    (`serve.kvcache.PagedAllocator`), so memory tracks actual tokens held
    instead of ``max_batch * max_len`` worst case.  ``allocator=
    "contiguous"`` keeps the dense per-slot buffers as the baseline arm.
  * Incoming requests queue; whenever a slot frees, the next request is
    admitted and its prompt is prefilled as a **single row** (batch 1 —
    no ``max_batch``× broadcast) in fixed-size chunks.  The final partial
    chunk is padded up to a power-of-two **bucket**, bounding jit
    retraces to the number of buckets instead of the number of distinct
    prompt lengths; near ``max_len`` the bucketed chunk is left-shifted
    over already-written positions (idempotent rewrites of identical KV
    rows) so the write window never overruns the buffer.
  * **Continuous batching** (DESIGN.md §15): with ``EngineConfig.
    tick_budget`` set, prefill chunks are scheduled *between* decode
    ticks — the scheduler's ``prefill_quota`` token-budget policy decides
    how many prompt tokens each tick spends on chunked prefill while
    every active slot keeps decoding, so one long prompt can no longer
    stall in-flight streams.  A partially-prefilled admission is
    first-class engine state (``Engine.admitting``: slot claimed, prefix
    credit mounted, schedule partially executed); page growth and CoW
    forks happen lazily, per chunk batch actually executed.  With
    ``tick_budget=None`` (default) the whole schedule still runs inside
    the admission tick — same code path, same trace signatures.
  * Every engine tick runs one decode step for all active slots together
    (inactive rows compute garbage that is ignored — static shapes, no
    recompilation; under paging their scatter lands on the reserved
    trash page).  Mid-prefill rows ride through decode too: their device
    cursor stays pinned at the resume position, so each tick's garbage
    write lands inside the next chunk's rewrite window (or on the trash
    page at a page boundary) — never on a shared or already-final row.
  * A request finishes on EOS or at max_new_tokens — including an EOS
    produced by prefill itself, which finishes the request at admission,
    same tick.  Slots whose cache hits ``max_len`` are hard-stopped
    (``Request.truncated``) instead of silently clamping writes; prompts
    with ``prompt_len >= max_len`` are rejected at submit.
  * Under paging, finished requests feed a **shared-prefix radix index**
    (`serve.prefix.PrefixIndex`, DESIGN.md §11): admission mounts the
    longest page-aligned cached prefix into the new slot's block table
    (refcount++, no copy) and prefills only the uncached suffix.  Pages
    are copy-on-write — the only engine write that can land below the
    mounted prefix (a near-``max_len`` bucketed chunk left-shifting over
    already-written positions) forks the touched shared pages first.
    Admission order is a pluggable ``Scheduler`` policy (fifo /
    priority / prefix-affinity — serve.scheduler); per-token streaming
    callbacks and prefix/fork/eviction counters surface through
    ``Request.on_token`` and ``Engine.stats()``.

The same engine drives the `serve` launcher and the serving example; on a
mesh the step functions are jit'd with sharded params (TP) and replicated
small decode batches.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import KVCache, PagedKVCache
from repro.models.registry import ModelApi
from repro.serve.kvcache import PagedAllocator, SlotAllocator
from repro.serve.prefix import PrefixIndex
from repro.serve.scheduler import make_scheduler
from repro.serve.telemetry import MetricsRegistry, dump_flight, make_tracer

log = logging.getLogger("repro.serve")

# families whose decode state is entirely cursor-guarded: KV rows beyond
# the cursor are invalid by construction, so padded prefill buckets are
# safe.  Recurrent carries (ssm/hybrid/rwkv) would absorb pad tokens, so
# those families prefill in exact-length chunks instead.
_KV_FAMILIES = ("dense", "moe", "vlm")
_PAGEABLE_FAMILIES = ("dense", "moe", "hybrid", "vlm")


# eq=False: requests are identity objects (schedulers remove them from
# queues by identity; a generated __eq__ would tuple-compare the ndarray
# prompt and raise on same-id requests)
@dataclasses.dataclass(eq=False)
class Request:
    request_id: int
    prompt: np.ndarray             # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    priority: int = 0              # larger admits first (priority policy)
    # streaming: called as on_token(request, token) for every generated
    # token, the prefill-produced first token included, in order
    on_token: Optional[Callable[["Request", int], None]] = None
    # filled by the engine:
    output: Optional[list] = None
    truncated: bool = False        # hard-stopped at max_len / page pool dry
    arrival: int = -1              # submit order (scheduler tiebreak)
    # latency accounting (Engine.stats aggregates p50/p99): stamped from
    # one wall-clock read per tick, so the counters cost no extra syscalls
    queued_ticks: int = -1         # ticks spent waiting for a slot
    ttft_ms: float = -1.0          # submit -> first token
    _t_submit: float = -1.0
    _t_last: float = -1.0          # previous token's tick timestamp
    _tick_submit: int = -1


@dataclasses.dataclass
class _PartialPrefill:
    """A chunked admission in flight: slot claimed, prefix credit
    mounted, schedule partially executed — first-class engine state
    (``Engine.admitting``, DESIGN.md §15).  ``pos`` is the resume
    point: prompt tokens covered so far (device KV rows [0, pos) are
    final); the slot's device cursor is pinned there between ticks."""
    req: Request
    schedule: List[Tuple[int, int]]
    credit: int = 0                # prefix-cache tokens mounted at staging
    next_chunk: int = 0            # index of the first unexecuted chunk
    pos: int = 0                   # tokens covered (== credit at staging)
    executed: int = 0              # chunks run so far (0 => clean unwind)
    last_tok: Optional[int] = None # the prefill-produced first token
    paused: bool = False           # pool-dry pause seen since last chunk
                                   # batch (telemetry emits resumed once)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_len: int = 512
    greedy: bool = True            # False: temperature sampling
    temperature: float = 1.0
    allocator: str = "paged"       # "paged" | "contiguous"
    page_size: int = 16
    num_pages: Optional[int] = None   # paged pool size (None: full capacity)
    prefill_chunk: int = 32        # max tokens per prefill step (pow2)
    prefix_cache: bool = True      # shared-prefix radix index over the
                                   # paged pool (DESIGN.md §11); no-op for
                                   # contiguous slots / recurrent carries
    tick_budget: Optional[int] = None  # continuous batching: max tokens
                                   # (decode + padded prefill-chunk
                                   # widths) one tick may execute.  None:
                                   # whole-prompt admission (legacy).
                                   # The scheduler's prefill_quota policy
                                   # splits it (decode-first by default);
                                   # ignored for recurrent families,
                                   # whose carries would absorb the
                                   # interleaved ticks' pad garbage
    scheduler: Any = "fifo"        # admission policy name or Scheduler
                                   # instance ("fifo"|"priority"|"prefix")
    telemetry: Any = None          # observability (DESIGN.md §16): None/
                                   # False disables every hook (zero
                                   # overhead — no events, no timestamps,
                                   # no allocation); True/"on" records
                                   # the full span trace + flight ring;
                                   # "flight" keeps only the crash ring;
                                   # or a telemetry.TelemetryConfig /
                                   # Tracer instance
    warmup: str = "none"           # "decode": pre-trace the decode step's
                                   # proven signature ladder (and autotune
                                   # native kernels) at construction, so
                                   # no serving tick ever compiles;
                                   # "serve": additionally pre-trace the
                                   # proven prefill chunk buckets — the
                                   # whole serving path compiles up front


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def clamp_prefill_chunk(chunk: int, max_len: int) -> int:
    """Engine-effective prefill chunk: a power of two no larger than half
    the (pow2-rounded) context.  Pure — the static analyzer re-derives
    the compile budget from recorded configs with this exact function."""
    return min(_next_pow2(chunk), _next_pow2(max_len) >> 1 or 1)


def prefill_schedule(prompt_len: int, *, chunk: int, max_len: int,
                     bucketed: bool, start: int = 0) -> List[Tuple[int, int]]:
    """(start, width) chunks covering [start, prompt_len).  Full chunks
    are exact; for cursor-guarded (bucketed) families the final partial
    chunk is padded to a power-of-two bucket and, near max_len,
    left-shifted over already-written positions (rewrites are
    idempotent).  Pure function of the config — both the engine and
    ``repro.analysis.serve_static``'s retrace-budget proof call it, so
    the proof enumerates exactly what the engine will trace."""
    out: List[Tuple[int, int]] = []
    pos = start
    while pos < prompt_len:
        take = min(chunk, prompt_len - pos)
        if bucketed:
            cb = _next_pow2(take)
            s = max(0, min(pos, max_len - cb))
        else:
            cb, s = take, pos
        out.append((s, cb))
        pos += take
    return out


def decode_table_width(longest: int, *, page_size: int,
                       pages_per_slot: int) -> int:
    """Bucketed block-table width for a decode tick whose longest active
    row holds ``longest`` positions (read + the written KV row), rounded
    up to a power of two.  Pure — shared with the static analyzer's
    decode-bucket enumeration."""
    need = -(-longest // page_size)
    return min(pages_per_slot, _next_pow2(max(need, 1)))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _jit_pool_page_copy(k_pool, v_pool, old, new):
    """Copy physical page ``old`` -> ``new`` in the stacked
    (L, num_pages, h_kv, page_size, d) K/V pools.  The pools are donated,
    so XLA aliases the buffers and the copy is O(page), not a fresh
    pool-sized allocation (the CoW fork path — Engine._copy_page)."""
    return (k_pool.at[:, new].set(k_pool[:, old]),
            v_pool.at[:, new].set(v_pool[:, old]))


class Engine:
    def __init__(self, api: ModelApi, params, cfg: EngineConfig, *,
                 seed: int = 0):
        if cfg.allocator not in ("paged", "contiguous"):
            raise ValueError(f"unknown allocator {cfg.allocator!r}")
        self.api = api
        self.params = params
        self.cfg = dataclasses.replace(
            cfg, prefill_chunk=clamp_prefill_chunk(cfg.prefill_chunk,
                                                   cfg.max_len))
        fam = api.cfg.family
        self.paged = cfg.allocator == "paged" and fam in _PAGEABLE_FAMILIES
        if cfg.allocator == "paged" and not self.paged:
            log.info("family %r has no pageable KV cache; using contiguous "
                     "slots", fam)
        forced = getattr(api.cfg.attention, "backend", None)
        if forced == "paged_pallas":
            # the paged decode kernel is single-query; prefill chunks are
            # multi-query, so an engine-wide force can never run — fail at
            # construction, not deep inside the first admission
            raise ValueError(
                "backend='paged_pallas' cannot be forced engine-wide: "
                "prefill chunks are multi-query and the paged decode "
                "kernel is single-query (n_q=1).  Leave backend=None — "
                "the planner selects paged_pallas for TPU decode ticks "
                "automatically")
        if self.paged:
            # downgrade (don't crash) when the plan could never select the
            # paged backend: mechanism without a 'paged' entry, a config
            # that forces another backend, integer compute lanes, ...
            ok, why = self._paged_eligible()
            if not ok:
                log.info("paged cache unavailable (%s); using contiguous "
                         "slots", why)
                self.paged = False
        if forced == "paged" and not self.paged:
            raise ValueError(
                f"backend='paged' forced but the engine is backed by "
                f"contiguous slots (allocator={cfg.allocator!r}, family "
                f"{fam!r}) — it needs allocator='paged' and a pageable "
                f"family")
        self._bucketed = fam in _KV_FAMILIES
        if self.paged:
            self.alloc = PagedAllocator(cfg.max_batch, cfg.max_len,
                                        cfg.page_size, cfg.num_pages)
            self.states = api.init_states(
                cfg.max_batch, cfg.max_len, per_slot=True, paged=True,
                page_size=cfg.page_size, num_pages=self.alloc.num_pages)
        else:
            self.alloc = SlotAllocator(cfg.max_batch)
            self.states = api.init_states(cfg.max_batch, cfg.max_len,
                                          per_slot=True)
        # shared-prefix radix cache: page-aligned prefixes of finished
        # requests stay resident and are mounted at admission.  Recurrent
        # carries (hybrid mamba) cannot skip prefix compute — their state
        # at the suffix depends on running the whole prefix — so the
        # index is KV-pure families only.
        self.prefix: Optional[PrefixIndex] = None
        if self.paged and cfg.prefix_cache and fam in _KV_FAMILIES:
            self.prefix = PrefixIndex(self.alloc)
            self.alloc.attach_reclaimer(self._reclaim_pages)
        elif cfg.prefix_cache and self.paged:
            log.info("prefix cache unavailable for family %r (recurrent "
                     "carries cannot skip prefill)", fam)
        self.scheduler = make_scheduler(cfg.scheduler)
        self.active: Dict[int, Request] = {}     # slot -> request
        # slot -> in-flight chunked admission (insertion order == staging
        # order; resumed FIFO each tick before new admissions)
        self.admitting: Dict[int, _PartialPrefill] = {}
        if cfg.tick_budget is not None:
            if cfg.tick_budget < 1:
                raise ValueError(
                    f"tick_budget must be >= 1 (or None), got "
                    f"{cfg.tick_budget}")
            if not self._bucketed:
                log.info("family %r prefills exact-length whole prompts "
                         "(recurrent carries); tick_budget ignored", fam)
        # metrics registry (DESIGN.md §16): the counters dict is owned by
        # the registry and aliased here, so every existing counter key
        # keeps working while --metrics-json gets one unified snapshot
        self.metrics = MetricsRegistry()
        self.metrics.counters.update({
            "prefix_hit_tokens": 0, "prefix_hit_requests": 0,
            "forked_pages": 0, "prefill_tokens": 0,
            "generated_tokens": 0, "finished_requests": 0,
            "table_uploads": 0, "table_uploads_decode": 0,
            "table_uploads_prefill": 0, "decode_ticks": 0,
            "prefill_chunks": 0, "paused_prefills": 0})
        self.counters: Dict[str, int] = self.metrics.counters
        self._arrival = 0
        self._tick = 0
        self._admission_backoff = False
        self._prefill_stalled = False
        self._progressed = False
        # per-request latency samples (finished or streaming): bounded
        # reservoir histograms — stats() reports p50/p99 over the
        # reservoir, O(capacity) memory however long the engine runs
        self._lat = {k: self.metrics.histogram(k)
                     for k in ("ttft_ms", "itl_ms", "queued_ticks")}
        # span tracer + flight recorder, or None (the zero-overhead
        # default): every hook below is one attribute load + is-None
        # guard, and the emit path is statically audited to perform no
        # host<->device transfers (analysis.serve_static
        # .audit_telemetry_file)
        self.tel = make_tracer(cfg.telemetry)
        self._key = jax.random.PRNGKey(seed)
        self.decode_plan = self._plan_decode()
        if self.decode_plan is not None:
            log.info("engine decode %s [max_batch=%d max_len=%d alloc=%s]",
                     self.decode_plan.trace_line(), cfg.max_batch,
                     cfg.max_len, "paged" if self.paged else "contiguous")
        if self.tel is not None:
            self.tel.set_meta("engine", {
                "family": fam, "max_batch": cfg.max_batch,
                "max_len": cfg.max_len,
                "allocator": "paged" if self.paged else "contiguous",
                "page_size": cfg.page_size,
                "prefill_chunk": self.cfg.prefill_chunk,
                "tick_budget": cfg.tick_budget,
                "prefix_cache": self.prefix is not None})
            if self.decode_plan is not None:
                # plan provenance rides the trace: why this backend
                self.tel.set_meta("decode_plan", {
                    "mechanism": self.decode_plan.mechanism,
                    "backend": self.decode_plan.backend,
                    "reason": self.decode_plan.reason})
        # trace-counting wrappers: the wrapped python body runs only while
        # jax traces a NEW input signature, so these counters are live
        # compile counts — checked against the proven retrace budget
        # (repro.analysis.serve_static; measured > proven = soundness bug)
        self._decode_traces = 0
        self._prefill_traces = 0
        self._jit_decode = jax.jit(
            self._trace_counted(self._decode_step, "_decode_traces"))
        self._jit_prefill_chunk = jax.jit(
            self._trace_counted(self._prefill_chunk, "_prefill_traces"))
        self._prefill_buckets: set = set()   # chunk widths handed to jit
        self._decode_table_buckets: set = set()  # high-water table widths
        # host block tables (alloc.block_tables) are authoritative; the
        # device mirror refreshes lazily in ONE batched upload per tick
        self._tables_dirty = False
        self._retrace_budget_cache: Optional[Dict[str, Any]] = None
        if self.cfg.warmup not in ("none", "decode", "serve"):
            raise ValueError(f"unknown warmup policy {self.cfg.warmup!r} "
                             f"(expected 'none', 'decode' or 'serve')")
        if self.cfg.warmup in ("decode", "serve"):
            self._warmup_decode()
        if self.cfg.warmup == "serve":
            self._warmup_prefill()

    # ---- planning / introspection ----
    @property
    def queue(self):
        """The scheduler, exposed under the old attribute name (len() /
        truthiness keep meaning 'requests waiting for admission')."""
        return self.scheduler

    def stats(self) -> Dict[str, int]:
        """Engine-level serving counters: prefix-cache effectiveness
        (``prefix_hit_tokens`` — prompt tokens served from cached pages
        instead of prefill), copy-on-write activity (``forked_pages``),
        cache churn (``evictions``, pages LRU-evicted under pool
        pressure), plus throughput/compile accounting."""
        s = dict(self.counters)
        s["prefill_compiles"] = self.prefill_compiles
        s["decode_compiles"] = self.decode_compiles
        s["retrace_budget"] = self.retrace_budget()
        s["scheduler"] = getattr(self.scheduler, "name",
                                 type(self.scheduler).__name__)
        if self.prefix is not None:
            s["evictions"] = self.prefix.evictions
            s["cached_pages"] = self.prefix.cached_pages
            s["prefix_lookups_hit"] = self.prefix.hits
            s["prefix_lookups_miss"] = self.prefix.misses
        else:
            s["evictions"] = 0
            s["cached_pages"] = 0
        if self.paged:
            s["pages_in_use"] = self.alloc.pages_in_use
            s["high_water_pages"] = self.alloc.high_water_pages
        s["inflight_prefills"] = len(self.admitting)
        # per-request latency percentiles, fed by tick timestamps:
        # ttft_ms (submit -> first token), itl_ms (token -> next token,
        # in-flight streams included), queued_ticks (submit -> slot).
        # Backed by bounded reservoir histograms (telemetry.Histogram);
        # latency_samples reports the true observation counts
        for k, h in self._lat.items():
            s[f"{k}_p50"] = h.percentile(50)
            s[f"{k}_p99"] = h.percentile(99)
        s["latency_samples"] = {k: h.count for k, h in self._lat.items()}
        return s

    def _reclaim_pages(self, need: int) -> int:
        """Allocator reclaim hook: LRU-evict cached prefix pages, and
        surface the eviction on the tick timeline when tracing (the
        allocator calls this only under pool pressure — never on the
        steady-state path, so the hook costs nothing per tick)."""
        freed = self.prefix.evict(need)
        if self.tel is not None and freed:
            self.tel.instant("eviction", need_pages=need, freed_pages=freed)
        return freed

    def _paged_eligible(self):
        """(ok, why_not) for backing this model's decode with the paged
        pool — probed up front so ineligibility degrades to contiguous
        slots instead of raising out of plan_attention."""
        from repro.core.mechanism import (AttnShapes, backend_eligible,
                                          get_mechanism,
                                          resolve_mechanism_name)

        acfg = self.api.cfg.attention
        forced = getattr(acfg, "backend", None)
        if forced not in (None, "paged"):
            return False, f"config forces backend={forced!r}"
        shapes = AttnShapes(
            batch=self.cfg.max_batch, n_q=1, n_k=self.cfg.max_len,
            num_heads=acfg.num_heads, num_kv_heads=acfg.num_kv_heads,
            head_dim=acfg.head_dim, dtype=self.api.cfg.cdtype,
            has_cache=True, scalar_cursor=False, paged=True)
        return backend_eligible("paged", acfg, shapes,
                                get_mechanism(resolve_mechanism_name(acfg)))

    def _plan_decode(self):
        """Inspectable attention plan for the steady-state decode tick
        (per-slot ragged cursors; paged pool or full-slot KV buffer).
        None for attention-free families (rwkv)."""
        from repro.core.mechanism import AttnShapes, plan_attention

        mcfg = self.api.cfg
        if mcfg.family == "ssm":
            return None
        acfg = mcfg.attention
        if self.paged:
            n_k = self.alloc.pages_per_slot * self.cfg.page_size
        else:
            n_k = self.cfg.max_len
        shapes = AttnShapes(
            batch=self.cfg.max_batch, n_q=1, n_k=n_k,
            num_heads=acfg.num_heads, num_kv_heads=acfg.num_kv_heads,
            head_dim=acfg.head_dim, dtype=mcfg.cdtype, has_cache=True,
            scalar_cursor=False, paged=self.paged)
        plan = plan_attention(acfg, shapes)
        if (plan.backend == "paged"
                and getattr(acfg, "backend", None) is None
                and not getattr(acfg, "use_kernel", False)):
            # under these exact conditions models.transformer.lm_step
            # hoists ONE whole-model page gather out of the layer scan
            # (fused_gather_applies) — surface it in the inspectable plan
            plan = dataclasses.replace(
                plan, reason=plan.reason + "; all-layer fused gather "
                "hoisted out of the layer scan (DESIGN.md §14)")
        return plan

    @property
    def prefill_compiles(self) -> int:
        """Number of distinct prefill traces (== compiles).  Bounded by
        the bucket count for cursor-guarded families, not by the number
        of distinct prompt lengths."""
        try:
            n = self._jit_prefill_chunk._cache_size()
            if n:
                return n
        except Exception:  # noqa: BLE001 — private jit API; fall back
            pass
        return max(len(self._prefill_buckets), self._prefill_traces)

    @property
    def decode_compiles(self) -> int:
        """Number of distinct decode traces (== compiles).  Bounded by
        the clamped block-table width buckets (log2(pages_per_slot)+1)
        under paging, 1 for contiguous slots."""
        try:
            n = self._jit_decode._cache_size()
            if n:
                return n
        except Exception:  # noqa: BLE001 — private jit API; fall back
            pass
        return self._decode_traces

    def _trace_counted(self, fn, attr: str):
        """Wrap a step function so jit tracing bumps ``self.<attr>`` —
        the wrapper body only runs on a cache miss, making the counter a
        live compile count."""
        @functools.wraps(fn)
        def counted(*args):
            setattr(self, attr, getattr(self, attr) + 1)
            return fn(*args)
        return counted

    def retrace_budget(self) -> Dict[str, Any]:
        """Proven compile budget for this engine's config, as derived by
        the static analyzer (``repro.analysis.serve_static``).  The live
        ``prefill_compiles`` / ``decode_compiles`` counters must never
        exceed the proven counts."""
        if self._retrace_budget_cache is None:
            from repro.analysis.serve_static import retrace_budget

            b = retrace_budget(
                bucketed=self._bucketed, paged=self.paged,
                max_len=self.cfg.max_len,
                prefill_chunk=self.cfg.prefill_chunk,
                page_size=self.cfg.page_size,
                pages_per_slot=(self.alloc.pages_per_slot
                                if self.paged else None),
                prefix_cache=self.prefix is not None)
            self._retrace_budget_cache = {
                "prefill_proven": b["prefill"]["proven"],
                "decode_proven": b["decode"]["proven"],
                "chunk_resume_closed": b["chunk_resume"]["closed"],
                "within_declared": b["within_budget"]}
        return dict(self._retrace_budget_cache)

    # ---- jitted kernels ----
    def _next_key(self) -> jax.Array:
        """Per-step sampling key.  Greedy decoding takes argmax — the key
        is dead — so the host-side ``jax.random.split`` is skipped
        entirely and every step reuses the root key (bit-identical
        outputs either way; sampling mode still splits per step)."""
        if self.cfg.greedy:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    def _select(self, logits, key):
        """(n, V) logits -> (n,) int32 next tokens (greedy or sampled)."""
        if self.cfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = max(self.cfg.temperature, 1e-6)
        return jax.random.categorical(key, logits / t, axis=-1).astype(
            jnp.int32)

    def _decode_step(self, params, tokens, states, key):
        logits, new_states = self.api.step(params, tokens, states, None)
        nxt = self._select(logits[:, -1], key)
        return nxt, new_states

    def _prefill_chunk(self, params, tokens, states, last_idx, key):
        """One single-row prefill chunk: tokens (1, cb) into batch-1 state
        view.  ``last_idx`` (traced) points at the final *real* token —
        bucket padding sits after it and is causally invisible to it."""
        logits, new_states = self.api.step(params, tokens, states, None)
        lg = jax.lax.dynamic_index_in_dim(logits[0], last_idx, axis=0,
                                          keepdims=False)
        nxt = self._select(lg[None], key)[0]
        return nxt, new_states

    # ---- batch-1 state views (single-row prefill) ----
    def _slot_view(self, slot: int):
        st = self.states
        from repro.models.transformer import LayerState

        if isinstance(st, LayerState):
            kv = st.kv
            if isinstance(kv, PagedKVCache):
                # pools are shared across slots — only table/cursor narrow
                kv_v = PagedKVCache(kv.k, kv.v,
                                    kv.block_tables[:, slot:slot + 1],
                                    kv.length[:, slot:slot + 1])
            else:
                kv_v = KVCache(kv.k[:, slot:slot + 1], kv.v[:, slot:slot + 1],
                               kv.length[:, slot:slot + 1])
            ssm = st.ssm[:, slot:slot + 1] if st.ssm is not None else None
            conv = st.conv[:, slot:slot + 1] if st.conv is not None else None
            return LayerState(kv=kv_v, ssm=ssm, conv=conv)
        return jax.tree.map(lambda x: x[:, slot:slot + 1], st)

    def _merge_view(self, slot: int, view):
        st = self.states
        from repro.models.transformer import LayerState

        if isinstance(st, LayerState):
            kv, kvv = st.kv, view.kv
            if isinstance(kv, PagedKVCache):
                # take the updated pools wholesale (writes landed in this
                # slot's pages only); splice table/cursor rows back
                kv_n = PagedKVCache(
                    kvv.k, kvv.v,
                    kv.block_tables.at[:, slot].set(kvv.block_tables[:, 0]),
                    kv.length.at[:, slot].set(kvv.length[:, 0]))
            else:
                kv_n = KVCache(kv.k.at[:, slot].set(kvv.k[:, 0]),
                               kv.v.at[:, slot].set(kvv.v[:, 0]),
                               kv.length.at[:, slot].set(kvv.length[:, 0]))
            ssm = (st.ssm.at[:, slot].set(view.ssm[:, 0])
                   if st.ssm is not None else None)
            conv = (st.conv.at[:, slot].set(view.conv[:, 0])
                    if st.conv is not None else None)
            self.states = LayerState(kv=kv_n, ssm=ssm, conv=conv)
        else:
            self.states = jax.tree.map(
                lambda x, vv: x.at[:, slot].set(vv[:, 0]), st, view)

    @staticmethod
    def _set_view_cursor(view, value: int):
        """Pin the batch-1 view's KV cursor (bucketed chunks advance it by
        the padded width; the true position is host-known)."""
        kv = view.kv
        return view._replace(kv=kv._replace(
            length=jnp.full_like(kv.length, value)))

    # ---- prefill scheduling ----
    def _prefill_schedule(self, prompt_len: int,
                          start: int = 0) -> List[Tuple[int, int]]:
        """(start, width) chunks covering [start, prompt_len).  Full
        chunks are exact; for cursor-guarded families the final partial
        chunk is padded to a power-of-two bucket and, near max_len,
        left-shifted over already-written positions (rewrites are
        idempotent — and when ``start`` is a prefix-cache credit, a
        left shift below it lands on shared pages, which admission forks
        first: DESIGN.md §11).  ``start > 0`` requires cached KV rows at
        [0, start) — the prefix credit."""
        return prefill_schedule(prompt_len, chunk=self.cfg.prefill_chunk,
                                max_len=self.cfg.max_len,
                                bucketed=self._bucketed, start=start)

    def _prefill_extent(self, prompt_len: int) -> int:
        return max((s + c for s, c in self._prefill_schedule(prompt_len)),
                   default=0)

    def _ensure_pages(self, slot: int, length: int) -> bool:
        """Grow the slot's block table to cover ``length`` positions and
        mark the device mirror stale (the next ``_flush_tables`` pushes
        all dirty rows in one upload).  False: pool exhausted."""
        grew = self.alloc.ensure(slot, length)
        if grew is None:
            return False
        if grew:
            self._mark_tables_dirty()
        return True

    def _exec_chunks(self, slot: int, part: _PartialPrefill, upto: int,
                     now: float) -> Optional[Request]:
        """Run schedule chunks ``[part.next_chunk, upto)`` through the
        jitted single-row prefill.  The caller reserved the pages
        (``_reserve_chunks``) and flushed the table mirror, so the view's
        block-table row is final for every chunk in the batch.  Between
        ticks the merged view's cursor is pinned to the resume point
        ``pos`` — an interleaved decode tick's garbage write lands at
        ``pos``, inside the next chunk's write window (windows always
        cover the resume position), so it is rewritten idempotently.
        Returns the finished request when the batch completed the
        schedule AND its first token was terminal (finish at admission),
        else None."""
        req = part.req
        prompt = np.asarray(req.prompt, np.int32)  # sync: host — the prompt is host-resident numpy, nothing crosses the link
        L = len(prompt)
        tr = self.tel
        lo = part.next_chunk
        if part.paused:
            part.paused = False
            if tr is not None:
                tr.request_resumed(req.request_id, part.pos)
        # start the chunk-batch X span AFTER the resumed instant: the X
        # event is emitted at its start timestamp, so anything recorded
        # between t0 and emission would read as time going backwards
        t0 = tr.now() if tr is not None else 0.0
        view = self._slot_view(slot)
        nxt = None
        last_i = len(part.schedule) - 1
        for i in range(part.next_chunk, upto):
            start, cb = part.schedule[i]
            real = min(start + cb, L) - start
            toks = np.zeros((1, cb), np.int32)
            toks[0, :real] = prompt[start:start + real]
            if self._bucketed:
                view = self._set_view_cursor(view, start)
            last = L - 1 - start if i == last_i else real - 1
            self._prefill_buckets.add(cb)
            self.counters["prefill_chunks"] += 1
            sub = self._next_key()
            nxt, view = self._jit_prefill_chunk(
                self.params,
                jnp.asarray(toks),   # sync: required — prompt-chunk upload (admission-rate, not per-tick)
                view,
                jnp.int32(last),     # sync: eliminable — scalar cursor upload; could ride inside the token buffer
                sub)
            if self.paged:
                # the view's pools are now the freshest — keep the full
                # states' pool in sync so later table growth edits stick
                kv = self.states.kv
                self.states = self.states._replace(
                    kv=kv._replace(k=view.kv.k, v=view.kv.v))
            # each schedule entry covers exactly min(chunk, L - pos) new
            # tokens (left-shifted windows rewrite, they don't advance)
            part.pos = min(part.pos + self.cfg.prefill_chunk, L)
            part.executed += 1
        part.next_chunk = upto
        self._progressed = True
        done = upto == len(part.schedule)
        if self._bucketed:
            view = self._set_view_cursor(view, L if done else part.pos)
        self._merge_view(slot, view)
        # host cursor tracks the resume point so the decode tick's
        # clamped table width covers the mid-prefill row's page
        self.alloc.slots[slot].length = part.pos
        if tr is not None:
            tr.request_chunks(req.request_id, t0, lo, upto, part.pos,
                              len(part.schedule))
        if not done:
            log.debug("request %d prefilled to %d/%d tokens (chunk "
                      "%d/%d)", req.request_id, part.pos, L,
                      part.next_chunk, len(part.schedule))
            return None
        part.last_tok = int(nxt)  # sync: required — prefill's first token feeds host-side finish/stream logic
        return self._complete_admission(slot, now)

    # ---- public API ----
    def submit(self, req: Request):
        # validate + defensively copy: a float array would silently turn
        # into garbage token ids inside the jitted prefill, and a caller
        # mutating its array after submit would corrupt queued prompts
        arr = np.asarray(req.prompt)
        if arr.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D (token ids), got shape {arr.shape}")
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"prompt must be an integer array, got dtype {arr.dtype}")
        req.prompt = arr.astype(np.int32, copy=True)
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if plen >= self.cfg.max_len:
            raise ValueError(
                f"prompt_len={plen} >= max_len={self.cfg.max_len}: the KV "
                f"buffer cannot hold the prompt plus one generated token")
        if self.paged:
            # the prefill write extent plus the first decode tick's KV
            # row.  Deliberately credit-free: a slot referencing N pages
            # needs N physical pages whether or not some are shared, and
            # cached credit can shrink (eviction) between submit and
            # admission — this check must reject only prompts the pool
            # could never hold
            need = -(-max(self._prefill_extent(plen), plen + 1)
                     // self.cfg.page_size)
            if need > self.alloc.num_pages - 1:
                raise ValueError(
                    f"prompt needs {need} pages but the pool holds "
                    f"{self.alloc.num_pages - 1}")
        req.output = []
        req.truncated = False
        req.arrival = self._arrival
        req._t_submit = time.perf_counter()
        req._tick_submit = self._tick
        self._arrival += 1
        self.scheduler.add(req)
        if self.tel is not None:
            self.tel.request_submit(req.request_id, plen,
                                    req.max_new_tokens, req.priority)

    def _prefix_credit(self, req: Request) -> Tuple[int, List[int]]:
        """(tokens, pages) of the longest usable cached prefix of the
        request's prompt: page-aligned by construction, and capped so at
        least one prompt token is always prefilled (the engine needs the
        last prompt token's logits to generate)."""
        if self.prefix is None:
            return 0, []
        m, pages = self.prefix.match(req.prompt)
        ps = self.cfg.page_size
        cap = ((len(req.prompt) - 1) // ps) * ps
        m = min(m, cap)
        return m, pages[:m // ps]

    def _copy_page(self, old: int, new: int):
        """Device half of a CoW fork: copy pool page ``old`` -> ``new``
        across all layers (the forked page must carry the shared rows the
        slot is NOT about to rewrite).  Jitted with donated pools so XLA
        updates the buffers in place — O(page) work, not a fresh
        pool-sized array per fork; page ids are traced scalars, so every
        fork reuses one trace."""
        kv = self.states.kv
        k, v = _jit_pool_page_copy(
            kv.k, kv.v,
            jnp.int32(old), jnp.int32(new))  # sync: required — page-id scalars for the donated CoW copy (fork-rate, not per-tick)
        self.states = self.states._replace(kv=kv._replace(k=k, v=v))
        if self.tel is not None:
            self.tel.instant("cow_fork", old_page=old, new_page=new)

    def _mark_tables_dirty(self):
        """Flag the device block-table mirror stale.  The host tables
        (``alloc.block_tables``; zeroed rows included — ``release()``
        clears a slot's row) are authoritative, so any number of host
        edits collapse into ONE batched upload at the next
        ``_flush_tables``, replacing the old per-slot
        ``jnp.asarray(block_tables[slot])`` upload loop."""
        self._tables_dirty = True

    def _flush_tables(self, where: str = "decode"):
        """Mirror the full host block-table array into device state in a
        single batched host->device transfer.  Called once before every
        decode tick and before each prefill reads a slot view — never
        per slot, so a tick's table traffic is at most one upload no
        matter how many slots grew, forked, or were scrubbed."""
        if not (self.paged and self._tables_dirty):
            return
        rows = jnp.asarray(  # sync: required — the tick's one batched h2d block-table upload
            self.alloc.block_tables)
        kv = self.states.kv
        self.states = self.states._replace(kv=kv._replace(
            block_tables=jnp.broadcast_to(rows[None],
                                          kv.block_tables.shape)))
        self._tables_dirty = False
        self.counters["table_uploads"] += 1
        self.counters[f"table_uploads_{where}"] += 1
        if self.tel is not None:
            self.tel.instant("table_upload", where=where)

    def _scrub_slot_device(self, slot: int):
        """Retire an inactive slot's device row: the row keeps flowing
        through the static-shape decode step, and its garbage scatter
        must land on the trash page — never on pages the row's previous
        mapping pointed at (they may be cached/reallocated).  The host
        table row is already zeroed (``alloc.release``), so the table
        half rides the next batched flush; only the cursor is zeroed
        eagerly (a device-side edit, no transfer)."""
        kv = self.states.kv
        self.states = self.states._replace(kv=kv._replace(
            length=kv.length.at[:, slot].set(0)))
        self._mark_tables_dirty()

    def _stage_slot(self, slot: int, req: Request, credit: int,
                    pages: List[int]) -> List[Tuple[int, int]]:
        """Mount the prefix credit and fix the admission's prefill
        schedule.  Staging is allocation-free: page growth and CoW forks
        happen lazily, per chunk batch actually executed
        (``_reserve_chunks``) — a chunk the token budget defers to a
        later tick allocates nothing now."""
        if credit:
            self.alloc.map_shared(slot, pages)
            self._mark_tables_dirty()
        return self._prefill_schedule(len(req.prompt), start=credit)

    def _reserve_chunks(self, slot: int, part: _PartialPrefill,
                        upto: int) -> bool:
        """Grow the block table and CoW-fork shared pages for schedule
        chunks ``[part.next_chunk, upto)`` — exactly the batch the caller
        is about to execute this tick.  Returns False when the page pool
        ran dry even after reclaim (caller unwinds a zero-progress
        admission or pauses a half-prefilled one; pages grabbed before
        the exhaustion stay mapped — they are reclaimed with the slot)."""
        if not self.paged or upto <= part.next_chunk:
            return True
        chunks = part.schedule[part.next_chunk:upto]
        need = max(s + c for s, c in chunks)
        if upto == len(part.schedule):
            # the final batch also covers the first decode tick's KV row
            # (the slot decodes the tick it completes, before the next
            # growth pass runs)
            need = max(need, len(part.req.prompt) + 1)
        if not self._ensure_pages(slot, need):
            return False
        if part.credit:
            # copy-on-write: the only engine writes below the credit are
            # near-max_len bucketed chunks left-shifting over already-
            # written positions.  The rewrite is idempotent (same tokens,
            # same positions) but must not scatter into pages the index /
            # other slots still reference — fork those first, and only
            # for the chunks executing this tick (DESIGN.md §15)
            ps = self.cfg.page_size
            for start, cb in chunks:
                if start >= part.credit:
                    continue
                lo = start // ps
                hi = -(-min(start + cb, part.credit) // ps)
                for lp in range(lo, hi):
                    if self.alloc.writable(slot, lp):
                        continue
                    fork = self.alloc.fork(slot, lp)
                    if fork is None:
                        return False
                    self._copy_page(*fork)
                    self._mark_tables_dirty()
                    self.counters["forked_pages"] += 1
                    log.debug("CoW fork: slot %d logical page %d "
                              "(%d -> %d)", slot, lp, *fork)
        return True

    def _prefill_quota(self) -> Optional[int]:
        """This tick's chunked-prefill token quota (None = unbounded),
        from the scheduler's token-budget policy.  Recurrent families
        always prefill whole prompts — their carries would absorb the
        interleaved ticks' pad garbage — so the budget only paces
        cursor-guarded (bucketed) families."""
        if not self._bucketed:
            return None
        fn = getattr(self.scheduler, "prefill_quota", None)
        if fn is None:     # custom Scheduler predating the budget policy
            budget = self.cfg.tick_budget
            return (None if budget is None
                    else max(0, budget - len(self.active)))
        return fn(self, len(self.active))

    def _plan_chunks(self, part: _PartialPrefill, quota: Optional[int],
                     spent: int) -> int:
        """How far into the partial's schedule this tick may execute:
        returns ``upto`` (chunk index).  The budget charges *padded*
        widths (what jit executes).  The tick's first chunk always fits
        when the quota is positive — overshoot is bounded by one bucket
        — so a small budget slows admission instead of stalling it."""
        upto, cost = part.next_chunk, 0
        for _s, cb in part.schedule[part.next_chunk:]:
            if quota is not None and spent + cost + cb > quota and (
                    spent or cost or quota <= 0):
                break
            upto += 1
            cost += cb
        return upto

    def _batch_cost(self, part: _PartialPrefill, upto: int) -> int:
        return sum(cb for _s, cb in part.schedule[part.next_chunk:upto])

    def _append_token(self, req: Request, tok: int,
                      now: Optional[float] = None):
        """Record a generated token, stamp its latency sample, and fire
        the streaming callback."""
        tok = int(tok)  # sync: host — tok is already a host-side numpy scalar here
        req.output.append(tok)
        self.counters["generated_tokens"] += 1
        if now is not None:
            if len(req.output) == 1:
                req.ttft_ms = (now - req._t_submit) * 1e3
                self._lat["ttft_ms"].record(req.ttft_ms)
            else:
                self._lat["itl_ms"].record((now - req._t_last) * 1e3)
            req._t_last = now
        if req.on_token is not None:
            try:
                req.on_token(req, tok)
            except Exception:   # noqa: BLE001 — user callback must not
                log.exception(  # kill the serving loop
                    "on_token callback failed for request %d",
                    req.request_id)

    def _unwind_slot(self, slot: int):
        """Give a claimed slot (and every page it mapped) back, and scrub
        its device row so the inactive row's decode scatter lands on the
        trash page instead of pages the old mapping pointed at."""
        self.alloc.release(slot)
        if self.paged:
            self._scrub_slot_device(slot)

    def _complete_admission(self, slot: int,
                            now: float) -> Optional[Request]:
        """The partial finished its whole schedule: promote it to an
        active (decoding) slot and account the admission.  Returns the
        request when its first (prefill-produced) token was terminal —
        EOS or max_new_tokens=1 — i.e. finish at admission."""
        part = self.admitting.pop(slot)
        req = part.req
        self.active[slot] = req
        if self.tel is not None:
            self.tel.request_decode(req.request_id, part.credit)
        self.alloc.slots[slot].length = len(req.prompt)
        self.counters["prefill_tokens"] += len(req.prompt) - part.credit
        if part.credit:
            self.counters["prefix_hit_tokens"] += part.credit
            self.counters["prefix_hit_requests"] += 1
        self._append_token(req, part.last_tok, now)
        nxt = req.output[-1]
        done = (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and nxt == req.eos_id))
        if done:
            log.debug("request %d finished at admission", req.request_id)
            return self._finish(slot)
        log.debug("admitted request %d into slot %d (prefix credit "
                  "%d tokens)", req.request_id, slot, part.credit)
        return None

    def _advance_one(self, slot: int, quota: Optional[int], spent: int,
                     now: float,
                     reserved_upto: Optional[int] = None
                     ) -> Tuple[int, Optional[Request]]:
        """Advance one in-progress admission by this tick's share of the
        token budget: plan the chunk batch, reserve its pages/forks,
        execute.  Returns (padded tokens spent, finished request or
        None).  Reservation failure on a zero-progress credit admission
        re-stages uncached (the cache must never block an admission an
        empty cache would allow); any other failure pauses the partial in
        place — slot, pages, and executed chunks are all kept, and the
        request resumes when the pool frees up."""
        part = self.admitting[slot]
        upto = reserved_upto
        if upto is not None:
            if upto == part.next_chunk:
                return 0, None          # staged with zero budget left
        else:
            upto = self._plan_chunks(part, quota, spent)
            if upto == part.next_chunk:
                return 0, None          # budget spent: defer to next tick
            if not self._reserve_chunks(slot, part, upto):
                if part.credit and part.executed == 0:
                    # scrub the mounted credit and retry uncached, still
                    # as the same in-progress admission (same slot id)
                    req = part.req
                    del self.admitting[slot]
                    self._unwind_slot(slot)
                    slot2 = self.alloc.claim(req.request_id)
                    fresh = _PartialPrefill(
                        req=req, schedule=self._stage_slot(slot2, req, 0, []))
                    self.admitting[slot2] = fresh
                    self.states = _reset_slot(self.states, slot2)
                    if self.paged:
                        self._mark_tables_dirty()
                    if self.tel is not None:
                        self.tel.request_restaged(req.request_id)
                    return self._advance_one(slot2, quota, spent, now)
                self._prefill_stalled = True
                part.paused = True
                self.counters["paused_prefills"] += 1
                if self.tel is not None:
                    self.tel.request_paused(part.req.request_id, part.pos)
                log.debug("request %d paused mid-prefill at %d/%d tokens "
                          "(page pool dry)", part.req.request_id, part.pos,
                          len(part.req.prompt))
                return 0, None
        cost = self._batch_cost(part, upto)
        # the batch's table edits (growth + forks) ride ONE upload
        self._flush_tables("prefill")
        return cost, self._exec_chunks(slot, part, upto, now)

    def _run_prefills(self, quota: Optional[int],
                      now: float) -> List[Request]:
        """The tick's chunked-prefill pass: resume in-progress admissions
        first (FIFO in staging order), then admit from the scheduler
        while slots and budget allow.  Admission itself (claim + stage)
        is allocation-free, so new requests keep entering ``admitting``
        even after the budget is spent — their chunks run on later
        ticks."""
        finished: List[Request] = []
        # distinguishes "admission failed on an offered request" (a stuck
        # engine if nothing is active) from "the scheduler deferred"
        # (next() -> None — a policy choice, keep ticking)
        self._admission_backoff = False
        self._prefill_stalled = False
        spent = 0
        for slot in list(self.admitting):
            if slot not in self.admitting:
                continue        # re-staged uncached under a new slot id
            cost, fin = self._advance_one(slot, quota, spent, now)
            spent += cost
            if fin is not None:
                finished.append(fin)
        tr = self.tel
        if tr is not None:
            tr.begin("scheduler", queued=len(self.scheduler))
        while len(self.scheduler):
            req = self.scheduler.next(self)
            if req is None:
                break
            slot = self.alloc.claim(req.request_id)
            if slot is None:
                self._admission_backoff = True
                break
            credit, pages = self._prefix_credit(req)
            part = _PartialPrefill(
                req=req, schedule=self._stage_slot(slot, req, credit, pages),
                credit=credit, pos=credit)
            # reserve the first chunk batch BEFORE dequeuing: a pool-dry
            # admission unwinds with the request still queued (retried
            # uncached when a credit was mounted, backed off otherwise)
            upto = self._plan_chunks(part, quota, spent)
            if not self._reserve_chunks(slot, part, upto):
                self._unwind_slot(slot)
                if credit:
                    # the cache must never block an admission an empty
                    # cache would allow — retry uncached (eviction freed
                    # what it could)
                    slot = self.alloc.claim(req.request_id)
                    credit, pages = 0, []
                    part = _PartialPrefill(
                        req=req,
                        schedule=self._stage_slot(slot, req, 0, []))
                    upto = self._plan_chunks(part, quota, spent)
                    if not self._reserve_chunks(slot, part, upto):
                        self._unwind_slot(slot)
                        self._admission_backoff = True
                        break
                else:
                    self._admission_backoff = True
                    break
            self.scheduler.remove(req)
            req.queued_ticks = max(0, self._tick - req._tick_submit - 1)
            self._lat["queued_ticks"].record(req.queued_ticks)
            self.admitting[slot] = part
            if tr is not None:
                tr.request_admitted(req.request_id, slot, part.credit,
                                    len(part.schedule))
            self._progressed = True   # claiming + staging IS progress
            # reset this slot's cursor/recurrent state before any chunk
            # runs (device table row = shared + fresh + forks)
            self.states = _reset_slot(self.states, slot)
            if self._bucketed and part.pos:
                # pin the device cursor at the resume point right away: a
                # credit-mounted partial that executes no chunk this tick
                # still rides the decode step, and an unpinned (zero)
                # cursor would scatter its garbage row into the first
                # SHARED page instead of past the mount (page-aligned
                # credit → the write lands on an unmapped logical page →
                # trash page 0)
                kv = self.states.kv
                self.states = self.states._replace(kv=kv._replace(
                    length=kv.length.at[:, slot].set(part.pos)))
            if self.paged:
                self._mark_tables_dirty()
            cost, fin = self._advance_one(slot, quota, spent, now,
                                          reserved_upto=upto)
            spent += cost
            if fin is not None:
                finished.append(fin)
        if tr is not None:
            tr.end("scheduler")
        return finished

    def cancel(self, request_id: int) -> bool:
        """Abort a request wherever it lives: still queued (dequeue),
        mid-prefill (unwind the slot — nothing is cached; the partial KV
        rows were never validated by a finish), or actively decoding
        (finish now with ``truncated=True``; the generated prefix is
        cached as usual).  Returns False when the id is unknown (already
        finished counts as unknown)."""
        for req in self.scheduler.pending():
            if req.request_id == request_id:
                self.scheduler.remove(req)
                req.truncated = True
                if self.tel is not None:
                    self.tel.request_cancel(request_id, "queued")
                return True
        for slot, part in list(self.admitting.items()):
            if part.req.request_id == request_id:
                del self.admitting[slot]
                self._unwind_slot(slot)
                part.req.truncated = True
                if self.tel is not None:
                    self.tel.request_cancel(request_id, "prefill")
                return True
        for slot, req in list(self.active.items()):
            if req.request_id == request_id:
                req.truncated = True
                self._finish(slot)
                return True
        return False

    def _finish(self, slot: int):
        req = self.active.pop(slot)
        self.counters["finished_requests"] += 1
        if self.tel is not None:
            self.tel.request_finish(
                req.request_id,
                "truncated" if req.truncated else "finish",
                len(req.output))
        if self.prefix is not None:
            # cache the finished sequence: every written KV row is valid
            # (prompt + all-but-the-last generated token have rows), and
            # the index takes references on the page-aligned prefix — the
            # release below then frees only what nothing else holds
            rows = self.alloc.slots[slot].length
            toks = np.concatenate([
                req.prompt,
                np.asarray(  # sync: host — output tokens are host-side python ints
                    req.output[:max(0, rows - len(req.prompt))], np.int32)])
            self.prefix.insert(toks[:rows], self.alloc.held(slot))
        self.alloc.release(slot)
        if self.paged:
            # the freed pages can be reacquired by other slots (or stay
            # cached in the index) any tick — scrub the device row
            self._scrub_slot_device(slot)
        return req

    def step(self) -> List[Request]:
        """One engine tick. Returns requests that finished this tick."""
        # grow in-flight slots' tables for this tick's KV row BEFORE
        # admitting — decoding requests have page priority over new
        # admissions (an admission must never drain the free list out
        # from under a request that only needed one more page).  Slots at
        # max_len hard-stop: decoding past it would clamp the write
        # offset and corrupt the newest rows.  Newly admitted slots are
        # covered through prompt_len + 1 by the admission ensure.
        self._tick += 1
        self._progressed = False
        now = time.perf_counter()
        tr = self.tel
        if tr is not None:
            tr.begin("tick", n=self._tick, active=len(self.active),
                     admitting=len(self.admitting),
                     queued=len(self.scheduler))
        finished: List[Request] = []
        for slot in list(self.active):
            req = self.active[slot]
            if self.alloc.slots[slot].length >= self.cfg.max_len or (
                    self.paged and not self._ensure_pages(
                        slot, self.alloc.slots[slot].length + 1)):
                req.truncated = True
                finished.append(self._finish(slot))
                log.debug("request %d hard-stopped at max_len/page cap",
                          req.request_id)
        if tr is not None:
            tr.begin("prefill_pass")
        finished.extend(self._run_prefills(self._prefill_quota(), now))
        if tr is not None:
            tr.end("prefill_pass")
        if not self.active:
            if tr is not None:
                tr.end("tick")
            return finished
        last = np.zeros((self.cfg.max_batch, 1), np.int32)
        for slot, req in self.active.items():
            last[slot, 0] = req.output[-1]
        sub = self._next_key()
        # the tick's ONE batched block-table upload (replaces the old
        # per-slot jnp.asarray loop over grown slots), then clamp the
        # decode tick's block-table width to the bucketed batch
        # high-water page count: attention (gather or paged kernel) then
        # only walks pages some active row can actually hold, instead of
        # the full pool-capacity table.  Power-of-two buckets bound the
        # decode retraces by log2(pages_per_slot); tables are restored
        # afterwards (the decode step never rewrites them).
        if tr is not None:
            tr.begin("decode_step", batch=len(self.active))
        self._flush_tables("decode")
        last_dev = jnp.asarray(last)  # sync: required — the tick's last-token batch upload
        states_in, full_tables = self.states, None
        if self.paged:
            hw = self._decode_table_width()
            kv = self.states.kv
            full_tables = kv.block_tables
            states_in = self.states._replace(
                kv=kv._replace(block_tables=full_tables[:, :, :hw]))
            if hw not in self._decode_table_buckets:
                self._decode_table_buckets.add(hw)
                self._tune_decode_bucket(last_dev, states_in, sub)
                if tr is not None:
                    # first tick at this table width: attach kernel/plan
                    # provenance (which launch config won the autotune,
                    # and why) to the timeline + trace metadata
                    tr.instant("decode_bucket", cat="plan", table_width=hw,
                               **self._kernel_provenance())
        nxt, new_states = self._jit_decode(self.params, last_dev,
                                           states_in, sub)
        if full_tables is not None:
            kv = new_states.kv
            new_states = new_states._replace(
                kv=kv._replace(block_tables=full_tables))
        self.states = new_states
        self.counters["decode_ticks"] += 1
        if self.admitting and self._bucketed:
            # mid-prefill rows rode this decode tick as inactive batch
            # rows: the step advanced their device cursors past the
            # resume point and scattered one garbage KV row at it.  The
            # garbage is harmless — the next chunk's window rewrites that
            # position (windows always cover the resume point) — but the
            # cursor must be re-pinned to ``pos`` every tick, or an
            # admission idling across several ticks would drift its
            # cursor and scatter garbage ABOVE the resume point, beyond
            # the next chunk's rewrite extent (device-side edit, no
            # transfer).
            kv = self.states.kv
            length = kv.length
            for slot, part in self.admitting.items():
                length = length.at[:, slot].set(part.pos)
            self.states = self.states._replace(
                kv=kv._replace(length=length))
        self._progressed = True
        nxt = np.asarray(nxt)  # sync: required — the tick's one d2h readback (next tokens drive host finish logic)
        if tr is not None:
            tr.end("decode_step")
        for slot in list(self.active):
            req = self.active[slot]
            self._append_token(req, nxt[slot], now)
            self.alloc.slots[slot].length += 1
            done = (len(req.output) >= req.max_new_tokens
                    or (req.eos_id is not None
                        and req.output[-1] == req.eos_id))
            if done:
                finished.append(self._finish(slot))
        if tr is not None:
            tr.end("tick")
        return finished

    def _warmup_decode(self) -> None:
        """Pre-trace the decode step's whole signature set at construction
        (``cfg.warmup="decode"``).  The paged bucket ladder is closed-form
        — the static proof (``serve_static.enumerate_decode_buckets`` /
        ``verify_engine_signatures``) enumerates exactly the clamped
        table-width buckets a live tick can ever present — so warming it
        moves every decode compile off the serving path: steady-state
        ticks never trace.  Warmed traces land in the same jit cache the
        measured-vs-proven cross-check counts, so ``decode_compiles``
        equals the proven ladder up front and a later live retrace still
        trips the budget gate.  Runs outside the tick path (construction
        time), so its transfers are not per-tick sync-contract traffic;
        outputs are discarded and ``self.states`` is untouched (inactive
        rows' scatters land on trash page 0 by design)."""
        sub = self._next_key()
        last = jnp.zeros((self.cfg.max_batch, 1), jnp.int32)
        if not self.paged:
            self._jit_decode(self.params, last, self.states, sub)
            return
        from repro.analysis.serve_static import enumerate_decode_buckets

        kv = self.states.kv
        full_tables = kv.block_tables
        for hw in enumerate_decode_buckets(
                max_len=self.cfg.max_len, page_size=self.cfg.page_size,
                pages_per_slot=self.alloc.pages_per_slot):
            states_in = self.states._replace(
                kv=kv._replace(block_tables=full_tables[:, :, :hw]))
            if hw not in self._decode_table_buckets:
                self._decode_table_buckets.add(hw)
                self._tune_decode_bucket(last, states_in, sub)
            self._jit_decode(self.params, last, states_in, sub)

    def _warmup_prefill(self) -> None:
        """Pre-trace the proven prefill chunk buckets
        (``cfg.warmup="serve"``): same closed-form enumeration the static
        proof checks (``serve_static.enumerate_prefill_buckets``), traced
        against a fresh slot-0 view — ava-identical to every live
        prefill signature, so admission never compiles either.  Outputs
        are discarded; paged writes land on the zeroed (trash-page)
        table of the discarded view copy."""
        from repro.analysis.serve_static import enumerate_prefill_buckets

        view = self._slot_view(0)
        for cb in enumerate_prefill_buckets(
                max_len=self.cfg.max_len,
                prefill_chunk=self.cfg.prefill_chunk,
                bucketed=self._bucketed,
                page_size=self.cfg.page_size if self.paged else None,
                prefix_cache=self.prefix is not None):
            if self._bucketed:
                view = self._set_view_cursor(view, 0)
            self._prefill_buckets.add(cb)
            sub = self._next_key()
            self._jit_prefill_chunk(self.params,
                                    jnp.zeros((1, cb), jnp.int32),
                                    view, jnp.int32(0), sub)

    def _tune_decode_bucket(self, last, states_in, key) -> None:
        """One eager (un-jitted) decode step the first time a table-width
        bucket appears, only where the paged kernel family lowers
        natively: concrete operands let the kernel registry time its
        paged-kernel candidates for this shape *before* the jitted tick
        traces — the trace then bakes the tuned winner instead of the
        default (kernels/ops.py, DESIGN.md §10).  Interpret-mode hosts
        skip this outright — timing interpreted Pallas measures nothing
        real, and the planner routes them to the gather path anyway."""
        from repro.kernels.ops import registry as kernel_registry

        if kernel_registry.interpret_for("paged") or (
                self.decode_plan is not None
                and self.decode_plan.backend != "paged_pallas"):
            return          # gather path / interpret mode: nothing to time
        self._decode_step(self.params, last, states_in, key)

    def _kernel_provenance(self) -> Dict[str, Any]:
        """JSON-safe kernel/plan provenance for trace attribution: the
        planner's chosen backend + reason, the registry's interpret
        decision, and which launch config won each autotuned shape.
        Called only under ``tel is not None`` at bucket-tune rate, never
        on the steady-state tick path."""
        from repro.kernels.ops import registry as kernel_registry

        out: Dict[str, Any] = {
            "backend": (self.decode_plan.backend
                        if self.decode_plan is not None else None),
            "plan_reason": (self.decode_plan.reason
                            if self.decode_plan is not None else None),
            "interpret": kernel_registry.interpret_for("paged"),
        }
        if kernel_registry.decisions:
            out["decisions"] = {
                str(k): {"choice": str(v.get("choice")),
                         "source": v.get("source"),
                         "native": v.get("native")}
                for k, v in kernel_registry.decisions.items()}
        return out

    def _decode_table_width(self) -> int:
        """Bucketed high-water page count across active AND mid-prefill
        slots: the widest block table any row needs for this tick's read
        + one written KV row, rounded up to a power of two (bounds decode
        retraces).  Admitting rows count because their pinned-cursor
        garbage write scatters at ``pos`` — were the clamped table
        narrower than ``pos``'s page, the clamped index would land that
        write on one of the slot's own already-written pages."""
        rows = [self.alloc.slots[s].length for s in self.active]
        # part.pos, not slots[s].length: a credit-mounted partial that has
        # not executed a chunk yet writes its garbage row at pos=credit
        rows += [part.pos for part in self.admitting.values()]
        longest = max(rows) + 1
        return decode_table_width(longest, page_size=self.cfg.page_size,
                                  pages_per_slot=self.alloc.pages_per_slot)

    def run_to_completion(self, max_ticks: int = 10_000,
                          on_tick=None) -> List[Request]:
        """Drive ticks until the engine drains.  ``on_tick(engine,
        finished)`` runs after every tick — the launcher's ``--log-json``
        hook; it must not submit or cancel (reentrancy is untested)."""
        done: List[Request] = []
        for _ in range(max_ticks):
            out = self.step()
            done.extend(out)
            if on_tick is not None:
                on_tick(self, out)
            if (not self.active and not self.admitting
                    and not len(self.scheduler)):
                break
            if (not self.active and not out and not self._progressed
                    and (self._admission_backoff
                         or self._prefill_stalled)):
                # the tick changed nothing: no active slot to free pages,
                # nothing finished, no partial prefill advanced (a
                # partially-prefilled admission advancing IS progress —
                # self._progressed), and an admission failed or a partial
                # stalled on the dry pool — every later tick would be
                # identical, so raise instead of silently burning
                # max_ticks (this state means a leak or an externally
                # held resource; healthy admission always makes progress
                # from an idle engine, since the prefix cache is fully
                # evictable and submit() rejects prompts the pool could
                # never hold).  A scheduler that merely deferred
                # (next() -> None, or a zero prefill quota) keeps
                # ticking: deferral is a policy choice, not a stuck
                # engine.
                head = self.scheduler.next(self)
                head_desc = (f"id={head.request_id}, "
                             f"prompt_len={len(head.prompt)}"
                             if head is not None else "deferred")
                raise RuntimeError(self._dump_on_error(
                    f"engine cannot make progress: {len(self.scheduler)} "
                    f"request(s) queued (head: {head_desc}), "
                    f"{len(self.admitting)} mid-prefill, no active "
                    f"slots, and admission backed off or stalled"
                    + (f" [pages_in_use={self.alloc.pages_in_use}/"
                       f"{self.alloc.num_pages - 1}]" if self.paged else
                       "")))
        self._check_compile_soundness()
        return done

    def _dump_on_error(self, msg: str) -> str:
        """Flight-recorder hook for engine error paths: dump the last K
        events and append the dump path to the error message (telemetry
        off: the message passes through untouched)."""
        if self.tel is None or self.tel.ring is None:
            return msg
        path = dump_flight(self.tel, msg)
        log.error("flight recorder dumped to %s", path)
        return f"{msg} [flight recorder: {path}]"

    def _check_compile_soundness(self) -> None:
        """Measured-vs-proven compile cross-check at drain (the live
        counterpart of ``analysis.serve.cross_check_bench``): a measured
        compile count above the proven retrace budget means the static
        enumeration missed a reachable signature — raise loudly, with
        the flight recorder dumped for forensics."""
        b = self.retrace_budget()
        pm, dm = self.prefill_compiles, self.decode_compiles
        if pm <= b["prefill_proven"] and dm <= b["decode_proven"]:
            return
        raise RuntimeError(self._dump_on_error(
            f"SOUNDNESS BUG: measured compiles exceed the proven retrace "
            f"budget (prefill {pm}/{b['prefill_proven']}, decode "
            f"{dm}/{b['decode_proven']}) — the static enumeration missed "
            f"a reachable trace signature"))


def _reset_slot(states, slot: int):
    """Reset one slot's decode state across all layers.

    Transformer family: zero the (L, b) cursor; KV buffer/pool rows need
    no clearing (validity is cursor-defined; paged tables are rewritten at
    admission).  Hybrid: also zero the slot's mamba ssm/conv carries.
    RWKV: zero the slot's recurrent state rows.
    """
    from repro.models.transformer import LayerState

    if isinstance(states, LayerState):
        kv = states.kv._replace(length=states.kv.length.at[:, slot].set(0))
        ssm = (states.ssm.at[:, slot].set(0)
               if states.ssm is not None else None)
        conv = (states.conv.at[:, slot].set(0)
                if states.conv is not None else None)
        return LayerState(kv=kv, ssm=ssm, conv=conv)
    # recurrent families (rwkv): zero every state leaf's slot row
    return jax.tree.map(lambda x: x.at[:, slot].set(jnp.zeros_like(x[:, slot])),
                        states)
