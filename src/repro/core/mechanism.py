"""Pluggable attention-mechanism registry + explicit backend planner.

This module is the dispatch seam of the whole stack (DESIGN.md §7).  A
*mechanism* (how scores are formed and combined with values — Softmax
dot-product, the paper's Inhibitor, …) registers once; every model token
mixer, kernel path, quantized/integer path, FHE circuit and benchmark
driver then picks it up through one inspectable API:

  * :class:`Mechanism`       — name, mask semantics, VJP hints, and one
                                callable per execution *backend*
  * :func:`register_mechanism` / :func:`get_mechanism` — the registry
  * :func:`plan_attention`   — the planner: (config, :class:`AttnShapes`)
                                -> :class:`ExecutionPlan` (backend + reason)
  * :func:`execute_plan`     — run a plan on (q, k, v)

Backends (``BACKENDS``) are execution strategies for one mechanism:

  ``naive``    broadcast oracle; autodiff-friendly; O(n²·d) memory
  ``fused``    cdist-decomposed / custom-VJP dense form (default)
  ``chunked``  streaming accumulation over KV chunks (exact — no Softmax
               normalizer to rescale for the inhibitor family)
  ``blocked``  two-level chunk scan with structural (causal/window/valid-
               length) masks computed from indices — no mask array in HBM
  ``pallas``   the Pallas TPU kernel (interpret mode on CPU hosts); since
               the kernels carry scalar-prefetched ``q_offset`` /
               ``kv_valid_len`` cursor operands it is eligible at
               decode-cache sites, including ragged per-slot cursors
  ``paged``    block-table gather over a paged KV pool (serving decode /
               single-row prefill; k/v arrive as page pools plus a
               :class:`PagedLayout`) — the non-TPU / prefill fallback
  ``paged_pallas``  block-table-native Pallas decode kernel: the grid
               walks each row's block table, staging K/V pages
               VMEM-resident — no contiguous gather (DESIGN.md §10)
  ``int``      integer-lane arithmetic (paper's quantized scaling arm)
  ``fhe_sim``  the TFHE circuit simulator (numpy, per-head; forced only)

``blocked``, ``pallas`` and ``paged_pallas`` never receive a materialized
mask array — they are listed in :data:`MASK_FREE_BACKENDS` and take a
:class:`Structural` description instead.  The planner only selects
backends whose eligibility predicate passes for the given shapes, so
"registered" and "selectable here" stay distinct, inspectable facts.

Config duck-typing: :func:`plan_attention` reads ``mechanism`` (falling
back to the legacy ``kind``), ``backend``, ``use_kernel`` (deprecated
alias for ``backend="pallas"``), ``chunked_threshold``,
``blocked_threshold``, ``causal`` and ``sliding_window`` off the config
object — it does not import :class:`repro.core.attention.AttentionConfig`
to stay cycle-free and to let tests plan with lightweight stand-ins.
"""

from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

log = logging.getLogger("repro.plan")

BACKENDS: Tuple[str, ...] = (
    "naive", "fused", "chunked", "blocked", "pallas", "paged",
    "paged_pallas", "int", "fhe_sim")

#: Backends that consume a :class:`Structural` description and must never
#: be handed a materialized (n_q, n_k) mask array.
MASK_FREE_BACKENDS = frozenset({"blocked", "pallas", "paged_pallas"})

#: Backends that consume a page pool + :class:`PagedLayout` instead of
#: contiguous (b, n_k, h_kv, d) key/value tensors.
PAGED_BACKENDS = frozenset({"paged", "paged_pallas"})

DEFAULT_BLOCKED_THRESHOLD = 1 << 20   # n_q·n_k above which dense masks are
                                      # unreasonable (formerly inline in
                                      # apply_attention)
DEFAULT_CHUNKED_THRESHOLD = 4096


# ---------------------------------------------------------------------------
# Planner inputs / outputs
# ---------------------------------------------------------------------------

class AttnShapes(NamedTuple):
    """Shape/placement facts the planner keys on (all static at trace time).

    ``scalar_cursor`` is False for ragged continuous batching (per-slot
    cache cursors), where structural masks cannot be expressed from a
    single query offset.  ``platform`` defaults to the active JAX backend.
    """
    batch: int
    n_q: int
    n_k: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any = jnp.float32
    has_explicit_mask: bool = False
    is_cross: bool = False
    has_cache: bool = False
    scalar_cursor: bool = True
    platform: Optional[str] = None
    paged: bool = False          # KV lives in a paged pool (block tables)

    @property
    def resolved_platform(self) -> str:
        return self.platform or jax.default_backend()

    @property
    def score_elements(self) -> int:
        return self.n_q * self.n_k


@dataclasses.dataclass(frozen=True)
class Structural:
    """Mask structure for :data:`MASK_FREE_BACKENDS` — computed from
    indices inside the backend, never materialized.  ``q_offset`` /
    ``kv_valid_len`` may be traced int32 scalars (decode cursors)."""
    causal: bool = True
    window: Optional[int] = None
    q_offset: Any = 0
    kv_valid_len: Any = None


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Block-table layout for the ``paged`` backend.  ``k``/``v`` arrive as
    head-major page pools (num_pages, h_kv, page_size, d); ``block_tables``
    (b, pages_per_slot) int32 maps each batch row's logical page index to a
    physical page.  Validity is expressed through the ordinary mask path
    (the gathered view is logically contiguous per row)."""
    block_tables: Any
    page_size: int


@dataclasses.dataclass(frozen=True)
class MechanismParams:
    """Union of per-call mechanism hyper-parameters.  Each backend reads
    the fields it understands (``signed`` is fixed per mechanism via
    :attr:`Mechanism.param_overrides`; dot-product ignores the shift).
    The ``kernel_*`` fields override the kernel registry's tuned block
    sizes (``None`` = registry decides — DESIGN.md §10)."""
    score_scale: Optional[float] = None
    score_shift: float = 0.0
    signed: bool = True
    normalize: bool = True
    kv_chunk: int = 256
    kernel_block_q: Optional[int] = None
    kernel_block_k: Optional[int] = None
    kernel_sub_k: Optional[int] = None
    kernel_pages_per_step: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """An inspectable dispatch decision: which mechanism implementation
    runs, on which backend, and why the planner chose it."""
    mechanism: str
    backend: str
    reason: str

    def trace_line(self) -> str:
        return (f"plan: mechanism={self.mechanism} backend={self.backend} "
                f"reason={self.reason}")


# ---------------------------------------------------------------------------
# Mechanism + registry
# ---------------------------------------------------------------------------

# Uniform backend signature:
#   fn(q, k, v, *, mask=None, params: MechanismParams,
#      structural: Optional[Structural] = None) -> (b, n_q, h, d)
BackendFn = Callable[..., jax.Array]


@dataclasses.dataclass(frozen=True)
class Mechanism:
    """One attention mechanism: semantics + its backend implementations.

    ``mask_semantics``: how disallowed pairs are suppressed —
      * ``"exclude"``  masked pairs are excluded from the combining sums
                       (inhibitor family; additive large constants would
                       be cancellation-prone in the fused decomposition)
      * ``"neg_inf"``  masked logits are driven to −inf before Softmax
    ``vjp``: gradient-path hint — ``"analytic"`` (custom VJP, recompute-
    based residuals) or ``"autodiff"``.
    ``lane_fn``: the lane-generic integer form of the mechanism
    (``fn(lane, q, k, v, *, mask, **mechanism_kwargs)`` at (..., n, d)
    per-head layout) — the single implementation behind the ``int`` and
    ``fhe_sim`` backends *and* the lane-parameterized model forward
    (DESIGN.md §9).
    ``fhe_circuit`` / ``int_reference``: the raw numpy TFHE circuit and
    raw integer-lane reference the benchmark drivers consume directly
    (both are thin lane dispatches of ``lane_fn``).
    """
    name: str
    description: str
    mask_semantics: str
    vjp: str
    backends: Mapping[str, BackendFn]
    param_overrides: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)
    lane_fn: Optional[Callable] = None
    fhe_circuit: Optional[Callable] = None
    int_reference: Optional[Callable] = None

    def make_params(self, **kw) -> MechanismParams:
        kw.update(self.param_overrides)
        return MechanismParams(**kw)


_REGISTRY: Dict[str, Mechanism] = {}


def register_mechanism(mech: Mechanism, *, overwrite: bool = False) -> Mechanism:
    """Register ``mech`` under ``mech.name``.  Re-registration requires
    ``overwrite=True`` so accidental shadowing fails loudly."""
    unknown = set(mech.backends) - set(BACKENDS)
    if unknown:
        raise ValueError(
            f"mechanism {mech.name!r} declares unknown backends {sorted(unknown)}; "
            f"known: {BACKENDS}")
    if mech.name in _REGISTRY and not overwrite:
        raise ValueError(f"mechanism {mech.name!r} already registered "
                         f"(pass overwrite=True to replace)")
    _REGISTRY[mech.name] = mech
    return mech


def get_mechanism(name: str) -> Mechanism:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown attention mechanism {name!r}; registered: "
            f"{available_mechanisms()}") from None


def available_mechanisms() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Eligibility + planning
# ---------------------------------------------------------------------------

def backend_eligible(backend: str, cfg, shapes: AttnShapes,
                     mech: Mechanism) -> Tuple[bool, str]:
    """Can ``backend`` run this mechanism at these shapes?  Returns
    (ok, why_not) — the reason string feeds plan traces and errors."""
    if backend not in mech.backends:
        return False, f"not registered for mechanism {mech.name!r}"
    paged = getattr(shapes, "paged", False)
    if paged and backend not in PAGED_BACKENDS:
        return False, "KV lives in a paged pool (block-table backends only)"
    if backend in PAGED_BACKENDS:
        if not paged:
            return False, "no paged KV pool at this call site"
        if shapes.has_explicit_mask or shapes.is_cross:
            return False, "paged pools serve cached causal self-attention"
    if backend == "paged_pallas" and shapes.n_q != 1:
        return False, (f"paged decode kernel is single-query (n_q=1); "
                       f"n_q={shapes.n_q} prefill goes through the gather "
                       f"path")
    is_int = jnp.issubdtype(jnp.dtype(shapes.dtype), jnp.integer)
    if backend in ("int", "fhe_sim") and not is_int:
        return False, "requires integer-lane inputs"
    if backend not in ("int", "fhe_sim") and is_int:
        return False, "float backend on integer-lane inputs"
    if backend in MASK_FREE_BACKENDS:
        if shapes.has_explicit_mask:
            return False, "explicit mask array given (structural masks only)"
        if shapes.is_cross:
            return False, "cross-attention has no structural mask"
    if backend == "blocked" and not shapes.scalar_cursor:
        # the flash kernels take per-row cursor operands; blocked does not
        return False, "ragged per-slot cursors (no shared query offset)"
    if backend == "fhe_sim":
        if shapes.has_explicit_mask or shapes.is_cross or shapes.has_cache:
            return False, "circuit is self-attention without masking"
        if getattr(cfg, "causal", False) or getattr(cfg, "sliding_window",
                                                    None) is not None:
            return False, "circuit attends all-to-all (non-causal only)"
    return True, ""


def kernel_family(mechanism: str) -> str:
    """Registry kernel family that implements ``mechanism``'s Pallas
    path (the key into ``kernels.ops.NATIVE_PLATFORMS`` / autotune
    candidates): the inhibitor variants share the "inhibitor" family,
    every dot-product mechanism the "flash" family."""
    return ("inhibitor" if mechanism in ("inhibitor", "inhibitor_unsigned")
            else "flash")


def kernel_native(family: str, platform: str) -> bool:
    """True when ``family``'s Pallas body lowers natively on
    ``platform`` (the kernel module's own ``LOWERS_ON`` declaration, via
    ``kernels.ops.NATIVE_PLATFORMS``).  The planner keys every kernel
    preference on this instead of hard-coding ``== "tpu"``: anywhere a
    family is non-native the kernel would run interpret-mode Pallas —
    orders of magnitude slower than the XLA gather/blocked paths — so it
    must never be *preferred*, only reachable by forcing the backend."""
    from repro.kernels.ops import NATIVE_PLATFORMS
    return platform in NATIVE_PLATFORMS.get(family, ("tpu",))


_traced_plans: set = set()
_use_kernel_warned = False
_kind_warned = False


def _trace(plan: ExecutionPlan, shapes: Optional[AttnShapes] = None) -> None:
    """One-line plan trace, deduplicated per (mechanism, backend) so
    per-layer tracing and varying sequence lengths (whose reasons embed
    concrete shape numbers) do not spam serve/train logs or grow the
    dedup set unboundedly."""
    key = (plan.mechanism, plan.backend)
    if key in _traced_plans:
        return
    _traced_plans.add(key)
    if shapes is not None:
        log.info("%s [n_q=%d n_k=%d heads=%d platform=%s]", plan.trace_line(),
                 shapes.n_q, shapes.n_k, shapes.num_heads,
                 shapes.resolved_platform)
    else:
        log.info("%s", plan.trace_line())


def resolve_mechanism_name(cfg) -> str:
    """``cfg.mechanism`` when set, else the deprecated ``cfg.kind`` (one
    ``DeprecationWarning`` per process), else the ``"dotprod"`` default."""
    global _kind_warned
    name = getattr(cfg, "mechanism", None)
    if name:
        return name
    kind = getattr(cfg, "kind", None)
    if kind:
        if not _kind_warned:
            _kind_warned = True
            warnings.warn(
                "AttentionConfig.kind is deprecated; set mechanism="
                f"{kind!r} (the registry key) instead",
                DeprecationWarning, stacklevel=2)
        return kind
    return "dotprod"


def plan_attention(cfg, shapes: AttnShapes) -> ExecutionPlan:
    """The planner: explicit, inspectable backend selection.

    Selection order (first eligible wins):

      1. ``cfg.backend`` — forced; ineligibility is an error.
      2. ``cfg.use_kernel`` — deprecated shim for ``backend="pallas"``;
         falls back to automatic selection when the kernel cannot run
         (explicit mask), since the legacy bool could not express
         eligibility.
      3. ``paged_pallas`` on TPU when the KV cache lives in a paged pool
         and this is a single-query decode tick — the block-table-native
         kernel (DESIGN.md §10).
      4. ``paged`` for the remaining paged-pool sites (non-TPU hosts,
         chunked prefill) — the clamped block-table gather.
      5. ``int`` when the inputs are integer lanes.
      6. ``pallas`` on TPU at large structural-mask shapes.
      7. ``blocked`` at large structural-mask shapes
         (``n_q·n_k ≥ cfg.blocked_threshold``).
      8. ``chunked`` when ``n_k > cfg.chunked_threshold``.
      9. ``fused`` (dense default), else ``naive``.
    """
    global _use_kernel_warned
    name = resolve_mechanism_name(cfg)
    mech = get_mechanism(name)

    forced = getattr(cfg, "backend", None)
    shim_note = ""
    # deprecation shim: the legacy bool only ever dispatched the inhibitor
    # family to the kernel (it was a no-op for dotprod), so the shim
    # preserves exactly those semantics — new mechanisms/backends must use
    # the explicit ``backend`` field
    legacy_kernel_mechanism = name in ("inhibitor", "inhibitor_unsigned")
    if (forced is None and getattr(cfg, "use_kernel", False)
            and legacy_kernel_mechanism):
        if not _use_kernel_warned:
            _use_kernel_warned = True
            warnings.warn(
                "AttentionConfig.use_kernel is deprecated; set "
                "backend='pallas' (or leave backend=None for the planner)",
                DeprecationWarning, stacklevel=2)
        # the legacy bool meant "use the TPU kernel" — on non-TPU hosts it
        # would run interpret-mode Pallas (orders of magnitude slower than
        # the XLA paths), which no legacy config ever did intentionally;
        # force an explicit backend="pallas" to get interpret mode
        ok, why = backend_eligible("pallas", cfg, shapes, mech)
        if ok and not kernel_native(kernel_family(name),
                                    shapes.resolved_platform):
            ok, why = False, (f"host platform is "
                              f"{shapes.resolved_platform!r}, no native "
                              f"lowering — kernel would run in interpret "
                              f"mode")
        if ok:
            plan = ExecutionPlan(name, "pallas",
                                 "forced by config (use_kernel shim)")
            _trace(plan, shapes)
            return plan
        shim_note = f"use_kernel requested but pallas ineligible ({why}); "
    elif forced is not None:
        ok, why = backend_eligible(forced, cfg, shapes, mech)
        if not ok:
            raise ValueError(
                f"backend {forced!r} forced by config but ineligible for "
                f"mechanism {name!r} at {shapes!r}: {why}")
        plan = ExecutionPlan(name, forced, "forced by config")
        _trace(plan, shapes)
        return plan

    def eligible(b: str) -> bool:
        return backend_eligible(b, cfg, shapes, mech)[0]

    total = shapes.score_elements
    blocked_at = getattr(cfg, "blocked_threshold", DEFAULT_BLOCKED_THRESHOLD)
    chunked_at = getattr(cfg, "chunked_threshold", DEFAULT_CHUNKED_THRESHOLD)

    if (kernel_native("paged", shapes.resolved_platform)
            and eligible("paged_pallas")):
        plan = ExecutionPlan(
            name, "paged_pallas",
            shim_note + f"paged KV pool, single-query decode "
            f"(block-table-native kernel lowers natively on "
            f"{shapes.resolved_platform!r})")
    elif eligible("paged"):
        if getattr(shapes, "paged", False) and shapes.n_q != 1:
            why = f"chunked prefill n_q={shapes.n_q}"
        else:
            why = (f"no native paged-kernel lowering on "
                   f"{shapes.resolved_platform!r}; interpret-mode Pallas "
                   f"never outranks the gather")
        plan = ExecutionPlan(
            name, "paged",
            shim_note + f"paged KV pool (block-table gather: {why})")
    elif eligible("int"):
        plan = ExecutionPlan(name, "int", shim_note + "integer-lane inputs")
    elif (kernel_native(kernel_family(name), shapes.resolved_platform)
            and total >= blocked_at and eligible("pallas")):
        plan = ExecutionPlan(
            name, "pallas",
            shim_note + f"native pallas lowering on "
            f"{shapes.resolved_platform!r}, structural mask, "
            f"n_q*n_k={total} >= blocked_threshold={blocked_at}")
    elif total >= blocked_at and eligible("blocked"):
        plan = ExecutionPlan(
            name, "blocked",
            shim_note + f"structural mask and n_q*n_k={total} >= "
            f"blocked_threshold={blocked_at}")
    elif shapes.n_k > chunked_at and eligible("chunked"):
        plan = ExecutionPlan(
            name, "chunked",
            shim_note + f"n_k={shapes.n_k} > chunked_threshold={chunked_at}")
    elif eligible("fused"):
        plan = ExecutionPlan(name, "fused", shim_note + "dense default")
    elif eligible("naive"):
        plan = ExecutionPlan(name, "naive",
                             shim_note + "only the oracle backend is eligible")
    else:
        raise ValueError(
            f"no eligible backend for mechanism {name!r} at {shapes!r} "
            f"(registered: {sorted(mech.backends)})")
    _trace(plan, shapes)
    return plan


def choose_plan(mechanism: str, candidates) -> ExecutionPlan:
    """Generic first-eligible-wins chooser for non-(q, k, v) token mixers
    (e.g. the RWKV WKV path).  ``candidates`` is an ordered iterable of
    ``(backend, eligible, reason)``; the chosen plan is trace-logged like
    :func:`plan_attention` decisions."""
    for backend, ok, reason in candidates:
        if ok:
            plan = ExecutionPlan(mechanism, backend, reason)
            _trace(plan)
            return plan
    raise ValueError(f"no eligible backend among candidates for "
                     f"{mechanism!r}")


def execute_plan(plan: ExecutionPlan, q, k, v, *,
                 params: MechanismParams,
                 mask=None,
                 structural: Optional[Structural] = None,
                 paged: Optional[PagedLayout] = None) -> jax.Array:
    """Run ``plan`` on (q, k, v): q (b, n_q, h, d); k, v (b, n_k, h_kv, d).

    ``mask`` is only legal for mask-consuming backends; mask-free backends
    take ``structural`` instead.  Mixing the two is a dispatch bug and
    fails loudly.  For the ``paged`` backend, k/v are page pools
    (num_pages, h_kv, page_size, d) and ``paged`` carries the block tables.
    """
    mech = get_mechanism(plan.mechanism)
    fn = mech.backends.get(plan.backend)
    if fn is None:
        raise ValueError(f"plan names backend {plan.backend!r} which is not "
                         f"registered for mechanism {plan.mechanism!r}")
    if plan.backend in MASK_FREE_BACKENDS and mask is not None:
        raise ValueError(f"backend {plan.backend!r} is mask-free; got an "
                         f"explicit mask array")
    if (paged is not None) != (plan.backend in PAGED_BACKENDS):
        raise ValueError(
            f"backend {plan.backend!r} and paged layout "
            f"{'given' if paged is not None else 'missing'} — paged pools "
            f"are only consumable by {sorted(PAGED_BACKENDS)}")
    if plan.backend in PAGED_BACKENDS:
        return fn(q, k, v, mask=mask, params=params, structural=structural,
                  paged=paged)
    return fn(q, k, v, mask=mask, params=params, structural=structural)


# ---------------------------------------------------------------------------
# Shared layout helpers for the builtin backends
# ---------------------------------------------------------------------------

def _to_heads(q, k, v):
    """(b, n, h|h_kv, d) -> GQA-repeated (b, h, n, d) triples (float32 kept
    by the callee; this only handles layout)."""
    from repro.core.inhibitor import _repeat_kv

    h = q.shape[2]
    rep = h // k.shape[2]
    k = _repeat_kv(k, rep)
    v = _repeat_kv(v, rep)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _int_shifts(params: MechanismParams, d: int) -> Tuple[int, int]:
    """Map the float-domain (γ, α) onto the integer lanes' power-of-two
    analogues: γ ≈ 2^shift, α rounded to the nearest integer level."""
    import math

    gamma = (params.score_scale if params.score_scale is not None
             else float(d) ** 0.5)
    shift = max(0, int(round(math.log2(gamma)))) if gamma > 1 else 0
    return shift, max(0, int(round(params.score_shift)))


# ---------------------------------------------------------------------------
# Builtin backends — inhibitor family (signed fixed per mechanism)
# ---------------------------------------------------------------------------

def _inhibitor_naive(q, k, v, *, mask=None, params, structural=None):
    """Broadcast oracle: eq. 5 scores, large-Z masking, eq. 6/7 inhibition."""
    from repro.core import inhibitor as inh

    n_k = k.shape[1]
    qt, kt, vt = _to_heads(q, k, v)
    z = inh.manhattan_scores(qt, kt, score_scale=params.score_scale,
                             score_shift=params.score_shift)
    m = None
    if mask is not None:
        m = jnp.broadcast_to(mask, z.shape)
        z = inh.mask_scores(z, m)
    out = (inh.inhibit_signed_naive(vt, z) if params.signed
           else inh.inhibit_naive(vt, z))
    if params.normalize:
        if m is not None:
            cnt = jnp.sum(m.astype(jnp.float32), axis=-1, keepdims=True)
        else:
            cnt = jnp.asarray(float(n_k), jnp.float32)
        out = out / jnp.maximum(cnt, 1.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _inhibitor_fused(q, k, v, *, mask=None, params, structural=None):
    from repro.core import inhibitor as inh

    return inh.inhibitor_attention(
        q, k, v, mask=mask, score_scale=params.score_scale,
        score_shift=params.score_shift, signed=params.signed,
        normalize=params.normalize)


def _inhibitor_chunked(q, k, v, *, mask=None, params, structural=None):
    from repro.core import inhibitor as inh

    return inh.inhibitor_attention_chunked(
        q, k, v, mask=mask, score_scale=params.score_scale,
        score_shift=params.score_shift, signed=params.signed,
        normalize=params.normalize, kv_chunk=params.kv_chunk)


def _inhibitor_blocked(q, k, v, *, mask=None, params, structural=None):
    from repro.core.blocked import blocked_inhibitor_attention

    s = structural or Structural()
    return blocked_inhibitor_attention(
        q, k, v, score_scale=params.score_scale,
        score_shift=params.score_shift, signed=params.signed,
        normalize=params.normalize, causal=s.causal, window=s.window,
        q_offset=s.q_offset, kv_valid_len=s.kv_valid_len,
        chunk_k=params.kv_chunk, chunk_q=min(params.kv_chunk, 512))


def _kernel_choice(params: MechanismParams):
    """Config block-size overrides -> a :class:`repro.kernels.ops.
    KernelChoice` (or None, letting the kernel registry tune)."""
    if (params.kernel_block_q is None and params.kernel_block_k is None
            and params.kernel_sub_k is None
            and params.kernel_pages_per_step is None):
        return None
    from repro.kernels.ops import KernelChoice

    return KernelChoice(params.kernel_block_q, params.kernel_block_k,
                        params.kernel_sub_k, params.kernel_pages_per_step)


def _structural_is_plain(s: Structural) -> bool:
    """True when the Structural carries no decode-cache cursors — the
    custom-VJP training kernel applies; otherwise the cursor-carrying
    (inference-only) entry point is used."""
    return (s.kv_valid_len is None
            and isinstance(s.q_offset, int) and s.q_offset == 0)


def _inhibitor_pallas(q, k, v, *, mask=None, params, structural=None):
    from repro.kernels import ops as kops

    s = structural or Structural()
    choice = _kernel_choice(params)
    if _structural_is_plain(s):
        return kops.flash_inhibitor(q, k, v, params.score_scale,
                                    params.score_shift, params.signed,
                                    params.normalize, s.causal, s.window,
                                    choice)
    return kops.flash_inhibitor_cached(
        q, k, v, s.q_offset, s.kv_valid_len, score_scale=params.score_scale,
        score_shift=params.score_shift, signed=params.signed,
        normalize=params.normalize, causal=s.causal, window=s.window,
        choice=choice)


def _gather_pages(k_pool, v_pool, paged: PagedLayout):
    """Gather per-row contiguous KV views out of the page pools.

    k_pool/v_pool: (num_pages, h_kv, page_size, d); block tables (b, P).
    Returns (b, P*page_size, h_kv, d) views — one gather per call, fused by
    XLA into the downstream reads.  Unmapped table entries point at the
    reserved trash page 0; those rows sit beyond the valid-length mask.

    This is the non-TPU / prefill fallback: the serve engine clamps the
    table width handed in here to the bucketed batch high-water page
    count, so the gather is O(pages actually held), not O(pool) — and on
    TPU single-query decode the planner selects ``paged_pallas`` instead,
    which never materializes this view at all (DESIGN.md §10).
    """
    def view(pool):
        t = pool[paged.block_tables]              # (b, P, h_kv, ps, d)
        b, npg, hk, ps, d = t.shape
        return t.transpose(0, 1, 3, 2, 4).reshape(b, npg * ps, hk, d)

    return view(k_pool), view(v_pool)


def _paged_lengths(q, s: Structural):
    """Per-row valid-length cursors for the paged decode kernels."""
    if s.kv_valid_len is None:
        raise ValueError(
            "paged_pallas needs per-row kv_valid_len cursors (the paged "
            "cache always carries them); got Structural(kv_valid_len=None)")
    lengths = jnp.asarray(s.kv_valid_len, jnp.int32)
    return jnp.broadcast_to(jnp.atleast_1d(lengths), (q.shape[0],))


def _inhibitor_paged(q, k, v, *, mask=None, params, structural=None,
                     paged=None):
    kc, vc = _gather_pages(k, v, paged)
    return _inhibitor_fused(q, kc, vc, mask=mask, params=params)


def _inhibitor_paged_pallas(q, k, v, *, mask=None, params, structural=None,
                            paged=None):
    """Block-table-native decode: k/v are page pools; the kernel grid
    walks each row's block table (no contiguous gather)."""
    from repro.kernels import ops as kops

    s = structural or Structural()
    return kops.paged_flash_inhibitor(
        q, k, v, paged.block_tables, _paged_lengths(q, s),
        score_scale=params.score_scale, score_shift=params.score_shift,
        signed=params.signed, normalize=params.normalize, window=s.window,
        choice=_kernel_choice(params))


def _inhibitor_int(q, k, v, *, mask=None, params, structural=None):
    """Lane dispatch: the mechanism's lane_fn on the jnp int32 lane."""
    from repro.core.lanes import IntLane
    from repro.quant.int_attention import (lane_attention_heads,
                                           lane_inhibitor_attention)

    gamma_shift, alpha_q = _int_shifts(params, q.shape[-1])
    return lane_attention_heads(
        IntLane(), lane_inhibitor_attention, q, k, v, mask=mask,
        gamma_shift=gamma_shift, alpha_q=alpha_q, signed=params.signed,
        normalize=params.normalize)


# ---------------------------------------------------------------------------
# Builtin backends — dot-product (Softmax) family
# ---------------------------------------------------------------------------

def _dotprod_naive(q, k, v, *, mask=None, params, structural=None):
    """Plain-jnp Softmax oracle (no custom VJP — autodiff reference)."""
    d = q.shape[-1]
    scale = (params.score_scale if params.score_scale is not None
             else float(d) ** 0.5)
    qt, kt, vt = _to_heads(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32),
                        kt.astype(jnp.float32)) / scale
    if mask is not None:
        logits = jnp.where(jnp.broadcast_to(mask, logits.shape), logits,
                           -1e9)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _dotprod_fused(q, k, v, *, mask=None, params, structural=None):
    from repro.core import dotprod as dp

    return dp.dot_product_attention(q, k, v, mask=mask,
                                    score_scale=params.score_scale)


def _dotprod_pallas(q, k, v, *, mask=None, params, structural=None):
    from repro.kernels import ops as kops

    s = structural or Structural()
    choice = _kernel_choice(params)
    if _structural_is_plain(s):
        return kops.flash_attention(q, k, v, params.score_scale, s.causal,
                                    s.window, choice)
    return kops.flash_attention_cached(
        q, k, v, s.q_offset, s.kv_valid_len, score_scale=params.score_scale,
        causal=s.causal, window=s.window, choice=choice)


def _dotprod_paged(q, k, v, *, mask=None, params, structural=None,
                   paged=None):
    kc, vc = _gather_pages(k, v, paged)
    return _dotprod_fused(q, kc, vc, mask=mask, params=params)


def _dotprod_paged_pallas(q, k, v, *, mask=None, params, structural=None,
                          paged=None):
    from repro.kernels import ops as kops

    s = structural or Structural()
    return kops.paged_flash_attention(
        q, k, v, paged.block_tables, _paged_lengths(q, s),
        score_scale=params.score_scale, window=s.window,
        choice=_kernel_choice(params))


def _dotprod_int(q, k, v, *, mask=None, params, structural=None):
    """Lane dispatch: the mechanism's lane_fn on the jnp int32 lane."""
    from repro.core.lanes import IntLane
    from repro.quant.int_attention import (lane_attention_heads,
                                           lane_dot_product_attention)

    scale_shift, _ = _int_shifts(params, q.shape[-1])
    return lane_attention_heads(
        IntLane(), lane_dot_product_attention, q, k, v, mask=mask,
        scale_shift=scale_shift)


# ---------------------------------------------------------------------------
# fhe_sim adapter (lane dispatch onto the TFHE simulator; forced only)
# ---------------------------------------------------------------------------

def _fhe_backend(lane_fn, *, use_signed=False, **lane_kw):
    """Adapt the mechanism's lane_fn, run on a fresh :class:`FheSimLane`,
    to the uniform (b, n, h, d) layout.  Runs outside jit (concrete
    integer arrays)."""
    import numpy as np

    def fn(q, k, v, *, mask=None, params=None, structural=None):
        from repro.core import lanes
        from repro.quant.int_attention import lane_attention_heads

        if mask is not None:
            raise ValueError("fhe_sim circuits attend all-to-all; explicit "
                             "masks are unsupported")
        lane = lanes.FheSimLane()
        kw = dict(lane_kw)
        if use_signed and params is not None:
            kw["signed"] = params.signed
            kw["normalize"] = params.normalize
        qn, kn, vn = (lane.array(np.asarray(jax.device_get(t),
                                            dtype=np.int64))
                      for t in (q, k, v))
        out = lane_attention_heads(lane, lane_fn, qn, kn, vn, **kw)
        return jnp.asarray(lane.to_numpy(out).astype(np.int32))

    return fn


# ---------------------------------------------------------------------------
# Builtin registrations
# ---------------------------------------------------------------------------

def _register_builtins() -> None:
    from repro.fhe.circuits import (dotprod_attention_circuit,
                                    inhibitor_attention_circuit)
    from repro.quant.int_attention import (int_dot_product_attention,
                                           int_inhibitor_attention,
                                           lane_dot_product_attention,
                                           lane_inhibitor_attention)

    register_mechanism(Mechanism(
        name="dotprod",
        description="Scaled dot-product Softmax attention (paper eq. 3)",
        mask_semantics="neg_inf",
        vjp="analytic",
        backends={
            "naive": _dotprod_naive,
            "fused": _dotprod_fused,
            "pallas": _dotprod_pallas,
            "paged": _dotprod_paged,
            "paged_pallas": _dotprod_paged_pallas,
            "int": _dotprod_int,
            "fhe_sim": _fhe_backend(lane_dot_product_attention,
                                    scale_shift=2, frac_bits=4),
        },
        lane_fn=lane_dot_product_attention,
        fhe_circuit=dotprod_attention_circuit,
        int_reference=int_dot_product_attention,
    ))

    _inhibitor_backends = {
        "naive": _inhibitor_naive,
        "fused": _inhibitor_fused,
        "chunked": _inhibitor_chunked,
        "blocked": _inhibitor_blocked,
        "pallas": _inhibitor_pallas,
        "paged": _inhibitor_paged,
        "paged_pallas": _inhibitor_paged_pallas,
        "int": _inhibitor_int,
        # the encrypted arm runs the same lane_fn on the TFHE simulator;
        # ``signed`` follows the mechanism (eq. 7 doubles the ReLU LUTs)
        "fhe_sim": _fhe_backend(lane_inhibitor_attention, use_signed=True,
                                gamma_shift=1, alpha_q=1),
    }
    register_mechanism(Mechanism(
        name="inhibitor",
        description="Signed inhibitor attention (paper eq. 7 / fused eq. 10)",
        mask_semantics="exclude",
        vjp="analytic",
        backends=dict(_inhibitor_backends),
        param_overrides={"signed": True},
        lane_fn=lane_inhibitor_attention,
        fhe_circuit=inhibitor_attention_circuit,
        int_reference=int_inhibitor_attention,
    ))
    register_mechanism(Mechanism(
        name="inhibitor_unsigned",
        description="Unsigned inhibitor attention (paper eq. 6 / fused eq. 9)",
        mask_semantics="exclude",
        vjp="analytic",
        backends=dict(_inhibitor_backends),
        param_overrides={"signed": False},
        lane_fn=lane_inhibitor_attention,
        fhe_circuit=inhibitor_attention_circuit,
        int_reference=int_inhibitor_attention,
    ))


_register_builtins()
