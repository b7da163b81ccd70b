"""Kernel registry: jit'd public wrappers for the Pallas kernels, with one
host-platform decision, per-shape block-size autotuning, and recompute-based
custom VJPs so the training-path kernels are usable under autodiff.

Registry responsibilities (DESIGN.md §10, §14):

  * **One interpret decision per kernel family.**  Each kernel module
    declares the platforms its Pallas body lowers natively on
    (``LOWERS_ON`` → :data:`NATIVE_PLATFORMS`); ``registry.
    interpret_for(family)`` is the per-family decision against the
    cached host platform (non-native hosts run the body as XLA ops in
    ``interpret=True`` mode) — call sites no longer carry their own
    ``not _on_tpu()`` checks, and a family that grows, say, a Triton
    lowering flips to native GPU dispatch by declaration alone.  The
    legacy process-wide ``registry.interpret`` remains as the
    "any-platform-but-TPU" view (today all families declare exactly
    ``("tpu",)``, so the two agree).
  * **Per-shape tuning.**  Every wrapper resolves a :class:`KernelChoice`
    — ``(block_q, block_k, sub_k, pages_per_step)`` — through
    ``registry.choose``: an explicit override (from
    ``AttentionConfig.kernel_*``) wins; otherwise the cached per-shape
    selection is used.  On a *native* platform for the family with
    *concrete* operands (an eager warmup call, e.g.
    ``benchmarks/serve_bench.py``'s un-jitted first tick) the candidate
    set is timed once and the winner cached; a jit trace resolves to
    the default *without* pinning the cache (so a later eager call can
    still tune), and interpret mode caches the default — timing a
    traced or interpreted call would measure nothing real.  Every
    resolution is recorded in ``registry.decisions`` (winner + source +
    platform + native flag) so benches and the planner can report which
    backend won and why.
  * **Kernel families.**  ``flash_inhibitor`` / ``flash_attention``
    (training prefill; custom VJP via the jnp references),
    ``*_cached`` variants carrying per-row ``q_offset`` /
    ``kv_valid_len`` decode cursors (inference-only — no VJP), the
    block-table-native ``paged_*`` decode kernels, and the RWKV6 WKV
    chunk kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash as kflash
from repro.kernels import inhibitor as kinhibitor
from repro.kernels import paged as kpaged
from repro.kernels import ref as kref
from repro.kernels import rwkv6 as krwkv6
from repro.kernels.flash import flash_attention_fwd
from repro.kernels.inhibitor import flash_inhibitor_fwd
from repro.kernels.paged import (paged_flash_attention_fwd,
                                 paged_flash_inhibitor_fwd)
from repro.kernels.rwkv6 import wkv6_chunked

log = logging.getLogger("repro.kernels")


def _host_platform() -> str:
    """The default device's platform.  A failed probe raises: reading it
    as "cpu" would silently put a chip host on the interpret path."""
    return jax.devices()[0].platform


def _on_tpu() -> bool:
    return _host_platform() == "tpu"


#: Per-family native-lowering platforms, assembled from the kernel
#: modules' own ``LOWERS_ON`` declarations — the single source of truth
#: for "would this Pallas body compile here, or only interpret?".  The
#: registry keys the timed-autotune gate and the wrappers' ``interpret``
#: flag on this, and the planner (core.mechanism.kernel_native) keys
#: kernel eligibility on it, so an interpret-mode kernel can never be
#: ranked above an XLA gather path by accident of platform checks
#: scattered across call sites.
NATIVE_PLATFORMS: Dict[str, Tuple[str, ...]] = {
    "inhibitor": tuple(kinhibitor.LOWERS_ON),
    "flash": tuple(kflash.LOWERS_ON),
    "paged": tuple(kpaged.LOWERS_ON),
    "wkv6": tuple(krwkv6.LOWERS_ON),
}


# ---------------------------------------------------------------------------
# KernelChoice + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """Block-size selection for one kernel launch.  ``None`` fields fall
    back to the tuned/default value — a partial override (say, just
    ``block_k``) leaves the rest to the registry.  Hashable, so it rides
    through ``jax.custom_vjp`` nondiff argnums."""
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    sub_k: Optional[int] = None
    pages_per_step: Optional[int] = None

    def merge_onto(self, base: "KernelChoice") -> "KernelChoice":
        return KernelChoice(
            self.block_q if self.block_q is not None else base.block_q,
            self.block_k if self.block_k is not None else base.block_k,
            self.sub_k if self.sub_k is not None else base.sub_k,
            (self.pages_per_step if self.pages_per_step is not None
             else base.pages_per_step))

    @property
    def empty(self) -> bool:
        return self == KernelChoice()


#: Candidate grids per kernel family — first entry is the default.
CANDIDATES: Dict[str, Tuple[KernelChoice, ...]] = {
    "inhibitor": (
        KernelChoice(64, 128, 16), KernelChoice(32, 128, 16),
        KernelChoice(128, 128, 16), KernelChoice(64, 256, 32),
        KernelChoice(64, 128, 8),
    ),
    "flash": (
        KernelChoice(64, 128), KernelChoice(32, 128),
        KernelChoice(128, 128), KernelChoice(64, 256),
    ),
    "paged": (
        KernelChoice(pages_per_step=4), KernelChoice(pages_per_step=1),
        KernelChoice(pages_per_step=2), KernelChoice(pages_per_step=8),
    ),
}


class KernelRegistry:
    """Process-wide kernel dispatch state: the cached host platform, the
    per-family interpret decision, and the per-(family, shape) tuned
    :class:`KernelChoice` cache."""

    def __init__(self):
        # test escape hatch: monkeypatching ``_interpret`` to a bool
        # overrides *every* family's decision (pretend-TPU in tests)
        self._interpret: Optional[bool] = None
        self._platform: Optional[str] = None
        self.tuned: Dict[tuple, KernelChoice] = {}
        # static cost-model ranking per tuned shape (costmodel priors):
        # [(KernelChoice, prior_seconds), ...] cheapest-first, recorded
        # whenever a timed tune runs — introspection for benches/tests
        self.priors: Dict[tuple, list] = {}
        # (family,) + shape_key -> {"choice", "source", "platform",
        # "native"}: which launch config won the last resolution and why
        # ("override" | "timed" | "default-interpret" | "default-trace")
        self.decisions: Dict[tuple, dict] = {}

    @property
    def platform(self) -> str:
        """Host platform, resolved once per process (``reset`` re-probes)."""
        if self._platform is None:
            self._platform = _host_platform()
        return self._platform

    @property
    def interpret(self) -> bool:
        """Legacy process-wide view: True anywhere the TPU-era kernels
        would interpret (i.e. any non-TPU host).  Family-aware call
        sites use :meth:`interpret_for` instead."""
        if self._interpret is not None:
            return self._interpret
        return self.platform != "tpu"

    def interpret_for(self, family: str) -> bool:
        """Per-family interpret decision: False exactly when ``family``'s
        Pallas body lowers natively on this host (its module's
        ``LOWERS_ON`` declaration contains :attr:`platform`)."""
        if self._interpret is not None:
            return self._interpret
        return self.platform not in NATIVE_PLATFORMS.get(family, ("tpu",))

    def reset(self) -> None:
        """Drop cached decisions (tests / device topology changes)."""
        self._interpret = None
        self._platform = None
        self.tuned.clear()
        self.priors.clear()
        self.decisions.clear()

    def _record(self, family: str, key: tuple, choice: KernelChoice,
                source: str) -> None:
        self.decisions[key] = {
            "choice": choice, "source": source,
            "platform": self.platform,
            "native": not self.interpret_for(family),
        }

    def choose(self, family: str, shape_key: tuple,
               override: Optional[KernelChoice] = None,
               timer: Optional[Callable[[KernelChoice], float]] = None,
               ) -> KernelChoice:
        """Resolve the launch configuration for ``family`` at ``shape_key``.

        ``override`` (non-empty) short-circuits tuning — explicit config
        wins.  ``timer`` runs one candidate and returns seconds; it is
        only consulted on a platform where ``family`` lowers natively
        (``interpret_for``) with concrete operands, and the winner is
        cached per shape so tuning cost is paid once.
        """
        candidates = CANDIDATES[family]
        default = candidates[0]
        key = (family,) + shape_key
        if override is not None and not override.empty:
            # partial overrides fill their None fields from the tuned
            # per-shape choice when one exists, else the default
            merged = override.merge_onto(self.tuned.get(key, default))
            self._record(family, key, merged, "override")
            return merged
        hit = self.tuned.get(key)
        if hit is not None:
            # the decision for this key was recorded when it was tuned
            return hit
        if timer is None:
            # trace-time resolution: use the default but do NOT pin the
            # cache — a later concrete-operand (eager warmup) call for the
            # same shape must still be able to tune
            if key not in self.decisions:
                self._record(family, key, default, "default-trace")
            return default
        choice = default
        source = "default-interpret"
        if not self.interpret_for(family):
            source = "timed"
            # static roofline priors (repro.analysis.costmodel) rank the
            # candidates before any timing runs: timing walks the list
            # cheapest-prior-first and candidates the model proves
            # infeasible (staged tiles over the VMEM budget) are skipped
            # outright — unless the model rejects everything, in which
            # case the ranking is advisory only and all are timed
            ranked = self._ranked(family, shape_key, candidates)
            skip_inf = any(p != float("inf") for _, p in ranked)
            best_t = float("inf")
            errors = []
            for cand, prior in ranked:
                if skip_inf and prior == float("inf"):
                    continue
                try:
                    t = timer(cand)
                except Exception as e:  # noqa: BLE001 — an invalid
                    # candidate (VMEM overflow, …) drops out, on record
                    log.warning("autotune %s %s: candidate %s dropped: "
                                "%s: %s", family, shape_key, cand,
                                type(e).__name__, e)
                    errors.append(e)
                    continue
                if t < best_t:
                    best_t, choice = t, cand
            if best_t == float("inf") and errors:
                raise errors[0]
        self.tuned[key] = choice
        self._record(family, key, choice, source)
        return choice

    def _ranked(self, family: str, shape_key: tuple, candidates):
        """Candidates sorted by static prior (recorded in ``priors``);
        declared order on any cost-model failure."""
        key = (family,) + shape_key
        try:
            from repro.analysis.costmodel import rank_kernel_candidates
            ranked = rank_kernel_candidates(family, shape_key, candidates)
        except Exception as e:  # noqa: BLE001 — priors must never block
            log.warning("autotune %s %s: cost-model priors failed (%s: "
                        "%s); timing in declared order", family,
                        shape_key, type(e).__name__, e)
            ranked = [(c, float("inf")) for c in candidates]
        self.priors[key] = ranked
        return ranked


registry = KernelRegistry()


def _concrete(*arrays) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _timer(fn: Callable[[KernelChoice], jax.Array]):
    """best-of-3 wall-clock timer for one candidate (TPU autotune only)."""
    def run(choice: KernelChoice) -> float:
        jax.block_until_ready(fn(choice))       # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(choice))
            best = min(best, time.perf_counter() - t0)
        return best
    return run


# ---------------------------------------------------------------------------
# flash inhibitor (paper's mechanism)
# ---------------------------------------------------------------------------

def _prefill_choice(family, q, k, causal, window, cached,
                    override: Optional[KernelChoice], runner):
    """Shared choice resolution for the prefill-layout kernel families
    ("inhibitor" / "flash"): same shape key, same concrete-operand
    timing gate."""
    shape_key = (q.shape[1], k.shape[1], q.shape[2], k.shape[2], q.shape[3],
                 causal, window, cached)
    timer = None
    if (override is None or override.empty) and _concrete(q, k):
        timer = _timer(runner)
    return registry.choose(family, shape_key, override, timer)


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_inhibitor(q, k, v, score_scale=None, score_shift=0.5, signed=True,
                    normalize=True, causal=True, window=None, choice=None):
    """Flash-inhibitor attention with recompute-based backward.

    Forward runs the Pallas kernel; backward recomputes via the jnp
    reference (activation-checkpoint style — no score matrix is saved).
    ``choice`` (a :class:`KernelChoice`) overrides the tuned block sizes.
    """
    def run(c: KernelChoice):
        return flash_inhibitor_fwd(
            q, k, v, score_scale=score_scale, score_shift=score_shift,
            signed=signed, normalize=normalize, causal=causal, window=window,
            block_q=c.block_q, block_k=c.block_k, sub_k=c.sub_k,
            interpret=registry.interpret_for("inhibitor"))

    return run(_prefill_choice("inhibitor", q, k, causal, window, False,
                               choice, run))


def _fi_fwd(q, k, v, score_scale, score_shift, signed, normalize, causal,
            window, choice):
    out = flash_inhibitor(q, k, v, score_scale, score_shift, signed,
                          normalize, causal, window, choice)
    return out, (q, k, v)


def _fi_bwd(score_scale, score_shift, signed, normalize, causal, window,
            choice, res, g):
    q, k, v = res

    def f(q_, k_, v_):
        return kref.flash_inhibitor_ref(
            q_, k_, v_, score_scale=score_scale, score_shift=score_shift,
            signed=signed, normalize=normalize, causal=causal, window=window)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_inhibitor.defvjp(_fi_fwd, _fi_bwd)


def flash_inhibitor_cached(q, k, v, q_offset, kv_valid_len, *,
                           score_scale=None, score_shift=0.5, signed=True,
                           normalize=True, causal=True, window=None,
                           choice=None):
    """Decode-cache flash inhibitor: per-row ``q_offset`` / ``kv_valid_len``
    cursors (traced int32 scalars or (b,) arrays).  Inference-only — no
    custom VJP is registered for the cursor-carrying form."""
    def run(c: KernelChoice):
        return flash_inhibitor_fwd(
            q, k, v, score_scale=score_scale, score_shift=score_shift,
            signed=signed, normalize=normalize, causal=causal, window=window,
            block_q=c.block_q, block_k=c.block_k, sub_k=c.sub_k,
            q_offset=q_offset, kv_valid_len=kv_valid_len,
            interpret=registry.interpret_for("inhibitor"))

    return run(_prefill_choice("inhibitor", q, k, causal, window, True,
                               choice, run))


# ---------------------------------------------------------------------------
# flash attention (baseline mechanism)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, score_scale=None, causal=True, window=None,
                    choice=None):
    def run(c: KernelChoice):
        return flash_attention_fwd(
            q, k, v, score_scale=score_scale, causal=causal, window=window,
            block_q=c.block_q, block_k=c.block_k,
            interpret=registry.interpret_for("flash"))

    return run(_prefill_choice("flash", q, k, causal, window, False,
                               choice, run))


def _fa_fwd(q, k, v, score_scale, causal, window, choice):
    out = flash_attention(q, k, v, score_scale, causal, window, choice)
    return out, (q, k, v)


def _fa_bwd(score_scale, causal, window, choice, res, g):
    q, k, v = res

    def f(q_, k_, v_):
        return kref.flash_attention_ref(
            q_, k_, v_, score_scale=score_scale, causal=causal, window=window)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_cached(q, k, v, q_offset, kv_valid_len, *,
                           score_scale=None, causal=True, window=None,
                           choice=None):
    """Decode-cache flash attention (see :func:`flash_inhibitor_cached`)."""
    def run(c: KernelChoice):
        return flash_attention_fwd(
            q, k, v, score_scale=score_scale, causal=causal, window=window,
            block_q=c.block_q, block_k=c.block_k,
            q_offset=q_offset, kv_valid_len=kv_valid_len,
            interpret=registry.interpret_for("flash"))

    return run(_prefill_choice("flash", q, k, causal, window, True,
                               choice, run))


# ---------------------------------------------------------------------------
# paged decode kernels (block-table-native serving decode)
# ---------------------------------------------------------------------------

def _paged_choice(family_key, q, k_pool, block_tables,
                  override: Optional[KernelChoice], runner):
    # (family, pages, page_size, heads, kv_heads, d) — head-major pools
    shape_key = (family_key, block_tables.shape[1], k_pool.shape[2],
                 q.shape[2], k_pool.shape[1], q.shape[3])
    timer = None
    if (override is None or override.empty) and _concrete(
            q, k_pool, block_tables):
        timer = _timer(runner)
    return registry.choose("paged", shape_key, override, timer)


def paged_flash_inhibitor(q, k_pool, v_pool, block_tables, lengths, *,
                          score_scale=None, score_shift=0.5, signed=True,
                          normalize=True, window=None, choice=None):
    """Block-table-native paged inhibitor decode (inference-only)."""
    def run(c: KernelChoice):
        return paged_flash_inhibitor_fwd(
            q, k_pool, v_pool, block_tables, lengths,
            score_scale=score_scale, score_shift=score_shift, signed=signed,
            normalize=normalize, window=window,
            pages_per_step=c.pages_per_step,
            interpret=registry.interpret_for("paged"))

    return run(_paged_choice("inhibitor", q, k_pool, block_tables, choice,
                             run))


def paged_flash_attention(q, k_pool, v_pool, block_tables, lengths, *,
                          score_scale=None, window=None, choice=None):
    """Block-table-native paged Softmax decode (inference-only)."""
    def run(c: KernelChoice):
        return paged_flash_attention_fwd(
            q, k_pool, v_pool, block_tables, lengths,
            score_scale=score_scale, window=window,
            pages_per_step=c.pages_per_step,
            interpret=registry.interpret_for("paged"))

    return run(_paged_choice("flash", q, k_pool, block_tables, choice, run))


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------

def wkv6(r, k, v, w, u, state=None, *, chunk: int = 32):
    """Chunked WKV6 (kernel) when starting from zero state; the exact scan
    when a carry state is provided.  The kernel-vs-scan *plan* is made
    (and trace-logged) once at the model level — models.rwkv.apply_block's
    ``choose_plan`` — so this wrapper only enforces the state-carry
    constraint for direct callers."""
    if state is not None:
        return kref.wkv6_ref(r, k, v, w, u, state)
    return wkv6_chunked(r, k, v, w, u, chunk=chunk,
                        interpret=registry.interpret_for("wkv6"))
