"""Shared platform cost table + analytic jaxpr cost model.

This module is the single home for the accelerator constants that
``benchmarks/roofline.py`` used to hard-code, plus two static analyses
built on them:

* :func:`jaxpr_costs` — walk a (closed) jaxpr and tally FLOPs and an
  HBM-byte upper bound per equation, recursing through ``pjit``/
  ``scan``/``cond``/``while``/``pallas_call``.  The byte count is the
  *unfused* sum of operand+result bytes — an upper bound XLA's fuser
  only improves on — except for gather/scatter-family primitives, where
  counting the full operand would be wildly wrong (a paged-KV gather
  reads the gathered rows, not the whole pool), so only the moved data
  is charged.
* :func:`kernel_prior` / :func:`rank_kernel_candidates` — a static
  execution-time prior for ``KernelRegistry`` candidate configs (grid
  dispatch overhead + HBM traffic + FLOPs, with a VMEM feasibility
  guard), letting the autotuner rank candidates *before* any timing
  runs and skip statically-infeasible ones.

Import cost: stdlib only at module level (``jax`` is imported lazily
inside :func:`jaxpr_costs`' callers' jaxprs, never here), so the lint
and the analyzer CLI stay fast to start.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence, Tuple

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Platform:
    """Peak numbers for one accelerator, used by every roofline in the
    repo (benchmarks and static analysis share this table)."""

    name: str
    peak_flops: float        # sustained matmul FLOP/s (bf16)
    hbm_bw: float            # HBM bandwidth, bytes/s
    link_bw: float           # inter-chip interconnect, bytes/s
    h2d_bw: float            # host<->device (PCIe-class), bytes/s
    dispatch_s: float        # fixed overhead per launched grid step
    vmem_bytes: int          # on-chip vector memory per core


#: TPU v5e — the numbers ``benchmarks/roofline.py`` has always used.
TPU_V5E = Platform(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    link_bw=50e9,
    h2d_bw=32e9,
    dispatch_s=1e-6,
    vmem_bytes=128 * 2 ** 20,
)

DEFAULT_PLATFORM = TPU_V5E


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Costs:
    """Accumulated static costs of one jaxpr."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    host_callbacks: int = 0      # pure/io/debug callbacks — host syncs
    unbounded_loops: int = 0     # while-loops: cost counted for one trip

    def add(self, other: "Costs", scale: float = 1.0) -> None:
        self.flops += other.flops * scale
        self.hbm_bytes += other.hbm_bytes * scale
        self.host_callbacks += other.host_callbacks
        self.unbounded_loops += other.unbounded_loops

    def as_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "host_callbacks": self.host_callbacks,
            "unbounded_loops": self.unbounded_loops,
        }


#: primitives that move/relayout data without arithmetic — charged
#: bytes for the *moved* data only (out read+write), zero FLOPs
_DATA_MOVEMENT = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "rev", "slice",
    "concatenate", "squeeze", "expand_dims", "convert_element_type",
    "iota", "copy", "pad", "select_n", "split",
})

#: gather/scatter family: charge moved slices + index bytes, never the
#: full operand (a paged-KV gather does not read the whole pool).  This
#: is what makes the whole-model fused page gather (DESIGN.md §14) win
#: *statically*: one all-layer gather charges the table's index bytes
#: once where the per-layer path charged them num_layers times — the
#: drop ANALYSIS_serve.json's decode roofline gates on.
_GATHER_LIKE = frozenset({"gather", "dynamic_slice"})
_SCATTER_LIKE = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter-mul",
    "scatter-min", "scatter-max", "dynamic_update_slice",
})

#: reductions: one FLOP per input element
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
    "cumlogsumexp", "cummax", "cummin", "cumprod",
})

#: host-callback primitives — each is a device<->host synchronisation
#: point inside a jitted computation
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "infeed", "outfeed",
})


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * int(dtype.itemsize)
    except (TypeError, ValueError):      # polymorphic dims etc.
        return 0


def _aval_size(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        return int(math.prod(shape))
    except (TypeError, ValueError):
        return 0


def _in_avals(eqn) -> List[Any]:
    from jax.extend.core import Literal
    return [v.aval for v in eqn.invars if not isinstance(v, Literal)]


def _dot_general_flops(eqn) -> float:
    ((lhs_c, _rhs_c), (lhs_b, _rhs_b)) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    contract = math.prod(lhs.shape[d] for d in lhs_c) or 1
    batch = math.prod(lhs.shape[d] for d in lhs_b) or 1
    out_elems = sum(_aval_size(v.aval) for v in eqn.outvars)
    # out already includes the batch dims; 2 FLOPs (mul+add) per MAC
    del batch
    return 2.0 * out_elems * contract


def _conv_flops(eqn) -> float:
    rhs = eqn.invars[1].aval           # kernel: spatial... x in_ch x out_ch
    out_elems = sum(_aval_size(v.aval) for v in eqn.outvars)
    kernel_macs = _aval_size(rhs) // max(rhs.shape[-1], 1)
    return 2.0 * out_elems * max(kernel_macs, 1)


def jaxpr_costs(jaxpr) -> Costs:
    """Tally static costs of a jaxpr (``jax.make_jaxpr(f)(*avals)``).

    Accepts a ``ClosedJaxpr`` or a raw ``Jaxpr``.  ``scan`` bodies are
    multiplied by their trip count; ``cond`` takes the most expensive
    branch; ``while`` bodies are counted once and flagged via
    ``unbounded_loops``; ``pallas_call`` kernels are multiplied by their
    grid size when the grid is statically known.
    """
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    total = Costs()
    for eqn in jx.eqns:
        name = eqn.primitive.name
        params = eqn.params
        if name in CALLBACK_PRIMS:
            total.host_callbacks += 1
            continue
        if name == "scan":
            total.add(jaxpr_costs(params["jaxpr"]),
                      scale=float(params.get("length", 1)))
            continue
        if name == "while":
            total.add(jaxpr_costs(params["body_jaxpr"]))
            total.unbounded_loops += 1
            continue
        if name == "cond":
            branches = [jaxpr_costs(b) for b in params["branches"]]
            worst = max(branches,
                        key=lambda c: c.flops + c.hbm_bytes,
                        default=Costs())
            total.add(worst)
            # callbacks on *any* branch are reachable syncs
            worst_cb = worst.host_callbacks
            total.host_callbacks += (
                sum(b.host_callbacks for b in branches) - worst_cb)
            continue
        if name == "pallas_call":
            try:
                grid = math.prod(params["grid_mapping"].grid) or 1
                total.add(jaxpr_costs(params["jaxpr"]), scale=float(grid))
                continue
            except Exception:      # opaque pallas params: fall through
                pass
        inner = params.get("jaxpr") or params.get("call_jaxpr")
        if inner is not None:      # pjit / custom_vjp / remat / checkpoint
            total.add(jaxpr_costs(inner))
            continue

        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        out_elems = sum(_aval_size(v.aval) for v in eqn.outvars)
        in_avals = _in_avals(eqn)
        in_bytes = sum(_aval_bytes(a) for a in in_avals)

        if name == "dot_general":
            total.flops += _dot_general_flops(eqn)
            total.hbm_bytes += in_bytes + out_bytes
        elif name == "conv_general_dilated":
            total.flops += _conv_flops(eqn)
            total.hbm_bytes += in_bytes + out_bytes
        elif name in _GATHER_LIKE:
            idx_bytes = sum(_aval_bytes(a) for a in in_avals[1:])
            total.hbm_bytes += 2 * out_bytes + idx_bytes
        elif name in _SCATTER_LIKE:
            upd_bytes = (_aval_bytes(in_avals[-1])
                         if in_avals else out_bytes)
            idx_bytes = sum(_aval_bytes(a) for a in in_avals[1:-1])
            total.hbm_bytes += 2 * upd_bytes + idx_bytes
        elif name in _DATA_MOVEMENT:
            total.hbm_bytes += 2 * out_bytes
        elif name in _REDUCTIONS:
            total.flops += sum(_aval_size(a) for a in in_avals)
            total.hbm_bytes += in_bytes + out_bytes
        else:                      # default: elementwise
            total.flops += out_elems
            total.hbm_bytes += in_bytes + out_bytes
    return total


def roofline(costs: Costs, platform: Platform = DEFAULT_PLATFORM, *,
             transfer_bytes: float = 0.0) -> Dict[str, Any]:
    """Roofline estimate for one jaxpr's costs on one platform."""
    compute_s = costs.flops / platform.peak_flops
    memory_s = costs.hbm_bytes / platform.hbm_bw
    transfer_s = transfer_bytes / platform.h2d_bw
    bound = max(
        (("compute", compute_s), ("memory", memory_s),
         ("transfer", transfer_s)),
        key=lambda kv: kv[1])[0]
    return {
        "flops": costs.flops,
        "hbm_bytes": costs.hbm_bytes,
        "transfer_bytes": transfer_bytes,
        "host_callbacks": costs.host_callbacks,
        "unbounded_loops": costs.unbounded_loops,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "transfer_s": transfer_s,
        "est_s": max(compute_s, memory_s) + transfer_s,
        "bound": bound,
    }


# --------------------------------------------------------------------------
# kernel-candidate static priors (KernelRegistry autotuner)
# --------------------------------------------------------------------------

_ITEMSIZE = 4          # kernels stage fp32 tiles in VMEM
_VMEM_BUDGET_FRAC = 4  # stage at most 1/4 of VMEM (double-buffering etc.)


def kernel_prior(family: str, shape_key: Sequence, choice,
                 platform: Platform = DEFAULT_PLATFORM) -> float:
    """Static execution-time prior (seconds) for one KernelChoice.

    ``choice`` is duck-typed (``block_q``/``block_k``/``sub_k``/
    ``pages_per_step`` attributes, any may be ``None``) so this module
    never imports ``repro.kernels``.  Returns ``inf`` for candidates
    whose staged tiles exceed the VMEM budget — statically infeasible,
    the autotuner need not time them.
    """
    vmem_cap = platform.vmem_bytes // _VMEM_BUDGET_FRAC
    if family == "paged":
        _fam, pages, page_size, h, h_kv, d = shape_key
        pps = getattr(choice, "pages_per_step", None) or 1
        steps = math.ceil(pages / pps)
        staged = 2 * pps * page_size * h_kv * d * _ITEMSIZE
        if staged > vmem_cap:
            return float("inf")
        kv_bytes = 2 * pages * page_size * h_kv * d * _ITEMSIZE
        flops = 4.0 * pages * page_size * h * d
        return (steps * platform.dispatch_s
                + kv_bytes / platform.hbm_bw
                + flops / platform.peak_flops)

    # prefill families (inhibitor / flash): blocked attention over a
    # (n_q, n_k) score grid
    n_q, n_k, h, h_kv, d = shape_key[:5]
    causal = bool(shape_key[5]) if len(shape_key) > 5 else False
    bq = getattr(choice, "block_q", None) or 64
    bk = getattr(choice, "block_k", None) or 128
    staged = (2 * bq * d + 2 * bk * d + bq * bk) * _ITEMSIZE
    if staged > vmem_cap:
        return float("inf")
    frac = 0.5 if (causal and n_q == n_k) else 1.0
    q_steps = math.ceil(n_q / bq)
    k_steps = math.ceil(n_k / bk)
    sub = getattr(choice, "sub_k", None)
    sub_steps = (bk / sub) if (family == "inhibitor" and sub) else 1.0
    steps = q_steps * k_steps * frac * sub_steps
    # every q-row pass re-reads the full K/V stream
    kv_bytes = frac * q_steps * 2.0 * n_k * h_kv * d * _ITEMSIZE
    flops = frac * 4.0 * n_q * n_k * h * d
    return (steps * platform.dispatch_s
            + kv_bytes / platform.hbm_bw
            + flops / platform.peak_flops)


def rank_kernel_candidates(family: str, shape_key: Sequence,
                           candidates: Sequence,
                           platform: Platform = DEFAULT_PLATFORM,
                           ) -> List[Tuple[Any, float]]:
    """Rank autotune candidates by static prior, cheapest first.

    The sort is stable, so candidates with equal priors (including a
    run of ``inf``) keep their declared order — the registry's default
    stays first when the model has no opinion.
    """
    priced = [(c, kernel_prior(family, shape_key, c, platform))
              for c in candidates]
    return sorted(priced, key=lambda cp: cp[1])
