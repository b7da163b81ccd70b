"""Paged decode kernels: block-table-native flash-inhibitor / flash-attention.

Serving decode (DESIGN.md §8) keeps KV rows in a shared page pool behind
per-slot block tables.  The ``paged`` backend used to gather the *whole*
pool back into a contiguous ``(b, P·ps, h_kv, d)`` tensor every tick —
O(pool) HBM traffic regardless of how many tokens a row actually holds.
These kernels walk each row's block table *inside the grid* instead
(DESIGN.md §10): the K/V BlockSpec index maps read the scalar-prefetched
block tables, so exactly one physical page is DMA'd into VMEM per staged
input and the contiguous intermediate never exists.

Grid layout:

  * grid = (batch · kv_heads, ceil(P / pages_per_step)) — dimension 1 is
    the sequential walk over each row's logical pages; scratch
    accumulators live across it.
  * scalar prefetch: ``block_tables`` (b, P) int32 and ``lengths`` (b,)
    int32 (the per-row cursor = number of valid KV rows, including the
    token scattered this tick).  Index maps translate (row, step, i) ->
    physical page ``tables[row, step·pps + i]``; entries beyond a row's
    cursor point at the reserved trash page 0, so consecutive dead steps
    re-reference the same block and cost no further copies.
  * ``pages_per_step`` physical pages are staged per grid step as
    separate BlockSpec'd inputs (pages are not contiguous in the pool, so
    one wider block cannot cover them); the kernel loops over the staged
    refs.
  * pools are head-major, (num_pages, h_kv, page_size, d): the staged
    block ``(1, 1, page_size, d)`` is one KV head's contiguous tile of a
    page, so its last two dims are (page_size, full d) — the Mosaic
    tiling rule (multiples of (8, 128) or the full extent) holds for any
    page_size that is a multiple of 8.
  * GQA: all ``group = heads / kv_heads`` query heads sharing a KV head
    are processed against one staged page (same staging as
    :mod:`repro.kernels.inhibitor`).

Masking is per-row and dynamic: ``k_pos < lengths[row]`` from
``broadcasted_iota`` — the single decode query sits at position
``lengths[row] - 1``, so causality is implied and only the sliding
window adds structure.  Pages at-or-beyond the cursor are skipped
entirely (``lax.cond`` around the compute), so per-row work is
O(valid pages), not O(table width).

Decode is inference-only: no custom VJP (the wrappers in
:mod:`repro.kernels.ops` do not register one).

Validated in ``interpret=True`` mode against the gather references in
:mod:`repro.kernels.ref` (tests/test_paged_kernels.py sweeps GQA,
windows, ragged cursors, page-straddling cursors and ``normalize``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGES_PER_STEP = 4

#: Platforms this module's Pallas bodies lower *natively* on.  The grid
#: walks block tables through ``pltpu.PrefetchScalarGridSpec`` scalar
#: prefetch (BlockSpec index maps reading prefetched tables), a
#: TPU/Mosaic feature with no Triton equivalent — on any other platform
#: the body only runs in ``interpret=True`` mode, which must never be
#: picked over the XLA gather path.  A Triton rewrite of the table walk
#: (pointer arithmetic instead of prefetch-indexed BlockSpecs) would
#: extend this to ("tpu", "gpu") and the registry/planner pick it up
#: with no further wiring (kernels.ops.NATIVE_PLATFORMS).
LOWERS_ON = ("tpu",)
NEG_INF = -1e30


def _decode_layout(q, k_pool, block_tables, lengths):
    """Shared shape bookkeeping + the group-major query layout."""
    batch, n_q, heads, d = q.shape
    if n_q != 1:
        raise ValueError(f"paged decode kernels are single-query (n_q=1); "
                         f"got n_q={n_q} — prefill goes through the gather "
                         f"path")
    num_pages, kv_heads, page_size, dk = k_pool.shape
    assert d == dk and heads % kv_heads == 0
    group = heads // kv_heads
    if block_tables.shape[0] != batch or lengths.shape != (batch,):
        raise ValueError(
            f"block_tables {block_tables.shape} / lengths {lengths.shape} "
            f"do not match batch={batch}")
    # head = kv_head * group + g (same factoring as the prefill kernels)
    qg = q.reshape(batch, kv_heads, group, d).reshape(
        batch * kv_heads, group, d)
    return qg, batch, heads, kv_heads, group, d, page_size


def _page_specs(pps: int, page_size: int, kv_heads: int, d: int,
                table_width: int):
    """``2·pps`` BlockSpecs staging pages k0,v0,k1,v1,… per grid step.

    The index maps read the scalar-prefetched block tables; logical page
    indices past the table width clamp to the last column (whose compute
    is masked off by the cursor anyway).
    """
    def page_index(bh, j, tables, lengths, i):
        del lengths
        logical = jnp.minimum(j * pps + i, table_width - 1)
        return (tables[bh // kv_heads, logical], bh % kv_heads, 0, 0)

    specs = []
    for i in range(pps):
        idx = functools.partial(page_index, i=i)
        specs.append(pl.BlockSpec((1, 1, page_size, d), idx))  # k page i
        specs.append(pl.BlockSpec((1, 1, page_size, d), idx))  # v page i
    return specs


def _qo_specs(group: int, d: int):
    def qo_index(bh, j, tables, lengths):
        del j, tables, lengths
        return (bh, 0, 0)
    return pl.BlockSpec((1, group, d), qo_index)


# ---------------------------------------------------------------------------
# paged flash-inhibitor (paper eq. 9 / eq. 10 streaming forms)
# ---------------------------------------------------------------------------

def _paged_inhibitor_kernel(
    tbl_ref, len_ref, q_ref, *rest,
    score_scale: float, score_shift: float, signed: bool, normalize: bool,
    window: Optional[int], kv_heads: int, page_size: int, pps: int,
    n_steps: int,
):
    kv_refs, (o_ref,), (acc_ref, cnt_ref) = (
        rest[:2 * pps], rest[2 * pps:2 * pps + 1], rest[2 * pps + 1:])
    bh = pl.program_id(0)
    j = pl.program_id(1)
    row = bh // kv_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    q = q_ref[0].astype(jnp.float32)              # (group, d)
    valid = len_ref[row]
    q_pos = valid - 1

    def process_page(i, acc, cnt):
        ks = kv_refs[2 * i][0, 0].astype(jnp.float32)         # (ps, d)
        vs = kv_refs[2 * i + 1][0, 0].astype(jnp.float32)

        # ---- scores: Z = relu(Σ_d |q − k| / γ − α)  (eq. 5 + shift) ----
        diff = jnp.abs(q[:, None, :] - ks[None, :, :])        # (g, ps, d)
        z = jnp.sum(diff, axis=-1) * (1.0 / score_scale)      # (g, ps)
        if score_shift:
            z = jnp.maximum(z - score_shift, 0.0)

        # ---- per-row cursor mask from positions (True = attend) ----
        k_pos = ((j * pps + i) * page_size
                 + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1))
        m = k_pos < valid
        if window is not None:
            # the decode query is the newest position, so the window's
            # causal half (k_pos <= q_pos) is already the cursor mask
            m = m & (k_pos > q_pos - window)
        mf = m.astype(jnp.float32)                            # (1, ps)

        # ---- inhibition (masked fused forms, eq. 9 / eq. 10) ----
        col_v = jnp.einsum("os,sd->od", mf, vs)               # (1, d)
        if signed:
            vp = jnp.maximum(vs, 0.0)
            vn = vs - vp
            t_pos = jnp.sum(jnp.abs(vp[None, :, :] - z[..., None])
                            * mf[0][None, :, None], axis=1)   # (g, d)
            t_neg = jnp.sum(jnp.abs(-vn[None, :, :] - z[..., None])
                            * mf[0][None, :, None], axis=1)
            part = 0.5 * (col_v + t_pos - t_neg)              # (g, d)
        else:
            row_z = jnp.sum(z * mf, axis=-1)                  # (g,)
            cross = jnp.sum(jnp.abs(vs[None, :, :] - z[..., None])
                            * mf[0][None, :, None], axis=1)
            part = 0.5 * (col_v - row_z[:, None] + cross)

        return acc + part, cnt + jnp.sum(mf, keepdims=True)

    # the key count lives in a (1, 1) VMEM tile and is read and written
    # as an array: Mosaic cannot store a scalar to VMEM
    def do_step():
        acc, cnt = acc_ref[...], cnt_ref[...]
        for i in range(pps):
            acc, cnt = process_page(i, acc, cnt)
        return acc, cnt

    # skip steps wholly past the cursor (their table entries are trash)
    acc, cnt = jax.lax.cond(
        j * pps * page_size < valid, do_step,
        lambda: (acc_ref[...], cnt_ref[...]))
    acc_ref[...] = acc
    cnt_ref[...] = cnt

    @pl.when(j == n_steps - 1)
    def _finalize():
        out = acc_ref[...]
        if normalize:
            out = out / jnp.maximum(cnt_ref[...], 1.0)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_flash_inhibitor_fwd(
    q: jax.Array,               # (batch, 1, heads, d)
    k_pool: jax.Array,          # (num_pages, kv_heads, page_size, d)
    v_pool: jax.Array,
    block_tables: jax.Array,    # (batch, P) int32
    lengths: jax.Array,         # (batch,) int32 per-row cursors
    *,
    score_scale: Optional[float] = None,
    score_shift: float = 0.5,
    signed: bool = True,
    normalize: bool = True,
    window: Optional[int] = None,
    pages_per_step: int = DEFAULT_PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-native paged inhibitor decode. Returns (batch, 1, heads, d)."""
    qg, batch, heads, kv_heads, group, d, ps = _decode_layout(
        q, k_pool, block_tables, lengths)
    scale = score_scale if score_scale is not None else math.sqrt(d)
    table_width = block_tables.shape[1]
    pps = max(1, min(pages_per_step, table_width))
    n_steps = -(-table_width // pps)

    kernel = functools.partial(
        _paged_inhibitor_kernel,
        score_scale=scale, score_shift=score_shift, signed=signed,
        normalize=normalize, window=window, kv_heads=kv_heads,
        page_size=ps, pps=pps, n_steps=n_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch * kv_heads, n_steps),
        in_specs=[_qo_specs(group, d)] + _page_specs(
            pps, ps, kv_heads, d, table_width),
        out_specs=_qo_specs(group, d),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    pools = [p for _ in range(pps) for p in (k_pool, v_pool)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch * kv_heads, group, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, *pools)
    return out.reshape(batch, 1, heads, d)


# ---------------------------------------------------------------------------
# paged flash attention (Softmax baseline, online recurrence)
# ---------------------------------------------------------------------------

def _paged_attention_kernel(
    tbl_ref, len_ref, q_ref, *rest,
    score_scale: float, window: Optional[int], kv_heads: int,
    page_size: int, pps: int, n_steps: int,
):
    kv_refs, (o_ref,), (acc_ref, m_ref, l_ref) = (
        rest[:2 * pps], rest[2 * pps:2 * pps + 1], rest[2 * pps + 1:])
    bh = pl.program_id(0)
    j = pl.program_id(1)
    row = bh // kv_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)              # (group, d)
    valid = len_ref[row]
    q_pos = valid - 1

    def process_page(i, acc, m_prev, l_prev):
        ks = kv_refs[2 * i][0, 0].astype(jnp.float32)         # (ps, d)
        vs = kv_refs[2 * i + 1][0, 0].astype(jnp.float32)
        k_pos = ((j * pps + i) * page_size
                 + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1))
        m_blk = k_pos < valid
        if window is not None:
            m_blk = m_blk & (k_pos > q_pos - window)

        s = jnp.einsum("gd,sd->gs", q, ks) * (1.0 / score_scale)
        s = jnp.where(m_blk, s, NEG_INF)                      # (g, ps)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked pages: exp(NEG_INF - NEG_INF) = 1 — zero them out
        p = p * jnp.any(m_blk, axis=-1)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("gs,sd->gd", p, vs)
        return acc, m_new, l_new

    def do_step():
        acc, m, l = acc_ref[...], m_ref[...], l_ref[...]
        for i in range(pps):
            acc, m, l = process_page(i, acc, m, l)
        return acc, m, l

    acc, m, l = jax.lax.cond(
        j * pps * page_size < valid, do_step,
        lambda: (acc_ref[...], m_ref[...], l_ref[...]))
    acc_ref[...] = acc
    m_ref[...] = m
    l_ref[...] = l

    @pl.when(j == n_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_flash_attention_fwd(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    score_scale: Optional[float] = None,
    window: Optional[int] = None,
    pages_per_step: int = DEFAULT_PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-native paged Softmax decode. Returns (batch, 1, heads, d)."""
    qg, batch, heads, kv_heads, group, d, ps = _decode_layout(
        q, k_pool, block_tables, lengths)
    scale = score_scale if score_scale is not None else math.sqrt(d)
    table_width = block_tables.shape[1]
    pps = max(1, min(pages_per_step, table_width))
    n_steps = -(-table_width // pps)

    kernel = functools.partial(
        _paged_attention_kernel,
        score_scale=scale, window=window, kv_heads=kv_heads,
        page_size=ps, pps=pps, n_steps=n_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch * kv_heads, n_steps),
        in_specs=[_qo_specs(group, d)] + _page_specs(
            pps, ps, kv_heads, d, table_width),
        out_specs=_qo_specs(group, d),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    pools = [p for _ in range(pps) for p in (k_pool, v_pool)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch * kv_heads, group, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, *pools)
    return out.reshape(batch, 1, heads, d)
