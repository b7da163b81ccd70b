"""Paged decode kernels: block-table-native flash-inhibitor / flash-attention.

Serving decode (DESIGN.md §8) keeps KV rows in a shared page pool behind
per-slot block tables.  These kernels walk each row's live pages
(DESIGN.md §10): whole pages are staged from the pool into VMEM and the
contiguous ``(b, P·ps, h_kv, d)`` gather never exists.

Grid and walk:

  * the grid is a work list (:func:`_work_list`), one step per compute
    block of ``bp`` live pages of one row, rows in order, at least one
    step per row.  Its length is dynamic: the sum over rows of
    ``ceil(live pages / bp)``.  Table columns outside a row's live pages
    ``[first, end)`` are never read — ``end = min(ceil(lengths[row] /
    page_size), P)``, ``first`` the page of the sliding window's oldest
    position (0 without a window) — so the table's width costs nothing.
  * scalar prefetch: the cursors ``lengths`` (b,) (the number of valid
    KV rows, including the token scattered this tick) and, per step,
    the row, the position of the block's first key and the physical
    page of each of its ``bp`` slots.
  * pools are head-major, (num_pages, h_kv, page_size, d): one page of
    every KV head is one ``(h_kv, page_size, d)`` slab, staged by one
    BlockSpec'd input per slot, so the pipeline fetches the next block's
    pages while the current block computes.  A slot past the row's last
    live page keeps the page it staged last (masked off), so nothing is
    copied for it.  (A ``make_async_copy`` of one page from a pool left
    in HBM cannot be expressed for head_dim 64: the pool's layout pads
    the minor dimension to 128 lanes and Mosaic refuses any slice of
    it.)
  * block rule (:func:`block_pages`): ``ceil(128 / page_size)`` pages,
    so a block's keys fill the 128 lanes of a vector register, capped at
    the table width.  ``pages_per_step`` overrides it.
  * each step computes every KV head of its row and the ``group = heads
    / kv_heads`` query heads that share it against the staged block; the
    row's accumulators live in VMEM scratch from its first step to its
    last, which writes the output.

Masks are per-row and dynamic: a staged key at position ``p`` is
attended when ``p < min(lengths[row], P·page_size)`` and, with a window,
``p > lengths[row] - 1 - window``.  The single decode query is the
newest position, so causality is the cursor mask.  Masked keys are
removed with ``where`` (never multiplied by 0), so whatever a partly
filled block holds cannot reach the result.

Each arm keeps its own per-block body, in float32:

  * inhibitor (eq. 5, 7): keys are laid across lanes, ``(d, keys)``.
    Scores ``z`` are one ``(1, keys)`` row per query head; the
    inhibition terms ``sign(v)·relu(|v| − z)`` (signed) or ``relu(v −
    z)`` accumulate elementwise in ``(d, keys)`` scratch and are reduced
    over keys once per row.  This arm is VPU work.
  * Softmax: the online max/denominator recurrence, with ``(group, d)
    @ (d, keys)`` and ``(group, keys) @ (keys, d)`` on the MXU.

Decode is inference-only: no custom VJP (the wrappers in
:mod:`repro.kernels.ops` do not register one).

Validated in interpret mode (``pltpu.InterpretParams``, the TPU
interpreter: uninitialised VMEM reads as NaN) against float64 and gather
references (tests/test_paged_kernels.py).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Platforms this module's Pallas bodies lower *natively* on.  The walk
#: stages pages through ``pltpu.PrefetchScalarGridSpec`` scalar prefetch
#: (BlockSpec index maps reading prefetched page numbers) over a dynamic
#: grid, TPU/Mosaic features with no Triton equivalent — on any other
#: platform the body only runs in interpret mode, which must never be
#: picked over the XLA gather path.  A Triton rewrite of the walk would
#: extend this to ("tpu", "gpu") and the registry/planner pick it up
#: with no further wiring (kernels.ops.NATIVE_PLATFORMS).
LOWERS_ON = ("tpu",)
NEG_INF = -1e30
#: keys a compute block aims to hold: the lanes of one vector register
LANES = 128


def block_pages(page_size: int, table_width: int,
                pages_per_step: Optional[int] = None) -> int:
    """Pages per compute block: ``pages_per_step`` when given, else
    enough pages for a block's keys to fill :data:`LANES`; never more
    than the table holds."""
    bp = pages_per_step or -(-LANES // page_size)
    return max(1, min(bp, table_width))


def _decode_shapes(q, k_pool, block_tables, lengths):
    """Shared shape checks: (batch, heads, kv_heads, group, d)."""
    batch, n_q, heads, d = q.shape
    if n_q != 1:
        raise ValueError(f"paged decode kernels are single-query (n_q=1); "
                         f"got n_q={n_q} — prefill goes through the gather "
                         f"path")
    _, kv_heads, _, dk = k_pool.shape
    assert d == dk and heads % kv_heads == 0
    if block_tables.shape[0] != batch or lengths.shape != (batch,):
        raise ValueError(
            f"block_tables {block_tables.shape} / lengths {lengths.shape} "
            f"do not match batch={batch}")
    return batch, heads, kv_heads, heads // kv_heads, d


def _work_list(block_tables, lengths, *, bp: int, page_size: int,
               window: Optional[int]):
    """The grid's steps: one per block of ``bp`` live pages of a row,
    rows in order, at least one step per row.

    Returns ``(n_steps, rows, pos0, pages)``, padded to the static bound
    ``batch · ceil(P / bp)`` steps: step ``w`` computes row ``rows[w]``'s
    block whose first key sits at position ``pos0[w]``; ``pages[w·bp +
    i]`` is the physical page its slot ``i`` stages.  A slot past the
    row's last live page keeps the page it staged at its last live step
    (masked off by position), so the pipeline copies nothing for it.
    """
    batch, table_width = block_tables.shape
    # [first, end): the table columns holding a row's attended positions
    end = jnp.minimum((lengths + page_size - 1) // page_size, table_width)
    first = jnp.zeros_like(end)
    if window is not None:
        first = jnp.minimum(jnp.maximum(lengths - window, 0) // page_size,
                            end)
    n_blocks = jnp.maximum((end - first + bp - 1) // bp, 1)
    stop = jnp.cumsum(n_blocks)
    w = jnp.arange(batch * (-(-table_width // bp)), dtype=jnp.int32)
    rows = jnp.minimum(jnp.sum(w[:, None] >= stop[None, :], axis=1),
                       batch - 1).astype(jnp.int32)
    col0 = first[rows] + (w - (stop - n_blocks)[rows]) * bp
    col = col0[:, None] + jnp.arange(bp, dtype=jnp.int32)[None, :]
    live = (col < end[rows][:, None]) & (w < stop[-1])[:, None]
    page = block_tables[rows[:, None], jnp.minimum(col, table_width - 1)]
    # the step that last staged a live page in each slot
    last = jax.lax.cummax(jnp.where(live, w[:, None], 0), axis=0)
    pages = jnp.take_along_axis(page, last, axis=0)
    return (stop[-1], rows, (col0 * page_size).astype(jnp.int32),
            pages.reshape(-1).astype(jnp.int32))


def _paged_decode(q_arr, k_pool, v_pool, block_tables, lengths, *,
                  window: Optional[int], pages_per_step: Optional[int],
                  interpret: bool, arm_scratch, make_arm: Callable):
    """Shared launcher: the work-list grid, the page walk and the masks.

    ``q_arr`` is the arm's (batch, kv_heads, a, b) query layout; the
    output has its shape, in float32.  ``make_arm(q_ref, *arm_refs)``
    returns the arm's ``(init, block, finalize)``: ``block(load_k,
    load_v, mask, mask_col)`` consumes one staged block, where
    ``load_k(h, dtype=float32)`` returns head ``h``'s keys as
    (bp·page_size, d) and ``load_v`` its values, ``mask`` is the
    (1, keys) attendability and ``mask_col()`` the same as a (keys, 1)
    column; ``finalize(o_ref, n_keys)`` writes the row.
    """
    batch = q_arr.shape[0]
    _, kv_heads, page_size, d = k_pool.shape
    table_width = block_tables.shape[1]
    bp = block_pages(page_size, table_width, pages_per_step)
    keys = bp * page_size
    lengths = lengths.astype(jnp.int32)
    n_steps, rows, pos0, pages = _work_list(
        block_tables.astype(jnp.int32), lengths, bp=bp,
        page_size=page_size, window=window)

    def kernel(len_ref, rows_ref, pos0_ref, pages_ref, q_ref, *refs):
        k_pages, v_pages = refs[0:2 * bp:2], refs[1:2 * bp:2]
        o_ref, arm_refs = refs[2 * bp], refs[2 * bp + 1:]
        init, block, finalize = make_arm(q_ref, *arm_refs)
        w, n = pl.program_id(0), pl.num_programs(0)
        row = rows_ref[w]
        valid = len_ref[row]
        # positions past the table are never staged (their pages clamp)
        limit = jnp.minimum(valid, table_width * page_size)
        oldest = 0 if window is None else jnp.maximum(valid - window, 0)

        @pl.when((w == 0) | (rows_ref[jnp.maximum(w - 1, 0)] != row))
        def _():
            init()

        def mask(shape, axis):
            pos = pos0_ref[w] + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                         axis)
            return (pos < limit) & (pos >= oldest)

        def loader(pages):
            return lambda h, dtype=jnp.float32: jnp.concatenate(
                [p[0, h].astype(dtype) for p in pages], axis=0)

        block(loader(k_pages), loader(v_pages), mask((1, keys), 1),
              lambda: mask((keys, 1), 0))

        @pl.when((w == n - 1) | (rows_ref[jnp.minimum(w + 1, n - 1)] != row))
        def _():
            finalize(o_ref, jnp.maximum(limit - oldest, 0))

    def page_map(w, len_ref, rows_ref, pos0_ref, pages_ref, *, i):
        return (pages_ref[w * bp + i], 0, 0, 0)

    def row_map(w, len_ref, rows_ref, pos0_ref, pages_ref):
        return (rows_ref[w], 0, 0, 0)

    page_specs = []
    for i in range(bp):
        spec = pl.BlockSpec((1, kv_heads, page_size, d),
                            functools.partial(page_map, i=i))
        page_specs += [spec, spec]                      # k page i, v page i
    qo_spec = pl.BlockSpec((1,) + q_arr.shape[1:], row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[qo_spec] + page_specs,
        out_specs=qo_spec,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32)
                        for s in arm_scratch(keys)],
    )
    pools = [p for _ in range(bp) for p in (k_pool, v_pool)]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_arr.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(lengths, rows, pos0, pages, q_arr, *pools)


# ---------------------------------------------------------------------------
# paged flash-inhibitor (paper eq. 9 / eq. 10 streaming forms)
# ---------------------------------------------------------------------------

def _inhibitor_arm(q_ref, acc_ref, *, score_scale: float,
                   score_shift: float, signed: bool, normalize: bool):
    """Keys across lanes.  ``q_ref`` block (1, h_kv, d, group);
    ``acc_ref`` (h_kv, group, d, keys) sums each lane's inhibition
    terms over the row's blocks.

    The terms are eq. 7's, taken directly: ``relu(v − z)`` unsigned, and
    signed ``relu(v⁺ − z) − relu(v⁻ − z) = sign(v)·relu(|v| − z)``,
    which holds because ``z ≥ 0``.  They equal eq. 9/10's fused forms
    without their ``Σ v`` that the ``|·|`` sums nearly cancel.
    """
    kv_heads, group = acc_ref.shape[:2]

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(load_k, load_v, mask, _mask_col):
        for h in range(kv_heads):
            kt = load_k(h).T                                  # (d, keys)
            # masked keys: v = 0 and z = 0 make every term 0
            vt = jnp.where(mask, load_v(h).T, 0.0)
            if signed:
                mag, sign = jnp.abs(vt), jnp.where(vt < 0, -1.0, 1.0)
            qt = q_ref[0, h]                                  # (d, group)
            for g in range(group):
                # Z = relu(Σ_d |q − k| / γ − α)  (eq. 5 + shift)
                z = jnp.sum(jnp.abs(kt - qt[:, g:g + 1]), axis=0,
                            keepdims=True) * (1.0 / score_scale)
                if score_shift:
                    z = jnp.maximum(z - score_shift, 0.0)
                z = jnp.where(mask, z, 0.0)                   # (1, keys)
                if signed:
                    acc_ref[h, g] += sign * jnp.maximum(mag - z, 0.0)
                else:
                    acc_ref[h, g] += jnp.maximum(vt - z, 0.0)

    def finalize(o_ref, n_keys):
        denom = jnp.maximum(
            jnp.full((1, 1), n_keys, jnp.int32).astype(jnp.float32), 1.0)
        for h in range(kv_heads):
            for g in range(group):
                out = jnp.sum(acc_ref[h, g], axis=1, keepdims=True)
                if normalize:
                    out = out / denom
                o_ref[0, h, :, g:g + 1] = out                 # (d, 1)

    return init, block, finalize


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
    pages_per_step: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-native paged inhibitor decode. Returns (batch, 1, heads, d)."""
    batch, heads, kv_heads, group, d = _decode_shapes(
        q, k_pool, block_tables, lengths)
    scale = score_scale if score_scale is not None else math.sqrt(d)
    # head = kv_head * group + g; the arm wants each head's queries as
    # columns: (batch, kv_heads, d, group)
    qt = q.reshape(batch, kv_heads, group, d).transpose(0, 1, 3, 2)
    out = _paged_decode(
        qt.astype(jnp.float32), k_pool, v_pool, block_tables, lengths,
        window=window, pages_per_step=pages_per_step, interpret=interpret,
        arm_scratch=lambda keys: [(kv_heads, group, d, keys)],
        make_arm=functools.partial(
            _inhibitor_arm, score_scale=scale, score_shift=score_shift,
            signed=signed, normalize=normalize))
    return out.transpose(0, 1, 3, 2).reshape(batch, 1, heads, d).astype(
        q.dtype)


# ---------------------------------------------------------------------------
# paged flash attention (Softmax baseline, online recurrence)
# ---------------------------------------------------------------------------

def _attention_arm(q_ref, acc_ref, m_ref, l_ref, *, score_scale: float):
    """Keys down sublanes, matmuls on the MXU.  ``q_ref`` block
    (1, h_kv, group, d), in the dtype the scores are multiplied in; per
    KV head ``acc_ref`` (group, d), running max ``m_ref`` and
    denominator ``l_ref`` (group, 1)."""
    kv_heads = acc_ref.shape[0]

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def block(load_k, load_v, mask, mask_col):
        keep = mask_col()
        for h in range(kv_heads):
            q = q_ref[0, h]                                   # (group, d)
            v = jnp.where(keep, load_v(h), 0.0)               # (keys, d)
            s = jax.lax.dot_general(
                q, load_k(h, q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (1.0 / score_scale)
            s = jnp.where(mask, s, NEG_INF)                   # (group, keys)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    def finalize(o_ref, n_keys):
        del n_keys
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)

    return init, block, finalize


def paged_flash_attention_fwd(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    score_scale: Optional[float] = None,
    window: Optional[int] = None,
    pages_per_step: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-native paged Softmax decode. Returns (batch, 1, heads, d)."""
    batch, heads, kv_heads, group, d = _decode_shapes(
        q, k_pool, block_tables, lengths)
    scale = score_scale if score_scale is not None else math.sqrt(d)
    # bf16 queries against bf16 pages multiply in bf16: each product is
    # exact in the float32 accumulator, so nothing is lost
    dtype = q.dtype if q.dtype == k_pool.dtype else jnp.float32
    out = _paged_decode(
        q.reshape(batch, kv_heads, group, d).astype(dtype),
        k_pool, v_pool, block_tables, lengths, window=window,
        pages_per_step=pages_per_step, interpret=interpret,
        arm_scratch=lambda keys: [(kv_heads, group, d),
                                  (kv_heads, group, 1),
                                  (kv_heads, group, 1)],
        make_arm=functools.partial(_attention_arm, score_scale=scale))
    return out.reshape(batch, 1, heads, d).astype(q.dtype)
