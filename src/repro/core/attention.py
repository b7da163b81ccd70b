"""Unified multi-head attention layer with swappable score mechanism.

The mechanism (``"dotprod"`` | ``"inhibitor"`` | ``"inhibitor_unsigned"``
| anything else registered) and the execution backend are both resolved
through :mod:`repro.core.mechanism`: ``plan_attention(cfg, shapes)``
returns an inspectable :class:`~repro.core.mechanism.ExecutionPlan` and
``apply_attention`` executes it — no string ladders or inline shape
heuristics live here (DESIGN.md §7).

The projection layout (fused QKV per-head, GQA, optional QKV bias, RoPE) is
shared across mechanisms so the paper's technique is a one-line config swap
on every architecture in :mod:`repro.configs`.

Decode support: a :class:`KVCache` carries (k, v, length); ``apply`` with
``cache`` set appends the new keys/values and attends over the valid prefix.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.mechanism import (
    DEFAULT_BLOCKED_THRESHOLD, DEFAULT_CHUNKED_THRESHOLD,
    MASK_FREE_BACKENDS, AttnShapes, PagedLayout, Structural, execute_plan,
    get_mechanism, plan_attention)
from repro.nn.linear import apply_dense, init_dense
from repro.nn.module import KeyGen


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    kind: Optional[str] = None      # DEPRECATED mechanism name (warns
                                    # once); set ``mechanism`` instead
    num_heads: int = 8
    num_kv_heads: int = 8
    head_dim: int = 64
    qkv_bias: bool = False
    out_bias: bool = False
    use_rope: bool = True
    rope_base: float = 10000.0
    rope_pct: float = 1.0           # fraction of head_dim rotated (stablelm)
    score_shift: float = 0.5        # inhibitor α (paper: 0.5)
    score_scale: Optional[float] = None  # default √head_dim (paper γ)
    normalize: bool = True          # key-count normalization (DESIGN.md §2)
    sliding_window: Optional[int] = None
    causal: bool = True
    mechanism: Optional[str] = None  # registry name; None -> ``kind``
    backend: Optional[str] = None   # force a backend; None = planner auto
    use_kernel: bool = False        # DEPRECATED: shim for backend="pallas"
    kv_chunk: int = 256             # chunk size for streaming/blocked forms
    # Pallas kernel block-size overrides (None = the kernel registry's
    # tuned/default selection — repro.kernels.ops, DESIGN.md §10)
    kernel_block_q: Optional[int] = None
    kernel_block_k: Optional[int] = None
    kernel_sub_k: Optional[int] = None
    kernel_pages_per_step: Optional[int] = None
    # planner thresholds (single source of truth: core.mechanism defaults)
    chunked_threshold: int = DEFAULT_CHUNKED_THRESHOLD   # n_k > this ->
                                                         # streaming form
    blocked_threshold: int = DEFAULT_BLOCKED_THRESHOLD   # n_q·n_k ≥ this ->
                                                         # mask-free paths


class KVCache(NamedTuple):
    k: jax.Array        # (b, max_len, h_kv, d)
    v: jax.Array        # (b, max_len, h_kv, d)
    length: jax.Array   # () int32 shared cursor, or (b,) per-slot cursors
                        # (ragged continuous batching — serve.engine)


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, *, per_slot: bool = False) -> KVCache:
    shape = (batch, max_len, num_kv_heads, head_dim)
    length = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), length)


class PagedKVCache(NamedTuple):
    """Paged decode cache: KV rows live in a shared pool of fixed-size
    pages instead of per-row ``max_len`` strides (serve.kvcache owns the
    host-side page accounting; this is the device half).

    New tokens are *scattered* to ``block_tables[row, pos // page_size]``
    at offset ``pos % page_size``; attention *gathers* each row's pages
    back into a logically contiguous view (the ``paged`` backend in
    core.mechanism).  Physical page 0 is the trash page — unmapped table
    entries point there, so inactive batch rows in a static-shape decode
    step scatter harmlessly.

    Layer-stacked decode states broadcast ONE table over the leading
    layer axis (``block_tables[0]`` is authoritative for every layer),
    which is what lets models/transformer.lm_step hoist a single
    whole-model page gather out of the layer scan instead of walking the
    table per layer (DESIGN.md §14).

    Pages are head-major: one KV head's rows of one page form a
    contiguous ``(page_size, d)`` tile, which is the block the paged
    decode kernels stage per grid step (kernels/paged.py).  A TPU block
    must span the full extent of, or a multiple of the (8, 128) tile in,
    its last two dims — a token-major ``(…, page_size, h_kv, d)`` page
    cut to one head is refused by the chip's compiler.
    """
    k: jax.Array            # (num_pages, h_kv, page_size, d) pool
    v: jax.Array            # (num_pages, h_kv, page_size, d) pool
    block_tables: jax.Array  # (b, pages_per_slot) int32
    length: jax.Array       # (b,) int32 per-slot cursors


def init_paged_kv_cache(batch: int, max_len: int, num_kv_heads: int,
                        head_dim: int, dtype=jnp.bfloat16, *,
                        page_size: int = 16,
                        num_pages: Optional[int] = None) -> PagedKVCache:
    pages_per_slot = -(-max_len // page_size)
    if num_pages is None:
        num_pages = batch * pages_per_slot + 1      # +1: trash page 0
    shape = (num_pages, num_kv_heads, page_size, head_dim)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                        jnp.zeros((batch, pages_per_slot), jnp.int32),
                        jnp.zeros((batch,), jnp.int32))


def init_attention(key, cfg: AttentionConfig, embed_dim: int, *,
                   dtype=jnp.float32) -> dict:
    kg = KeyGen(key)
    h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": init_dense(kg("wq"), (embed_dim,), (h, d), ("embed",),
                         ("heads", "head_dim"), use_bias=cfg.qkv_bias,
                         dtype=dtype),
        "wk": init_dense(kg("wk"), (embed_dim,), (hk, d), ("embed",),
                         ("kv_heads", "head_dim"), use_bias=cfg.qkv_bias,
                         dtype=dtype),
        "wv": init_dense(kg("wv"), (embed_dim,), (hk, d), ("embed",),
                         ("kv_heads", "head_dim"), use_bias=cfg.qkv_bias,
                         dtype=dtype),
        "wo": init_dense(kg("wo"), (h, d), (embed_dim,),
                         ("heads", "head_dim"), ("embed",),
                         use_bias=cfg.out_bias, dtype=dtype),
    }


def structural_mask_predicate(causal: bool, window, qi, kj):
    """Attendability of (query index ``qi``, key index ``kj``) under the
    causal/sliding-window structure — the shared definition of the
    window-implies-causal semantics for every mask-building path
    (``_build_mask``, the blocked backend's chunk masks, the lane
    forward's cleartext masks); the Pallas kernels keep an in-kernel
    copy for lowering locality, locked against this one by
    tests/test_window_semantics.py.  Works on numpy and jnp index arrays
    alike.  Returns None when unstructured (attend all-to-all)."""
    masks = []
    if causal:
        masks.append(kj <= qi)
    if window is not None:
        masks.append((kj > qi - window) & (kj <= qi))
    if not masks:
        return None
    m = masks[0]
    for extra in masks[1:]:
        m = m & extra
    return m


def _build_mask(cfg: AttentionConfig, n_q: int, n_k: int, q_offset,
                kv_valid_len=None) -> Optional[jax.Array]:
    """Boolean (b|1, 1, n_q, n_k) mask combining causality, sliding window
    and KV-cache validity. ``q_offset`` / ``kv_valid_len`` may be scalars
    (shared cursor) or (b,) vectors (ragged continuous batching)."""
    masks = []
    qoff = jnp.asarray(q_offset)
    if qoff.ndim == 0:
        qoff = qoff[None]
    qi = qoff[:, None, None] + jnp.arange(n_q)[None, :, None]  # (b|1, nq, 1)
    kj = jnp.arange(n_k)[None, None, :]                        # (1, 1, nk)
    structural = structural_mask_predicate(cfg.causal, cfg.sliding_window,
                                           qi, kj)
    if structural is not None:
        masks.append(structural)
    if kv_valid_len is not None:
        kv = jnp.asarray(kv_valid_len)
        if kv.ndim == 0:
            kv = kv[None]
        masks.append(jnp.broadcast_to(kj < kv[:, None, None],
                                      (kv.shape[0], n_q, n_k)))
    if not masks:
        return None
    m = masks[0]
    for extra in masks[1:]:
        m = m & extra
    return m[:, None]


def apply_attention(
    params: dict,
    cfg: AttentionConfig,
    x: jax.Array,
    *,
    x_kv: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    attn_mask: Optional[jax.Array] = None,
    compute_dtype=None,
):
    """Attention over ``x`` (self) or ``x_kv`` (cross). Returns (y, cache').

    x: (b, n_q, embed). positions: (b, n_q) absolute positions for RoPE
    (defaults to arange, or cache.length + arange when decoding).
    """
    from repro.nn.rotary import apply_rope

    cdt = compute_dtype or x.dtype
    b, n_q, _ = x.shape
    src = x if x_kv is None else x_kv

    q = apply_dense(params["wq"], x, 1, cdt)          # (b, n_q, h, d)
    k = apply_dense(params["wk"], src, 1, cdt)        # (b, n_kv, hk, d)
    v = apply_dense(params["wv"], src, 1, cdt)

    if positions is None:
        offset = cache.length if cache is not None else 0
        off = jnp.asarray(offset)
        if off.ndim == 1:                       # per-slot cursors (b,)
            positions = off[:, None] + jnp.arange(n_q)[None, :]
        else:
            positions = jnp.arange(n_q)[None, :] + off
        positions = jnp.broadcast_to(positions, (b, n_q))

    if cfg.use_rope and x_kv is None:
        if cfg.rope_pct >= 1.0:
            q = apply_rope(q, positions, base=cfg.rope_base)
            k = apply_rope(k, positions, base=cfg.rope_base)
        else:
            rd = int(cfg.head_dim * cfg.rope_pct)
            rd -= rd % 2
            q = jnp.concatenate(
                [apply_rope(q[..., :rd], positions, base=cfg.rope_base),
                 q[..., rd:]], axis=-1)
            k = jnp.concatenate(
                [apply_rope(k[..., :rd], positions, base=cfg.rope_base),
                 k[..., rd:]], axis=-1)

    new_cache = None
    kv_valid_len = None
    paged_layout = None
    if isinstance(cache, PagedKVCache):
        # scatter new k/v into the block-table pages at the cursor(s);
        # the 'paged' backend gathers the pages back per row
        ps = cache.k.shape[2]
        pos = cache.length[:, None] + jnp.arange(n_q)[None, :]     # (b, n_q)
        rows = jnp.arange(b)[:, None]
        pages = cache.block_tables[rows, pos // ps]                # (b, n_q)
        offs = pos % ps
        # head-major pool: [pages, :, offs] addresses (b, n_q, h_kv, d)
        k_pool = cache.k.at[pages, :, offs].set(k.astype(cache.k.dtype))
        v_pool = cache.v.at[pages, :, offs].set(v.astype(cache.v.dtype))
        new_cache = PagedKVCache(k_pool, v_pool, cache.block_tables,
                                 cache.length + n_q)
        k, v = k_pool.astype(cdt), v_pool.astype(cdt)
        kv_valid_len = cache.length + n_q
        n_k = cache.block_tables.shape[1] * ps      # gathered logical view
        paged_layout = PagedLayout(cache.block_tables, ps)
    elif cache is not None:
        # append new k/v at the cache cursor(s), attend over the buffer
        if cache.length.ndim == 1:              # ragged: per-slot cursors
            upd = jax.vmap(
                lambda buf, new, off: jax.lax.dynamic_update_slice(
                    buf, new, (off, 0, 0)))
            k_buf = upd(cache.k, k.astype(cache.k.dtype), cache.length)
            v_buf = upd(cache.v, v.astype(cache.v.dtype), cache.length)
        else:
            k_buf = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, cache.length, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, cache.length, 0, 0))
        new_cache = KVCache(k_buf, v_buf, cache.length + n_q)
        k, v = k_buf.astype(cdt), v_buf.astype(cdt)
        kv_valid_len = cache.length + n_q

    if paged_layout is None:
        n_k = k.shape[1]
    q_offset = cache.length if cache is not None else 0
    scalar_cursor = jnp.asarray(q_offset).ndim == 0

    # Mechanism AND backend come exclusively from the registry/planner —
    # the plan is inspectable up front via plan_attention(cfg, shapes).
    shapes = AttnShapes(
        batch=b, n_q=n_q, n_k=n_k, num_heads=cfg.num_heads,
        num_kv_heads=k.shape[2], head_dim=cfg.head_dim, dtype=q.dtype,
        has_explicit_mask=attn_mask is not None, is_cross=x_kv is not None,
        has_cache=cache is not None, scalar_cursor=bool(scalar_cursor),
        paged=paged_layout is not None)
    plan = plan_attention(cfg, shapes)
    mech = get_mechanism(plan.mechanism)
    mech_params = mech.make_params(
        score_scale=cfg.score_scale, score_shift=cfg.score_shift,
        normalize=cfg.normalize, kv_chunk=cfg.kv_chunk,
        kernel_block_q=cfg.kernel_block_q, kernel_block_k=cfg.kernel_block_k,
        kernel_sub_k=cfg.kernel_sub_k,
        kernel_pages_per_step=cfg.kernel_pages_per_step)

    if plan.backend in MASK_FREE_BACKENDS:
        # blocked/pallas/paged_pallas compute causality/window/valid-length
        # from indices inside their loops — no (n_q, n_k) mask array in HBM
        structural = Structural(causal=cfg.causal, window=cfg.sliding_window,
                                q_offset=q_offset, kv_valid_len=kv_valid_len)
        out = execute_plan(plan, q, k, v, params=mech_params,
                           structural=structural, paged=paged_layout)
    else:
        mask = attn_mask
        if mask is None and x_kv is None:
            mask = _build_mask(cfg, n_q, n_k, q_offset, kv_valid_len)
        elif mask is None and x_kv is not None and kv_valid_len is not None:
            kvl = jnp.asarray(kv_valid_len)
            if kvl.ndim == 1:
                mask = (jnp.arange(n_k)[None, :] < kvl[:, None])[:, None,
                                                                 None]
            else:
                mask = (jnp.arange(n_k)[None, :] < kvl)[None, None, None]
        out = execute_plan(plan, q, k, v, mask=mask, params=mech_params,
                           paged=paged_layout)

    y = apply_dense(params["wo"], out, 2, cdt)        # out: (b, n_q, h, d)
    return y, new_cache
