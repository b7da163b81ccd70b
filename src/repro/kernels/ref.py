"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel test sweeps shapes/dtypes and asserts allclose against these.
They are thin reorderings of the core/nn reference implementations so that
the kernels and the model code share a single source of truth.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dotprod import dot_product_attention
from repro.core.inhibitor import (
    causal_mask,
    inhibitor_attention,
    sliding_window_mask,
)
from repro.nn.ssm import wkv6_scan_ref


def _mask_for(n_q: int, n_k: int, causal: bool, window: Optional[int]):
    # Kernel convention: query block positions start at 0 (training/prefill);
    # decode goes through the jnp cache path, not the kernel.
    if causal and window is not None:
        return sliding_window_mask(n_q, n_k, window)[None, None]
    if causal:
        return causal_mask(n_q, n_k)[None, None]
    if window is not None:
        return sliding_window_mask(n_q, n_k, window)[None, None]
    return None


def flash_inhibitor_ref(q, k, v, *, score_scale=None, score_shift=0.5,
                        signed=True, normalize=True, causal=True,
                        window=None):
    """Oracle for kernels.inhibitor.flash_inhibitor_fwd."""
    mask = _mask_for(q.shape[1], k.shape[1], causal, window)
    return inhibitor_attention(
        q, k, v, mask=mask, score_scale=score_scale,
        score_shift=score_shift, signed=signed, normalize=normalize)


def flash_attention_ref(q, k, v, *, score_scale=None, causal=True,
                        window=None):
    """Oracle for kernels.flash.flash_attention_fwd."""
    mask = _mask_for(q.shape[1], k.shape[1], causal, window)
    return dot_product_attention(q, k, v, mask=mask, score_scale=score_scale)


def wkv6_ref(r, k, v, w, u, state=None):
    """Oracle for kernels.rwkv6.wkv6_chunked (exact lax.scan recurrence)."""
    return wkv6_scan_ref(r, k, v, w, u, state)


def _gather_paged(k_pool, v_pool, block_tables):
    """(num_pages, h_kv, ps, d) pools + (b, P) tables -> contiguous
    (b, P*ps, h_kv, d) views — the gather the paged kernels replace."""
    def view(pool):
        t = pool[block_tables]                    # (b, P, h_kv, ps, d)
        b, npg, hk, ps, d = t.shape
        return t.transpose(0, 1, 3, 2, 4).reshape(b, npg * ps, hk, d)

    return view(k_pool), view(v_pool)


def _decode_mask(n_k: int, lengths, window: Optional[int]):
    """(b, 1, 1, n_k) attendability of each gathered position for the
    single decode query at position lengths[row]-1."""
    kj = jnp.arange(n_k)[None, :]
    m = kj < lengths[:, None]
    if window is not None:
        m = m & (kj > (lengths[:, None] - 1) - window)
    return m[:, None, None, :]


def paged_flash_inhibitor_ref(q, k_pool, v_pool, block_tables, lengths, *,
                              score_scale=None, score_shift=0.5, signed=True,
                              normalize=True, window=None):
    """Oracle for kernels.paged.paged_flash_inhibitor_fwd (gather + fused)."""
    kc, vc = _gather_paged(k_pool, v_pool, block_tables)
    mask = _decode_mask(kc.shape[1], lengths, window)
    return inhibitor_attention(
        q, kc.astype(q.dtype), vc.astype(q.dtype), mask=mask,
        score_scale=score_scale, score_shift=score_shift, signed=signed,
        normalize=normalize)


def paged_flash_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                              score_scale=None, window=None):
    """Oracle for kernels.paged.paged_flash_attention_fwd (gather + fused)."""
    kc, vc = _gather_paged(k_pool, v_pool, block_tables)
    mask = _decode_mask(kc.shape[1], lengths, window)
    return dot_product_attention(q, kc.astype(q.dtype), vc.astype(q.dtype),
                                 mask=mask, score_scale=score_scale)
