"""Decoder-only transformer LM covering the dense / MoE / hybrid families.

One homogeneous, `lax.scan`-able block per config: parameters are stacked
with a leading ("layers",) axis and the forward pass scans over them, so
the compiled HLO contains each layer's program once regardless of depth
(30–48 layers compile in seconds, and remat policy applies per layer).

Block (pre-norm):
    a   = token_mixer(norm1(x))        # attention, or attention ∥ mamba
    x   = x + a
    f   = ffn_or_moe(norm2(x))
    x   = x + f

The token mixer's attention mechanism — dot-product, the paper's
Inhibitor, or any other registered mechanism — is resolved through the
:mod:`repro.core.mechanism` registry (``cfg.attention.mechanism``, legacy
``cfg.attention.kind``), and the execution backend is chosen per shape by
its planner; the hybrid family (hymba) averages a parallel mamba branch
with the attention branch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import (
    AttentionConfig, KVCache, PagedKVCache, apply_attention, init_attention,
    init_kv_cache, init_paged_kv_cache)
from repro.distributed.sharding import constrain
from repro.nn import embedding as emb
from repro.nn import mlp as mlpnn
from repro.nn import moe as moenn
from repro.nn import norm as normnn
from repro.nn import ssm as ssmnn
from repro.nn.module import KeyGen, Param, fold_key


# ---------------------------------------------------------------------------
# Per-layer state (decode caches)
# ---------------------------------------------------------------------------

class LayerState(NamedTuple):
    """Decode-time state for ONE layer (stacked over layers in practice)."""
    kv: Optional[KVCache] = None          # attention cache
    ssm: Optional[jax.Array] = None       # mamba ssm state (b, c, n)
    conv: Optional[jax.Array] = None      # mamba conv carry (b, k-1, c)


# ---------------------------------------------------------------------------
# Norm dispatch
# ---------------------------------------------------------------------------

def _init_norm(cfg: ModelConfig, dtype):
    if cfg.norm == "rmsnorm":
        return normnn.init_rmsnorm(cfg.d_model, dtype=dtype)
    return normnn.init_layernorm(cfg.d_model, dtype=dtype)


def _apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rmsnorm":
        return normnn.apply_rmsnorm(p, x, eps=cfg.norm_eps)
    return normnn.apply_layernorm(p, x, eps=cfg.norm_eps)


def _init_ffn(key, cfg: ModelConfig, dtype):
    if cfg.mlp == "gated_silu":
        return mlpnn.init_gated_mlp(key, cfg.d_model, cfg.d_ff,
                                    use_bias=cfg.mlp_bias, dtype=dtype)
    return mlpnn.init_mlp(key, cfg.d_model, cfg.d_ff,
                          use_bias=cfg.mlp_bias, dtype=dtype)


def _apply_ffn(cfg: ModelConfig, p, x, cdt):
    if cfg.mlp == "gated_silu":
        return mlpnn.apply_gated_mlp(p, x, activation="silu",
                                     compute_dtype=cdt)
    act = "gelu" if cfg.mlp == "mlp_gelu" else "relu"
    return mlpnn.apply_mlp(p, x, activation=act, compute_dtype=cdt)


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig) -> dict:
    from repro.core.mechanism import get_mechanism, resolve_mechanism_name

    get_mechanism(resolve_mechanism_name(cfg.attention))  # fail fast
    kg = KeyGen(key)
    dtype = cfg.pdtype
    p = {
        "ln1": _init_norm(cfg, dtype),
        "attn": init_attention(kg("attn"), cfg.attention, cfg.d_model,
                               dtype=dtype),
        "ln2": _init_norm(cfg, dtype),
    }
    if cfg.family == "hybrid":
        assert cfg.ssm is not None and cfg.ssm.kind == "mamba"
        inner = cfg.ssm.inner_dim or 2 * cfg.d_model
        p["mamba"] = ssmnn.init_mamba(
            kg("mamba"), cfg.d_model, inner, state_dim=cfg.ssm.state_dim,
            conv_dim=cfg.ssm.conv_dim, dt_rank=cfg.ssm.dt_rank, dtype=dtype)
        # learned per-branch output scales (hymba fuses mean of normed outs)
        p["branch_scale"] = Param(jnp.ones((2,), dtype), (None,))
    if cfg.moe is not None:
        p["moe"] = moenn.init_moe(
            kg("moe"), cfg.d_model, cfg.moe.expert_hidden_dim,
            cfg.moe.effective_experts,
            shared_hidden_dim=cfg.moe.shared_hidden_dim,
            shared_gate=cfg.moe.shared_gate, dtype=dtype)
    else:
        p["ffn"] = _init_ffn(kg("ffn"), cfg, dtype)
    return p


def apply_block(params: dict, cfg: ModelConfig, x: jax.Array, *,
                positions=None, state: Optional[LayerState] = None,
                attn_mask=None):
    """Returns (x, new_state, aux_losses (2,))."""
    cdt = cfg.cdtype
    h = _apply_norm(cfg, params["ln1"], x)
    h = constrain(h, "batch", "seq_sp", "embed")

    kv = state.kv if state is not None else None
    a, new_kv = apply_attention(params["attn"], cfg.attention, h,
                                positions=positions, cache=kv,
                                attn_mask=attn_mask, compute_dtype=cdt)

    new_ssm = new_conv = None
    if cfg.family == "hybrid":
        m, (new_ssm, new_conv) = ssmnn.apply_mamba(
            params["mamba"], h, state_dim=cfg.ssm.state_dim,
            ssm_state=state.ssm if state is not None else None,
            conv_state=state.conv if state is not None else None,
            compute_dtype=cdt)
        s = params["branch_scale"].astype(cdt)
        a = 0.5 * (s[0] * a + s[1] * m)

    x = x + a
    x = constrain(x, "batch", "seq_sp", "embed")

    h2 = _apply_norm(cfg, params["ln2"], x)
    aux = jnp.zeros((2,), jnp.float32)
    if cfg.moe is not None:
        f, moe_aux = moenn.apply_moe(
            params["moe"], h2, top_k=cfg.moe.top_k,
            capacity_factor=cfg.moe.capacity_factor,
            normalize_topk=cfg.moe.normalize_topk, compute_dtype=cdt)
        aux = jnp.stack([moe_aux.load_balance_loss, moe_aux.router_z_loss])
    else:
        f = _apply_ffn(cfg, params["ffn"], h2, cdt)
    x = x + f
    x = constrain(x, "batch", "seq_sp", "embed")

    new_state = LayerState(kv=new_kv, ssm=new_ssm, conv=new_conv)
    return x, new_state, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> dict:
    kg = KeyGen(key)
    dtype = cfg.pdtype

    # stacked block params: vmap init over per-layer keys -> leading
    # ("layers",) axis on every leaf
    layer_keys = jax.random.split(kg("blocks"), cfg.num_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
    blocks = jax.tree.map(
        lambda p: Param(p.value, ("layers",) + p.axes) if isinstance(p, Param)
        else p, blocks, is_leaf=lambda p: isinstance(p, Param))

    p = {
        "embed": emb.init_embedding(kg("embed"), cfg.vocab_size, cfg.d_model,
                                    dtype=dtype),
        "blocks": blocks,
        "final_norm": _init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        from repro.nn.linear import init_dense
        p["lm_head"] = init_dense(kg("lm_head"), (cfg.d_model,),
                                  (cfg.vocab_size,), ("embed",), ("vocab",),
                                  dtype=dtype)
    if cfg.frontend is not None:
        from repro.nn.linear import init_dense
        p["frontend_proj"] = init_dense(
            kg("frontend_proj"), (cfg.frontend.embed_dim,), (cfg.d_model,),
            (None,), ("embed",), use_bias=True, dtype=dtype)
    return p


def _scan_blocks(params, cfg: ModelConfig, x, positions, states=None,
                 attn_mask=None):
    """Scan apply_block over stacked layer params (and optional states)."""

    def body(carry, layer_in):
        h = carry
        if states is None:
            lp = layer_in
            st = None
        else:
            lp, st = layer_in
        h, new_state, aux = apply_block(lp, cfg, h, positions=positions,
                                        state=st, attn_mask=attn_mask)
        return h, (new_state if states is not None else None, aux)

    body_fn = body
    if cfg.remat == "full":
        body_fn = jax.checkpoint(body)
    elif cfg.remat == "dots":
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    xs = params["blocks"] if states is None else (params["blocks"], states)
    if cfg.unroll:
        x, (new_states, auxs) = unrolled_scan(body_fn, x, xs, cfg.num_layers)
    else:
        x, (new_states, auxs) = jax.lax.scan(body_fn, x, xs)
    return x, new_states, jnp.sum(auxs, axis=0)


def unrolled_scan(body_fn, carry, xs, length: int):
    """Python-loop drop-in for lax.scan (dry-run cost extraction)."""
    ys = []
    for i in range(length):
        layer_in = jax.tree.map(lambda t: t[i], xs)
        carry, y = body_fn(carry, layer_in)
        ys.append(y)
    stacked = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    return carry, stacked


def lm_forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
               positions: Optional[jax.Array] = None,
               extra_embeds: Optional[jax.Array] = None):
    """Training / prefill forward. tokens: (b, s) int32 -> logits (b, s, V).

    ``extra_embeds``: (b, n_extra, frontend_dim) modality-stub embeddings
    prepended to the token embeddings (VLM/audio families).
    Returns (logits, aux(2,)).
    """
    cdt = cfg.cdtype
    x = emb.apply_embedding(params["embed"], tokens, compute_dtype=cdt)
    if extra_embeds is not None:
        from repro.nn.linear import apply_dense
        fe = apply_dense(params["frontend_proj"], extra_embeds.astype(cdt),
                         1, cdt)
        x = jnp.concatenate([fe, x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = constrain(x, "batch", "seq_sp", "embed")
    x, _, aux = _scan_blocks(params, cfg, x, positions)
    x = _apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = emb.attend_logits(params["embed"], x, compute_dtype=cdt)
    else:
        from repro.nn.linear import apply_dense
        logits = apply_dense(params["lm_head"], x, 1, cdt)
    logits = constrain(logits, "batch", None, "vocab")
    return logits, aux


# ---------------------------------------------------------------------------
# Lane-parameterized forward (float / int / fhe_sim execution of a PTQ'd LM)
# ---------------------------------------------------------------------------

def _lane_causal_mask(cfg: ModelConfig, n: int):
    """Cleartext attention structure for the lane forward (masks are
    public; masked pairs are excluded from the combining sums).  Shares
    the single causal/window predicate with ``_build_mask``."""
    import numpy as np

    from repro.core.attention import structural_mask_predicate

    a = cfg.attention
    m = structural_mask_predicate(a.causal, a.sliding_window,
                                  np.arange(n)[:, None],
                                  np.arange(n)[None, :])
    return None if m is None else m[None, None]


def _lane_attention_kwargs(mech, qlm):
    """Integer-domain hyper-parameters for a mechanism's lane_fn, filtered
    by its signature (mechanisms accept different shift sets)."""
    import inspect

    full = {
        "gamma_shift": qlm.gamma_shift,
        "alpha_q": qlm.alpha_q,
        "signed": bool(mech.param_overrides.get("signed", False)),
        "normalize": qlm.cfg.attention.normalize,
        "scale_shift": qlm.scale_shift,
        "frac_bits": qlm.ptq.softmax_frac,
        "exp_clip": qlm.ptq.exp_clip,
    }
    accepted = inspect.signature(mech.lane_fn).parameters
    return {k: v for k, v in full.items() if k in accepted}


def apply_block_lane(qblock: dict, qlm, lane, x, *, mask=None,
                     layer_tag: str = "L0"):
    """One pre-norm block on a lane: norm → attention (via the mechanism
    registry's lane_fn) → residual → norm → MLP → residual.  Costs land
    in per-sublayer scopes on the ``fhe_sim`` lane."""
    from repro.core.mechanism import get_mechanism, resolve_mechanism_name
    from repro.nn.lane_layers import lane_linear, lane_mlp, lane_norm
    from repro.quant.int_attention import lane_attention_heads

    cfg, ptq = qlm.cfg, qlm.ptq
    a = cfg.attention
    sub_mean = cfg.norm == "layernorm"
    mech = get_mechanism(resolve_mechanism_name(a))
    if mech.lane_fn is None:
        raise ValueError(f"mechanism {mech.name!r} has no lane_fn — "
                         "it cannot run on integer/encrypted lanes")

    with lane.scope(f"{layer_tag}.ln1"):
        h = lane_norm(lane, x, qblock["ln1"], ptq=ptq,
                      subtract_mean=sub_mean)
    b, n = lane.shape(h)[0], lane.shape(h)[1]
    with lane.scope(f"{layer_tag}.qkv_proj"):
        q = lane.reshape(lane_linear(lane, h, qblock["wq"], ptq=ptq),
                         (b, n, a.num_heads, a.head_dim))
        k = lane.reshape(lane_linear(lane, h, qblock["wk"], ptq=ptq),
                         (b, n, a.num_kv_heads, a.head_dim))
        v = lane.reshape(lane_linear(lane, h, qblock["wv"], ptq=ptq),
                         (b, n, a.num_kv_heads, a.head_dim))
    with lane.scope(f"{layer_tag}.attn"):
        o = lane_attention_heads(lane, mech.lane_fn, q, k, v, mask=mask,
                                 **_lane_attention_kwargs(mech, qlm))
    with lane.scope(f"{layer_tag}.out_proj"):
        o = lane_linear(lane, lane.reshape(
            o, (b, n, a.num_heads * a.head_dim)), qblock["wo"], ptq=ptq)
        x = lane.add(x, o)
    with lane.scope(f"{layer_tag}.ln2"):
        h2 = lane_norm(lane, x, qblock["ln2"], ptq=ptq,
                       subtract_mean=sub_mean)
    with lane.scope(f"{layer_tag}.mlp"):
        act = "gelu" if cfg.mlp == "mlp_gelu" else "relu"
        f = lane_mlp(lane, h2, qblock["wi"], qblock["wo_mlp"], ptq=ptq,
                     activation=act)
        x = lane.add(x, f)
    return x


def lm_forward_lane(qlm, lane, tokens):
    """End-to-end lane forward of a PTQ'd LM: tokens (b, s) cleartext →
    logits handle (b, s, V) on ``lane``.

    On ``fhe_sim`` this is the paper's headline scenario — the whole
    block runs under the TFHE cost model, bit-exact with the ``int``
    lane, with per-layer PBS/add/cmul/bit-width scopes accumulated on
    ``lane.ctx`` (see examples/fhe_inference.py).

    On the ``interval`` lane (:func:`repro.analysis.analyze_qlm`) the
    same call is the whole-model *static analysis*: ``tokens`` supplies
    shape only (embedding bounds span the vocabulary), and the trace
    proves worst-case widths and cmul counts for every input.
    """
    from repro.nn.lane_layers import lane_embed, lane_logits

    cfg = qlm.cfg
    with lane.scope("embed"):
        x = lane_embed(lane, qlm.embed, tokens)
    mask = _lane_causal_mask(cfg, lane.shape(x)[1])
    for i, qblock in enumerate(qlm.blocks):
        x = apply_block_lane(qblock, qlm, lane, x, mask=mask,
                             layer_tag=f"L{i}")
    with lane.scope("head"):
        return lane_logits(lane, x, qlm.final_norm, qlm.lm_head,
                           ptq=qlm.ptq,
                           subtract_mean=cfg.norm == "layernorm")


def fused_gather_applies(cfg: ModelConfig, kv, n_q: int) -> bool:
    """Would :func:`lm_step` hoist the all-layer page gather for this
    paged state?  (DESIGN.md §14.)

    True exactly when the per-layer planner would pick the host-gather
    ``paged`` backend with nothing forced: a forced backend
    (``cfg.attention.backend``) or the ``use_kernel`` shim keeps the
    per-layer path (the escape hatch parity tests rely on), and a
    platform whose planner prefers the block-table-native kernel
    (``paged_pallas`` on TPU single-query decode) keeps the kernel.
    """
    from repro.core.mechanism import AttnShapes, plan_attention

    if not isinstance(kv, PagedKVCache):
        return False
    a = cfg.attention
    if a.backend is not None or a.use_kernel:
        return False
    ps = kv.k.shape[3]
    shapes = AttnShapes(
        batch=kv.block_tables.shape[1], n_q=n_q,
        n_k=kv.block_tables.shape[2] * ps,
        num_heads=a.num_heads, num_kv_heads=kv.k.shape[2],
        head_dim=a.head_dim, dtype=cfg.cdtype,
        has_explicit_mask=False, is_cross=False, has_cache=True,
        scalar_cursor=False, paged=True)
    try:
        plan = plan_attention(a, shapes)
    except ValueError:
        return False
    return plan.backend == "paged"


def _gather_paged_view(kv: PagedKVCache) -> KVCache:
    """ONE whole-model page gather: stacked pools (L, pages, hk, ps, d)
    → contiguous logical view (L, b, P·ps, hk, d) for every layer.

    ``init_states`` broadcasts a single cache over layers and the engine
    uploads one host table broadcast the same way (``_flush_tables``), so
    ``block_tables[0]`` is authoritative for all L layers — the gather
    reads the table once instead of re-walking it per layer inside the
    scan.  The (page, offset) index pair addresses the pool directly, so
    no (b, P, ps, …) → (b, P·ps, …) reshape of the gathered data is ever
    materialized."""
    tables = kv.block_tables[0]                       # (b, P), layer-shared
    ps = kv.k.shape[3]
    page_idx = jnp.repeat(tables, ps, axis=1)         # (b, N): tables[b, j//ps]
    off_idx = jnp.tile(jnp.arange(ps, dtype=tables.dtype),
                       tables.shape[1])[None]         # (1, N): j % ps
    # the page and offset indices straddle the head axis, so numpy
    # indexing puts the index dims first: (b, N, L, hk, d)
    kc = kv.k[:, page_idx, :, off_idx].transpose(2, 0, 1, 3, 4)
    vc = kv.v[:, page_idx, :, off_idx].transpose(2, 0, 1, 3, 4)
    return KVCache(kc, vc, kv.length)


def _scatter_paged_rows(kv: PagedKVCache, view: KVCache,
                        n_q: int) -> PagedKVCache:
    """Write the ``n_q`` rows each layer appended to the logical view
    back into the page pool (the inverse of the hoisted gather).  Rows of
    inactive slots land on trash page 0 exactly as the per-layer scatter
    did — duplicate trash-page writes are don't-care by design."""
    tables = kv.block_tables[0]
    ps = kv.k.shape[3]
    rows = jnp.arange(tables.shape[0])[:, None]                    # (b, 1)
    pos = kv.length[0][:, None] + jnp.arange(n_q)[None]            # (b, t)
    pages = tables[rows, pos // ps]
    offs = pos % ps
    # [:, pages, :, offs] addresses (b, t, L, hk, d) — see the gather
    k_pool = kv.k.at[:, pages, :, offs].set(
        view.k[:, rows, pos].transpose(1, 2, 0, 3, 4))
    v_pool = kv.v.at[:, pages, :, offs].set(
        view.v[:, rows, pos].transpose(1, 2, 0, 3, 4))
    return PagedKVCache(k_pool, v_pool, kv.block_tables, view.length)


def init_states(cfg: ModelConfig, batch: int, max_len: int, *,
                per_slot: bool = False, paged: bool = False,
                page_size: int = 16,
                num_pages: Optional[int] = None) -> LayerState:
    """Stacked (num_layers-leading) decode state for the LM.

    ``per_slot``: per-batch-row cache cursors (ragged continuous batching).
    ``paged``: back the KV cache with a shared page pool + block tables
    (serve.kvcache.PagedAllocator owns the host-side accounting); cursors
    are always per-slot in that layout.
    """
    a = cfg.attention
    if paged:
        kv = init_paged_kv_cache(batch, max_len, a.num_kv_heads, a.head_dim,
                                 dtype=cfg.cdtype, page_size=page_size,
                                 num_pages=num_pages)
        kv = jax.tree.map(lambda t: jnp.broadcast_to(
            t[None], (cfg.num_layers,) + t.shape), kv)
        kv = PagedKVCache(kv.k, kv.v, kv.block_tables, kv.length)
    else:
        kv = init_kv_cache(batch, max_len, a.num_kv_heads, a.head_dim,
                           dtype=cfg.cdtype, per_slot=per_slot)
        kv = jax.tree.map(lambda t: jnp.broadcast_to(
            t[None], (cfg.num_layers,) + t.shape), kv)
        kv = KVCache(kv.k, kv.v, kv.length)
    ssm = conv = None
    if cfg.family == "hybrid":
        inner = cfg.ssm.inner_dim or 2 * cfg.d_model
        ssm = jnp.zeros((cfg.num_layers, batch, inner, cfg.ssm.state_dim),
                        jnp.float32)
        conv = jnp.zeros((cfg.num_layers, batch, cfg.ssm.conv_dim - 1, inner),
                         cfg.cdtype)
    return LayerState(kv=kv, ssm=ssm, conv=conv)


def lm_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
            states: LayerState):
    """Decode step: tokens (b, t) appended at states.kv.length.

    Returns (logits (b, t, V), new_states)."""
    cdt = cfg.cdtype
    x = emb.apply_embedding(params["embed"], tokens, compute_dtype=cdt)
    b, t, _ = x.shape
    # states are layer-stacked: kv.length is (L,) shared or (L, b) ragged.
    # positions=None lets each layer derive RoPE positions from its cursor.
    st = states
    if st.kv.length.ndim == 0:
        st = st._replace(kv=KVCache(
            st.kv.k, st.kv.v,
            jnp.broadcast_to(st.kv.length, (cfg.num_layers,))))
    if fused_gather_applies(cfg, st.kv, t):
        # whole-model fused gather (DESIGN.md §14): gather the paged
        # pools into one contiguous logical view up front, run every
        # layer's attention on its slice via the plain masked ``fused``
        # backend (bit-exact with the per-layer gather: identical
        # operands, identical mask), then scatter the appended rows back
        # into the pool once.  XLA sees one batched gather + one scatter
        # instead of L table walks per step.
        pool = st.kv
        run_cfg = dataclasses.replace(
            cfg, attention=dataclasses.replace(cfg.attention,
                                               backend="fused"))
        st = st._replace(kv=_gather_paged_view(pool))
        x, new_states, _ = _scan_blocks(params, run_cfg, x, None, states=st)
        new_states = new_states._replace(
            kv=_scatter_paged_rows(pool, new_states.kv, t))
    else:
        x, new_states, _ = _scan_blocks(params, cfg, x, None, states=st)
    x = _apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = emb.attend_logits(params["embed"], x, compute_dtype=cdt)
    else:
        from repro.nn.linear import apply_dense
        logits = apply_dense(params["lm_head"], x, 1, cdt)
    return logits, new_states
