"""Operations and bytes of the model's work, from the configuration's
shapes alone (``bench/configs/<config>.json``, Hugging Face key names).

Nothing here looks at how the program implements the work: no table
bucket width, no kernel grid, no padding.  The same work is counted
whatever computes it.

Sparse experts use Qwen1.5-MoE's keys: ``num_experts`` (the experts held
on this chip; listed in ``reduced`` when cut), ``num_experts_per_tok``,
``moe_intermediate_size``, ``shared_expert_intermediate_size`` (0 or
absent: no shared expert), ``mlp_only_layers`` and
``decoder_sparse_step`` (which layers stay dense), and a ``deployment``
object with the published ``num_experts`` and the ``chips_per_layer``
that share each layer.  A configuration without ``num_experts`` is
dense.
"""

from __future__ import annotations

from fractions import Fraction
from typing import Iterable

import numpy as np

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
          "float8_e4m3fn": 1}


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"])


def expert_layers(c: dict) -> list:
    """Indices of the layers whose MLP is the sparse-expert block, as
    Hugging Face's Qwen2-MoE decides it: not in ``mlp_only_layers`` and
    (index + 1) a multiple of ``decoder_sparse_step``."""
    if not c.get("num_experts"):
        return []
    dense = set(c.get("mlp_only_layers", []))
    step = c.get("decoder_sparse_step", 1)
    return [i for i in range(c["num_hidden_layers"])
            if i not in dense and (i + 1) % step == 0]


def _expert_mlp_params(c: dict):
    """Matmul weights one token meets in an expert layer's MLP.

    The router scores every published expert (D · published
    ``num_experts``); the shared expert, where there is one, is a gated
    MLP of width Fs (3·D·Fs) with a sigmoid gate of D weights.  Routed
    work is an expectation: each token picks ``num_experts_per_tok`` of
    the published experts, and this chip holds ``num_experts`` of them, so
    with routing spread evenly k · held / published of its choices land
    here, each a gated MLP of width Fe (3·D·Fe).  Measured routing would
    replace the expectation with a program counter.
    """
    D = c["hidden_size"]
    published = c["deployment"]["num_experts"]
    Fs = c.get("shared_expert_intermediate_size") or 0
    shared = 3 * D * Fs + (D if Fs else 0)
    routed = (Fraction(c["num_experts_per_tok"] * c["num_experts"], published)
              * 3 * D * c["moe_intermediate_size"])
    return D * published + shared + routed


def matmul_params(c: dict):
    """Weights that take part in a matrix product for every token: the
    attention projections, each layer's MLP (a dense gated MLP of width
    ``intermediate_size``, or an expert layer as
    :func:`_expert_mlp_params` counts it), and the logits head (the tied
    embedding table counts once, as the head; its lookup is no product).
    An int, or a float where the expected routed share is fractional."""
    L, D, H, Hk, d, V = _dims(c)
    attn = D * H * d + 2 * D * Hk * d + H * d * D
    n_exp = len(expert_layers(c))
    total = L * attn + D * V
    if n_exp < L:
        total += (L - n_exp) * 3 * D * c["intermediate_size"]
    if n_exp:
        total += n_exp * _expert_mlp_params(c)
    total = Fraction(total)
    return int(total) if total.denominator == 1 else float(total)


def matmul_flops_per_token(c: dict):
    return 2 * matmul_params(c)


def expert_work(c: dict, assignments: int, experts_hit: int):
    """(bytes, ops) of one tick's routed-expert matrix products over all
    expert layers.  ``assignments`` counts the (token, held expert) pairs
    the router made, summed over the expert layers; ``experts_hit`` the
    (layer, held expert) pairs that received at least one token.  Each
    expert hit reads its three D×Fe matrices once, at the parameter
    dtype; each pair reads and writes one D-wide activation, at the
    compute dtype, and does 2·3·D·Fe operations."""
    D, Fe = c["hidden_size"], c["moe_intermediate_size"]
    w = _BYTES[c["dtypes"]["params"]]
    act = _BYTES[c["dtypes"]["compute"]]
    nbytes = experts_hit * 3 * D * Fe * w + assignments * 2 * D * act
    return float(nbytes), float(assignments * 6 * D * Fe)


def attn_flops(c: dict, ctx) -> float:
    """Score and value products of one query token over ``ctx`` keys, all
    layers: 2·H·d·ctx for the scores plus 2·H·d·ctx for the values.  Both
    mechanisms are counted alike: the inhibitor's |q - k| sums and ReLU
    inhibition are the same amount of work per (head, key, dim)."""
    L, _, H, _, d, _ = _dims(c)
    return 4.0 * L * H * d * np.asarray(ctx, np.float64)


def kv_bytes_per_token(c: dict) -> int:
    """K and V bytes one cached token holds over all layers."""
    L, _, _, Hk, d, _ = _dims(c)
    return L * 2 * Hk * d * _BYTES[c["dtypes"]["kv_cache"]]


def decode_attn_work(c: dict, ctxs: Iterable[int]):
    """(bytes, ops) the decode attention of one tick needs, for the active
    rows' contexts ``ctxs`` (keys each row attends over, its new token
    included): every row reads its own K/V once and reads q and writes the
    output once per layer.  Operations per (head, key, dim): 4 for
    dot-product (two multiply-adds), 9 for the signed inhibitor (eq. 5:
    subtract, abs, add; eq. 10: two subtract-abs-add passes)."""
    L, _, H, _, d, _ = _dims(c)
    ctxs = np.asarray(list(ctxs), np.float64)
    act = _BYTES[c["dtypes"]["compute"]]
    nbytes = ctxs.sum() * kv_bytes_per_token(c) + len(ctxs) * L * 2 * H * d * act
    per = 9 if c["attention"]["mechanism"].startswith("inhibitor") else 4
    ops = per * L * H * d * ctxs.sum()
    return float(nbytes), float(ops)


def least_time(nbytes: float, ops: float, peaks: dict) -> float:
    """Roofline: the larger of bytes over HBM bandwidth and operations
    over the bf16 peak."""
    return max(nbytes / peaks["hbm_bytes_s"], ops / peaks["bf16_flop_s"])
