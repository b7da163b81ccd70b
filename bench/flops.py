"""Operations and bytes of the model's work, from the configuration's
shapes alone (``bench/configs/<config>.json``, Hugging Face key names).

Nothing here looks at how the program implements the work: no table
bucket width, no kernel grid, no padding.  The same work is counted
whatever computes it.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
          "float8_e4m3fn": 1}


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    attention projections, the gated MLP, and the logits head (the tied
    embedding table counts once, as the head; its lookup is no product)."""
    L, D, F, H, Hk, d, V = _dims(c)
    per_layer = D * H * d + 2 * D * Hk * d + H * d * D + 3 * D * F
    return L * per_layer + D * V


def matmul_flops_per_token(c: dict) -> int:
    return 2 * matmul_params(c)


def attn_flops(c: dict, ctx) -> float:
    """Score and value products of one query token over ``ctx`` keys, all
    layers: 2·H·d·ctx for the scores plus 2·H·d·ctx for the values.  Both
    mechanisms are counted alike: the inhibitor's |q - k| sums and ReLU
    inhibition are the same amount of work per (head, key, dim)."""
    L, _, _, H, _, d, _ = _dims(c)
    return 4.0 * L * H * d * np.asarray(ctx, np.float64)


def kv_bytes_per_token(c: dict) -> int:
    """K and V bytes one cached token holds over all layers."""
    L, _, _, _, Hk, d, _ = _dims(c)
    return L * 2 * Hk * d * _BYTES[c["dtypes"]["kv_cache"]]


def decode_attn_work(c: dict, ctxs: Iterable[int]):
    """(bytes, ops) the decode attention of one tick needs, for the active
    rows' contexts ``ctxs`` (keys each row attends over, its new token
    included): every row reads its own K/V once and reads q and writes the
    output once per layer.  Operations per (head, key, dim): 4 for
    dot-product (two multiply-adds), 9 for the signed inhibitor (eq. 5:
    subtract, abs, add; eq. 10: two subtract-abs-add passes)."""
    L, _, _, H, _, d, _ = _dims(c)
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
