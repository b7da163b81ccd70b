"""Work counts from a configuration's shapes (``bench/flops.py``): the
dense ``smollm-135m`` files count what they always did, and a
sparse-expert configuration (Qwen1.5-MoE-A2.7B's published shapes) counts
its router, shared expert and routed experts, whole and cut to a chip's
share of the experts."""

import json

import pytest
from bench_helpers import ROOT

from bench import flops, weights


def _smollm(mechanism):
    return json.loads((ROOT / "bench" / "configs" /
                       f"smollm-135m.{mechanism}.json").read_text())


#: Qwen1.5-MoE-A2.7B's config.json
#: (https://huggingface.co/Qwen/Qwen1.5-MoE-A2.7B), with head_dim =
#: hidden_size / num_attention_heads, all 60 experts held
QWEN_MOE = {
    "num_hidden_layers": 24, "hidden_size": 2048, "intermediate_size": 5632,
    "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
    "vocab_size": 151936, "tie_word_embeddings": False,
    "num_experts": 60, "num_experts_per_tok": 4,
    "moe_intermediate_size": 1408, "shared_expert_intermediate_size": 5632,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "deployment": {"num_experts": 60, "chips_per_layer": 1},
    "dtypes": {"params": "bfloat16", "compute": "bfloat16",
               "kv_cache": "bfloat16"},
    "attention": {"mechanism": "inhibitor"},
}
ATTN = 4 * 2048 * 2048                  # q, k, v, o at 16 heads of 128
HEAD = 2048 * 151936                    # 311,164,928


def _cut(**kw):
    c = json.loads(json.dumps(QWEN_MOE))
    c.update(kw)
    return c


@pytest.mark.parametrize("mechanism", ["inhibitor", "dotprod"])
def test_dense_counts_are_unchanged(mechanism):
    """The numbers the parent's arithmetic gives for these files."""
    c = _smollm(mechanism)
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, Hk, d = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    V = c["vocab_size"]
    want = L * (D * H * d + 2 * D * Hk * d + H * d * D + 3 * D * F) + D * V
    got = flops.matmul_params(c)
    assert type(got) is int and got == want == 134_479_872
    assert flops.matmul_flops_per_token(c) == 268_959_744
    assert float(flops.attn_flops(c, 1000)) == 69_120_000.0
    assert flops.kv_bytes_per_token(c) == 23_040
    ops = 334_212_480.0 if mechanism == "inhibitor" else 148_538_880.0
    assert flops.decode_attn_work(c, [1, 100, 2048]) == (49_720_320.0, ops)
    assert flops.expert_layers(c) == []


def test_published_moe_counts_router_shared_and_routed_experts():
    per_layer = (ATTN + 2048 * 60 + 3 * 2048 * 5632 + 2048
                 + 4 * 3 * 2048 * 1408)
    assert per_layer == 86_108_160
    assert flops.matmul_params(QWEN_MOE) == 24 * per_layer + HEAD == (
        2_377_760_768)
    # with the untied embedding table: the A2.7B of the name
    assert flops.matmul_params(QWEN_MOE) + HEAD == 2_688_925_696


def test_a_chips_share_of_the_experts_counts_the_expected_routed_work():
    """8 of 24 layers, 10 of 60 experts (six chips share each layer): the
    router keeps its 60 outputs, the shared expert stays whole, and
    4 × 10 / 60 of a token's routed experts land here."""
    c = _cut(num_hidden_layers=8, num_experts=10,
             deployment={"num_experts": 60, "chips_per_layer": 6})
    routed = 4 * 10 * 3 * 2048 * 1408 // 60
    assert routed == 5_767_168
    per_layer = ATTN + 2048 * 60 + 3 * 2048 * 5632 + 2048 + routed
    assert flops.matmul_params(c) == 8 * per_layer + HEAD == 769_343_488
    odd = _cut(num_experts=7)           # 4 × 7 / 60 is no whole number
    want = 24 * (ATTN + 2048 * 60 + 3 * 2048 * 5632 + 2048) + HEAD + (
        24 * 4 * 7 * 3 * 2048 * 1408 / 60)
    assert flops.matmul_params(odd) == pytest.approx(want, rel=1e-15)


def test_dense_layers_and_configurations_without_a_dense_width():
    dense_first = _cut(mlp_only_layers=[0, 1])
    assert flops.expert_layers(dense_first) == list(range(2, 24))
    assert flops.matmul_params(dense_first) == (
        flops.matmul_params(QWEN_MOE)
        - 2 * (2048 * 60 + 3 * 2048 * 5632 + 2048 + 4 * 3 * 2048 * 1408)
        + 2 * 3 * 2048 * 5632)
    assert flops.expert_layers(_cut(decoder_sparse_step=2)) == list(
        range(1, 24, 2))
    no_dense = _cut()
    del no_dense["intermediate_size"]
    assert flops.matmul_params(no_dense) == flops.matmul_params(QWEN_MOE)
    assert weights.dims(no_dense) == {
        "L": 24, "D": 2048, "H": 16, "Hk": 16, "d": 128, "V": 151936,
        "E": 60, "Fe": 1408, "Fs": 5632}
    assert weights.dims(_smollm("dotprod"))["F"] == 1536


def test_expert_work_agrees_with_a_hand_count():
    """48 tokens routed 4 ways, all to held experts, 10 experts hit, in
    bfloat16: 10 × 3 × 2048 × 1408 × 2 B of weights,
    192 × 2 × 2048 × 2 B of activations, 192 × 6 × 2048 × 1408
    operations."""
    nbytes, ops = flops.expert_work(QWEN_MOE, assignments=192, experts_hit=10)
    assert nbytes == 173_015_040 + 1_572_864 == 174_587_904
    assert ops == 3_321_888_768
    assert flops.expert_work(QWEN_MOE, 0, 0) == (0.0, 0.0)
