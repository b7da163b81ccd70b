"""The plain reference agrees with the program at a tiny size on the CPU:
its attention with ``repro.core``'s, and its whole forward with the
program's forward over the same seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_helpers import tiny_config

from bench import check, weights

T = 64          # a multiple of the reference's query block


def _qkv(seed, T, H=4, Hk=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (T, H, d))
    k = jax.random.normal(ks[1], (T, Hk, d))
    v = jax.random.normal(ks[2], (T, Hk, d))
    return q, k, v


def _causal(T):
    return (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None]


@pytest.mark.parametrize("seed", [0, 1])
def test_inhibitor_reference_matches_core(seed):
    from repro.core.inhibitor import inhibitor_attention

    ref = check.load_reference("llama")
    q, k, v = _qkv(seed, T)
    got = ref._inhibitor(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                         4.0, 0.5, True)
    want = inhibitor_attention(q[None], k[None], v[None], mask=_causal(T),
                               score_scale=4.0, score_shift=0.5,
                               signed=True, normalize=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", [0, 1])
def test_dotprod_reference_matches_core(seed):
    from repro.core.dotprod import dot_product_attention

    ref = check.load_reference("llama")
    q, k, v = _qkv(seed, T)
    with jax.default_matmul_precision("highest"):
        got = ref._dotprod(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1), 4.0)
        want = dot_product_attention(q[None], k[None], v[None],
                                     mask=_causal(T), score_scale=4.0)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mechanism", ["dotprod", "inhibitor"])
def test_reference_forward_matches_program_forward(mechanism):
    """Same seeded weights, placed in the program's tree by
    ``weights.to_program``: the program's float32 whole-sequence forward
    and the reference's logits agree."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn.module import unbox

    c = tiny_config(mechanism)
    api = get_model(get_config(c["program"]).reduced())
    template = unbox(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    params = weights.to_program(c, 77, template)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (T,), 0,
                                c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = api.forward(params, {"tokens": tokens[None]})[0][0]
    ref = check.load_reference("llama")
    got = ref.forward(c, weights.make(c, 77), tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_fp8_control_departs_from_float32():
    c = tiny_config("inhibitor")
    ref = check.load_reference("llama")
    w = weights.make(c, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (T,), 0,
                                c["vocab_size"])
    f32 = ref.forward(c, w, tokens, "f32")
    low = ref.forward(c, w, tokens, "fp8")
    rel = float(jnp.max(jnp.abs(low - f32)) / jnp.max(jnp.abs(f32)))
    assert 1e-3 < rel < 0.5


def test_weights_are_seeded_and_placed_leaf_by_path():
    c = tiny_config("dotprod")
    a, b = weights.make(c, 2**35 + 1), weights.make(c, 2**35 + 1)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    other = weights.make(c, 2**35 + 2)
    assert not np.array_equal(a["wq"], other["wq"])
    with pytest.raises(KeyError):
        weights.to_program(c, 1, {"mystery": jnp.zeros((2,))})
