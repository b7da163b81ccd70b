"""Plain reference of a Llama-style decoder (SmolLM's block), in float32
``jax.numpy``, written from the configuration and the paper.

Block (pre-norm): x += attn(rmsnorm(x) · ln1); x += mlp(rmsnorm(x) · ln2);
logits = (rmsnorm(x) · norm) @ embed.T (tied).  RoPE rotates halves
(Hugging Face Llama), base ``rope_theta``.  Grouped KV heads: query head
h reads KV head h // (H / Hk).

Attention, by ``attention.mechanism`` in the configuration:

* ``dotprod``: softmax(q·k / scale) · v over the causal prefix (eq. 3).
* ``inhibitor`` (signed, arXiv 2310.02041): eq. 5 with shift,
  Z[i,j] = max(0, Σ_k |q[i,k] - k[j,k]| / scale - shift); eq. 7,
  H[i,k] = Σ_j max(0, V⁺[j,k] - Z[i,j]) + min(0, V⁻[j,k] + Z[i,j]) over
  the causal prefix j <= i, where V⁺ = max(V, 0) and V⁻ = min(V, 0); with
  ``normalize`` H is divided by the number of keys summed (i + 1).  This
  is the broadcast form, computed in blocks of queries so that the
  (queries, keys, dims) terms fit.

Nothing here imports the program.  ``precision="fp8"`` is the control:
every matrix-product operand, and the cached K and V, rounded to
float8_e4m3fn with one scale per tensor, the step below the bfloat16 the
configuration computes in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 64        # queries per block of the inhibitor's broadcast sums
FP8_MAX = 448.0     # largest finite float8_e4m3fn


NORMS = ("ln1", "ln2", "norm")   # scales, drawn as 1 + 0.1·N(0, 1)


def layout(c: dict) -> dict:
    """name -> shape of the weights this reference reads (stacked over
    layers); every name not in ``NORMS`` is drawn as N(0,
    ``initializer_range``)."""
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, Hk, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {
        "embed": (c["vocab_size"], D),
        "ln1": (L, D), "ln2": (L, D), "norm": (D,),
        "wq": (L, D, H * d), "wk": (L, D, Hk * d), "wv": (L, D, Hk * d),
        "wo": (L, H * d, D),
        "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
    }


def _fp8(x):
    """Round to float8_e4m3fn with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (T, heads, d); rotate halves."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, d/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dotprod(q, k, v, scale):
    """q: (T, H, d); k, v: (T, H, d) (KV heads already repeated)."""
    T = q.shape[0]
    s = jnp.einsum("ihd,jhd->hij", q, k) / scale
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hij,jhd->ihd", p, v)


def _inhibitor(q, k, v, scale, shift, normalize):
    T, H, d = q.shape
    vp, vn = jnp.maximum(v, 0.0), jnp.minimum(v, 0.0)
    kpos = jnp.arange(T)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, Q_BLOCK, 0)   # (B, H, d)
        qpos = i0 + jnp.arange(Q_BLOCK)
        z = jnp.sum(jnp.abs(qb[:, None] - k[None]), axis=-1) / scale
        z = jnp.maximum(z - shift, 0.0)                          # (B, T, H)
        m = (kpos[None, :] <= qpos[:, None])[:, :, None, None]   # (B, T, 1, 1)
        zz = z[..., None]                                        # (B, T, H, 1)
        terms = (jnp.maximum(vp[None] - zz, 0.0)
                 + jnp.minimum(vn[None] + zz, 0.0))              # (B, T, H, d)
        h = jnp.sum(jnp.where(m, terms, 0.0), axis=1)            # (B, H, d)
        if normalize:
            h = h / (qpos + 1).astype(jnp.float32)[:, None, None]
        return h

    starts = jnp.arange(0, T, Q_BLOCK)
    return jax.lax.map(block, starts).reshape(T, H, d)


def forward(c: dict, w: dict, tokens, precision: str = "f32"):
    """Logits (T, V), float32, of one sequence ``tokens`` (T,) int32;
    T must be a multiple of ``Q_BLOCK``.  Padding after the last real
    token is causally invisible to it."""
    L = c["num_hidden_layers"]
    H, Hk, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    att = c["attention"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q8 = _fp8 if precision == "fp8" else (lambda t: t)
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    T = tokens.shape[0]
    pos = jnp.arange(T)
    rep = H // Hk

    def mm(x, wt):
        return jnp.dot(q8(x), q8(wt))

    def layer(x, lw):
        h = _rmsnorm(x, lw["ln1"], eps)
        q = _rope(mm(h, lw["wq"]).reshape(T, H, d), pos, theta)
        k = q8(_rope(mm(h, lw["wk"]).reshape(T, Hk, d), pos, theta))
        v = q8(mm(h, lw["wv"]).reshape(T, Hk, d))
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        if att["mechanism"] == "dotprod":
            o = _dotprod(q, k, v, att["score_scale"])
        elif att["mechanism"] == "inhibitor":
            o = _inhibitor(q, k, v, att["score_scale"], att["score_shift"],
                           att["normalize"])
        else:
            raise ValueError(f"no reference for {att['mechanism']!r}")
        x = x + mm(o.reshape(T, H * d), lw["wo"])
        h = _rmsnorm(x, lw["ln2"], eps)
        g = mm(h, lw["w_gate"])
        x = x + mm(jax.nn.silu(g) * mm(h, lw["w_up"]), lw["w_down"])
        return x, None

    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")}
    # a float32 product on a TPU runs in bfloat16 passes unless told not to
    with jax.default_matmul_precision("highest"):
        x = q8(w["embed"][tokens])
        x, _ = jax.lax.scan(layer, x, layers, length=L)
        x = _rmsnorm(x, w["norm"], eps)
        return mm(x, w["embed"].T)


@functools.lru_cache(maxsize=None)
def jitted(c_key: str, precision: str):
    """One compiled reference per (configuration, precision)."""
    import json

    c = json.loads(c_key)
    return jax.jit(lambda w, t: forward(c, w, t, precision))
