"""Seeded weights, made by the benchmark on the device in one jitted call.

The configuration's plain reference (``bench/references/<reference>.py``)
owns the weights' layout: its ``layout(c)`` gives name -> shape and its
``NORMS`` the names that are norm scales.  ``to_program`` places the same
values into the program's parameter tree (the engine's ``params``) by the
configuration's ``program_params`` table: program path -> [weight name]
or [weight name, reshape], the reshape written in the configuration's
dimensions (``L,D,H,d`` and so on).  A leaf the table does not know, or a
shape that does not match, is an error.

Values: every matrix and the embedding ~ N(0, initializer_range), as the
source's initializer; each norm scale 1 + 0.1·N(0, 1), so a path that
dropped a norm's scale would show.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import check


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (64 bits and more)."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)


def dims(c: dict) -> dict:
    """The symbols a ``program_params`` reshape is written in."""
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "F": c["intermediate_size"], "H": c["num_attention_heads"],
            "Hk": c["num_key_value_heads"], "d": c["head_dim"],
            "V": c["vocab_size"]}


def _generate(c: dict, kd):
    ref = check.load_reference(c["reference"])
    key = jax.random.wrap_key_data(kd)
    shapes = ref.layout(c)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ref.NORMS:
            out[name] = 1.0 + 0.1 * z
        else:
            out[name] = c["initializer_range"] * z
    return out


def make(c: dict, seed: int) -> dict:
    """The benchmark's weights in its reference's layout, float32, on the
    device."""
    fn = jax.jit(functools.partial(_generate, c))
    return fn(jnp.asarray(key_data(seed)))


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def to_program(c: dict, seed: int, template):
    """The same weights as :func:`make`, in the program's tree
    ``template`` (its leaves give shape and dtype), in one jitted call."""
    table, sym = c["program_params"], dims(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    plan = []
    for p, leaf in flat:
        name = _path(p)
        if name not in table:
            raise KeyError(f"program parameter {name!r} has no counterpart "
                           f"in the benchmark's weights")
        src, *reshape = table[name]
        shape = (tuple(sym[s] for s in reshape[0].split(","))
                 if reshape else None)
        plan.append((src, shape, tuple(leaf.shape), leaf.dtype))

    def build(kd):
        w = _generate(c, kd)
        leaves = []
        for src, shape, want, dtype in plan:
            v = w[src] if shape is None else w[src].reshape(shape)
            if tuple(v.shape) != want:
                raise ValueError(f"{src}: shape {v.shape} != program {want}")
            leaves.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jnp.asarray(key_data(seed)))
