"""Seeded weights, made by the benchmark on the device in one jitted call.

The configuration's plain reference (``bench/references/<reference>.py``)
owns the weights' layout: its ``layout(c)`` gives name -> shape and its
``NORMS`` the names that are norm scales.  ``to_program`` places the same
values into the program's parameter tree (the engine's ``params``) by the
configuration's ``program_params`` table: program path -> [weight name]
or [weight name, reshape], the reshape written in the configuration's
dimensions (``L,D,H,d`` and so on, :func:`dims`).  A leaf the table does
not know, or a shape that does not match, is an error.

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

#: reshape symbol -> configuration key, for the keys a configuration has
_OPTIONAL_DIMS = {"F": "intermediate_size", "E": "num_experts",
                  "Fe": "moe_intermediate_size",
                  "Fs": "shared_expert_intermediate_size"}


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (64 bits and more)."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)


def dims(c: dict) -> dict:
    """The symbols a ``program_params`` reshape is written in: ``E`` is
    the experts held on this chip, ``Fe`` and ``Fs`` the routed and the
    shared expert's widths, each there where the configuration has it."""
    sym = {"L": c["num_hidden_layers"], "D": c["hidden_size"],
           "H": c["num_attention_heads"], "Hk": c["num_key_value_heads"],
           "d": c["head_dim"], "V": c["vocab_size"]}
    sym.update({s: c[k] for s, k in _OPTIONAL_DIMS.items() if k in c})
    return sym


def _layout(c: dict):
    ref = check.load_reference(c["reference"])
    return ref, sorted(ref.layout(c).items())


def _generate(c: dict, kd):
    ref, items = _layout(c)
    keys = jax.random.split(jax.random.wrap_key_data(kd), len(items))
    out = {}
    for k, (name, shape) in zip(keys, items):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ref.NORMS:
            out[name] = 1.0 + 0.1 * z
        else:
            out[name] = c["initializer_range"] * z
    return out


def make(c: dict, seed: int) -> dict:
    """The benchmark's weights in its reference's layout, float32, on the
    device, in one jitted call."""
    fn = jax.jit(functools.partial(_generate, c))
    return fn(jnp.asarray(key_data(seed)))


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def _plan(c: dict, template):
    """Per leaf of ``template``, (weight name, reshape or None, dtype);
    raises before any weight is made where the table and the program
    disagree."""
    table, sym = c["program_params"], dims(c)
    shapes = dict(_layout(c)[1])
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    plan = []
    for p, leaf in flat:
        name = _path(p)
        if name not in table:
            raise KeyError(f"program parameter {name!r} has no counterpart "
                           f"in the benchmark's weights")
        src, *reshape = table[name]
        new = (tuple(sym[s] for s in reshape[0].split(","))
               if reshape else None)
        if tuple(new or shapes[src]) != tuple(leaf.shape):
            raise ValueError(f"{src}: shape {shapes[src]} as {new} != "
                             f"program {tuple(leaf.shape)}")
        plan.append((src, new, leaf.dtype))
    return plan, treedef


def _placer(c: dict, template):
    """The jitted call from the seed's key data to the seeded tree in
    ``template``'s shapes and dtypes.  Its outputs are placed as jit
    places them, as the program's own were: a leaf placed otherwise
    would not match the engine's compiled programs, which would compile
    again on first use."""
    plan, treedef = _plan(c, template)

    def build(kd):
        w = _generate(c, kd)
        return jax.tree_util.tree_unflatten(treedef, [
            (w[src] if new is None else w[src].reshape(new)).astype(dtype)
            for src, new, dtype in plan])

    return jax.jit(build)


def to_program(c: dict, seed: int, template):
    """The same weights as :func:`make`, in the program's tree
    ``template``, in one jitted call that returns each leaf in the
    program's dtype.  XLA draws each weight straight into its leaf:
    compiled for a TPU v5e, the call's temporaries are well under one
    leaf's float32 draw (``tests/bench/test_bench_placement.py``)."""
    return _placer(c, template)(jnp.asarray(key_data(seed)))


def place(c: dict, seed: int, eng) -> None:
    """Put the benchmark's weights into ``eng`` (its ``params``), holding
    the parameters once: the program's own leaves give their shapes and
    dtypes and are deleted before the seeded tree is made."""
    template = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            eng.params)
    _plan(c, template)
    old, eng.params = eng.params, None
    for leaf in jax.tree_util.tree_leaves(old):
        leaf.delete()
    del old
    eng.params = to_program(c, seed, template)
