"""Set-up puts the benchmark's weights into the engine holding the
parameters once: the program's own leaves are freed before the seeded
tree is made, the placement call's temporaries (compiled for a TPU
v5e) stay under one leaf's float32 draw, and the placed leaves are bit
for bit the reference's draw (``weights.make``) reshaped and cast as the
program holds them."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_helpers import tiny_config

from bench import harness, weights


def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


def _one_call(c, seed, template):
    """The weights of ``make``'s draw, reshaped and cast as the program
    holds them, as the parent placed them: the reference for the
    placement's values."""
    table, sym = c["program_params"], weights.dims(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)

    def build(kd):
        w = weights._generate(c, kd)
        leaves = []
        for p, leaf in flat:
            src, *reshape = table[weights._path(p)]
            v = w[src]
            if reshape:
                v = v.reshape(tuple(sym[s] for s in reshape[0].split(",")))
            leaves.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jnp.asarray(weights.key_data(seed)))


def _template(mechanism, dtype):
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn.module import unbox

    api = get_model(get_config(tiny_config(mechanism)["program"]).reduced())
    shapes = unbox(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mechanism", ["inhibitor", "dotprod"])
def test_placement_is_bit_identical(mechanism, dtype):
    c = tiny_config(mechanism)
    template = _template(mechanism, dtype)
    seed = 2**33 + 17
    got = weights.to_program(c, seed, template)
    want = _one_call(c, seed, template)
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                         jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype == dtype, p
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=weights._path(p))


def _program_engine(monkeypatch, c, seed):
    """The engine as the program builds it, its own random weights in."""
    monkeypatch.setattr(weights, "place", lambda *a: None)
    eng = harness.build_engine(c, seed)
    monkeypatch.undo()
    return eng


def test_the_engine_holds_its_parameters_nowhere_else(monkeypatch):
    """Dropping ``Engine.params`` frees every byte of them: the engine
    keeps no other reference, so deleting its leaves frees no buffer the
    program still uses."""
    c = tiny_config("dotprod")
    eng = _program_engine(monkeypatch, c, 3)
    gc.collect()
    before = _live_bytes()
    held = sum(a.nbytes for a in jax.tree_util.tree_leaves(eng.params))
    eng.params = None
    gc.collect()
    assert _live_bytes() == before - held > 0


def test_placement_never_holds_two_parameter_trees(monkeypatch):
    """When the seeded tree is drawn, the program's own leaves are already
    deleted and the live bytes are those of the engine without them; the
    engine ends with the seeded weights and no more bytes than before."""
    c, seed = tiny_config("inhibitor"), 2**40 + 3
    eng = _program_engine(monkeypatch, c, seed)
    old = jax.tree_util.tree_leaves(eng.params)
    held = sum(a.nbytes for a in old)
    gc.collect()
    before = _live_bytes()
    seen = []
    generate = weights._generate

    def watched(c, kd):
        seen.append((all(a.is_deleted() for a in old), _live_bytes()))
        return generate(c, kd)

    monkeypatch.setattr(weights, "_generate", watched)
    weights.place(c, seed, eng)
    key = 2 * 4                                   # the seed's two words
    assert seen == [(True, before - held + key)]
    gc.collect()
    assert _live_bytes() == before
    monkeypatch.undo()
    want = _one_call(c, seed, eng.params)
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    """One chip of a described (not attached) TPU v5e; described here and
    not at import, since one process at a time may load the TPU library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_placement_temporaries_stay_under_one_float32_leaf(one_chip, dtype):
    """The cells' placement (smollm-135m at published widths), compiled
    for a TPU v5e: its outputs are the parameters once, in the program's
    dtype, and its temporaries are under the largest leaf's float32
    draw, so set-up's peak over the engine is at most the parameters once
    plus one float32 leaf."""
    import json

    from bench_helpers import ROOT
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn.module import unbox

    c = json.loads((ROOT / "bench" / "configs" /
                    "smollm-135m.inhibitor.json").read_text())
    api = get_model(get_config(c["program"]))
    shapes = unbox(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    template = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype, sharding=one_chip),
        shapes)
    leaves = jax.tree_util.tree_leaves(template)
    params = sum(int(np.prod(x.shape)) * np.dtype(dtype).itemsize
                 for x in leaves)
    largest = max(int(np.prod(x.shape)) for x in leaves) * 4
    kd = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = weights._placer(c, template).lower(kd).compile().memory_analysis()
    assert params <= mem.output_size_in_bytes < params * 1.03  # + padding
    assert mem.temp_size_in_bytes < largest


def test_placed_leaves_are_placed_as_the_programs_own(monkeypatch):
    """The engine compiled its programs for the program's own leaves; a
    placed leaf on another placement, or committed where they were not,
    would compile them again on first use, inside the ramp or the
    measured window (on the CPU, committed leaves took the tiny chat
    run's compiles in the window from 2 to 7)."""
    c, seed = tiny_config("dotprod"), 2**36 + 5
    eng = _program_engine(monkeypatch, c, seed)
    before = [(a.shape, a.dtype, a.sharding, a.committed)
              for a in jax.tree_util.tree_leaves(eng.params)]
    weights.place(c, seed, eng)
    assert [(a.shape, a.dtype, a.sharding, a.committed)
            for a in jax.tree_util.tree_leaves(eng.params)] == before
