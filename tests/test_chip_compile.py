"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed on the host,
compiles for a described (not attached) ``v5e:2x2`` topology, and each
test asserts that the Mosaic kernel made it into the executable
(``tpu_custom_call``).  Interpret-mode tests cannot see what this
catches: blocks that violate the chip's (8, 128) tiling rule, scalar
stores to VMEM, and fast-memory overflow.

Widths are ``smollm-135m``'s serving shapes: 9 query heads over 3 KV
heads, head_dim 64, bf16; decode at batch 8 over 16-token pages with
64-page block tables; prefill at 2,048 tokens.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and the test workers each import every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash import flash_attention_fwd
from repro.kernels.inhibitor import flash_inhibitor_fwd
from repro.kernels.paged import (paged_flash_attention_fwd,
                                 paged_flash_inhibitor_fwd)

HEADS, KV_HEADS, D = 9, 3, 64
BATCH, PAGE, TABLE = 8, 16, 64
PREFILL = 2048
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pages_per_step", [1, 4])
@pytest.mark.parametrize("kernel", [paged_flash_inhibitor_fwd,
                                    paged_flash_attention_fwd],
                         ids=["inhibitor", "dotprod"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, kernel,
                                              pages_per_step):
    pool = ((BATCH * TABLE + 1, KV_HEADS, PAGE, D), DT)
    _compile(lambda q, k, v, t, n: kernel(q, k, v, t, n,
                                          pages_per_step=pages_per_step),
             one_chip, ((BATCH, 1, HEADS, D), DT), pool, pool,
             ((BATCH, TABLE), jnp.int32), ((BATCH,), jnp.int32))


@pytest.mark.parametrize("kernel", [flash_inhibitor_fwd,
                                    flash_attention_fwd],
                         ids=["inhibitor", "dotprod"])
def test_prefill_flash_kernel_compiles_for_v5e(one_chip, kernel):
    kv = ((1, PREFILL, KV_HEADS, D), DT)
    _compile(lambda q, k, v: kernel(q, k, v, causal=True), one_chip,
             ((1, PREFILL, HEADS, D), DT), kv, kv)
