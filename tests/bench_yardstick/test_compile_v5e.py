"""Compile-only checks, for a described (not attached) TPU v5e, of the
kernels on the cells' main path at the cells' real sizes. Nothing runs: a
compile that passes here is not a chip run. All in this one file: the worker
that gets it loads the TPU's compiler, and keeps it."""
import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _qkv(one_chip, batch, heads, t, d):
    import jax
    import jax.numpy as jnp
    return [jax.ShapeDtypeStruct((batch, heads, t, d), jnp.bfloat16,
                                 sharding=one_chip)] * 3


def test_flash_forward_compiles_at_the_lm_cells_size(one_chip, no_cache):
    """opt-1.3b-fit-s1024: batch 4, 32 heads of 64, 1024 tokens, bf16."""
    import jax
    from mxtpu.ops import attention
    q, k, v = _qkv(one_chip, 4, 32, 1024, 64)
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True)).lower(q, k, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_scan_backward_residuals_at_the_lm_cells_size(one_chip, no_cache):
    """The scan backward's residuals of one layer at B=4, T=1024, which set the
    depth that fits: the configuration's file reckons 2 x B x H x T^2 x 4 B
    = 1.07 GB a layer."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention
    q, k, v = _qkv(one_chip, 4, 32, 1024, 64)

    def loss(q, k, v):
        return jnp.sum(attention.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0.25e9 < temp < 2.2e9, temp
