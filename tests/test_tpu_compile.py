"""Compile-only checks of the main path's Pallas kernels for a described
TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a topology that is described, not present. These tests
lower each kernel at EMSNet's published widths with ``interpret=False``
and assert that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). They catch what interpret mode cannot: block
shapes that break the TPU tiling rules, operands the chip cannot hold
in the memory space they were given. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantized import quantized_matmul


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


# (B, S, H, D): a batch of 8 max-length narratives through the text
# encoder's key-padding-masked attention (tinybert 312/12 = 26,
# bertbase 768/12 = 64)
@pytest.mark.parametrize("shape", [(8, 64, 12, 26), (8, 64, 12, 64)],
                         ids=["tinybert", "bertbase"])
def test_flash_kv_lengths_compiles(one_chip, shape):
    B, S, H, D = shape
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def attend(q, k, v, kv_lengths):
        return flash_attention(q, k, v, causal=False, kv_lengths=kv_lengths)

    assert "tpu_custom_call" in _compiled_text(attend, x, x, x, lens)


@pytest.mark.parametrize("D", [26, 64], ids=["tinybert", "bertbase"])
def test_flash_segments_compiles_at_chip_block(one_chip, D):
    """The ragged flush's packed (1, T) buffer through the
    segment-masked kernel at the config's default block."""
    from repro.configs.emsnet import EMSNetConfig
    block = EMSNetConfig().flash_block
    x = jax.ShapeDtypeStruct((1, 256, 12, D), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)

    def attend(q, k, v, segment_ids):
        return flash_attention(q, k, v, causal=False,
                               segment_ids=segment_ids, block_q=block,
                               block_k=block)

    assert "tpu_custom_call" in _compiled_text(attend, x, x, x, seg)


# (M, K, N): tinybert's qkv and FFN-down projections over 8 x 64 rows,
# the GRU input projection over 8 x 30 steps, the scene FC
@pytest.mark.parametrize("mkn", [(512, 312, 936), (512, 1200, 312),
                                 (240, 6, 192), (8, 3, 16)],
                         ids=["qkv", "ffn_down", "gru_wx", "scene_fc"])
def test_quantized_matmul_compiles(one_chip, mkn):
    M, K, N = mkn
    x = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    wq = jax.ShapeDtypeStruct((K, N), jnp.int8, sharding=one_chip)
    sw = jax.ShapeDtypeStruct((1, N), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(quantized_matmul, x, wq, sw)
