"""Compile the main path's Pallas kernel for a TPU v5e that is described,
not attached: the TPU compiler refuses here what interpret mode on the CPU
accepts (misaligned tiles, too much VMEM).  Shapes are the 720M DiT's
(16 heads of 72) at the training smoke shape and the paper's spatial
extent.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep all such compiles in this one file.
"""
import functools
import os

import pytest

H, D = 16, 72        # transformer2d-720m: d_model 1152 / 16 heads


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_fwd(one_chip, batch, q_len, kv_len, kv_valid):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention_fwd
    q = jax.ShapeDtypeStruct((batch, H, q_len, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, H, kv_len, D), jnp.bfloat16,
                              sharding=one_chip)
    fn = functools.partial(flash_attention_fwd, kv_len=kv_valid,
                           block_q=min(128, q_len), interpret=False)
    return jax.jit(fn).lower(q, kv, kv).compile().as_text()


@pytest.mark.parametrize("batch,s_len", [(16, 256), (1, 4096)],
                         ids=["S256", "S4096"])
def test_spatial_stage_kernel_compiles(one_chip, batch, s_len):
    # spatial attention: (B*T) sequences of S tokens
    hlo = _compile_fwd(one_chip, batch, s_len, s_len, s_len)
    assert "tpu_custom_call" in hlo


def test_temporal_stage_kernel_compiles(one_chip):
    # temporal attention: (B*S) sequences of T=16 frames, KV padded to 128
    # as kernels/ops.py pads it
    hlo = _compile_fwd(one_chip, 256, 16, 128, 16)
    assert "tpu_custom_call" in hlo
