"""The Pallas kernels compile for a TPU v5e at real widths.

Each test compiles a kernel for a described (not attached) v5e chip and
checks that the Mosaic kernel made it into the program
(``tpu_custom_call``).  Nothing runs, so this says nothing about
results or times; it catches what interpret mode cannot: block shapes
the TPU's tiling refuses, i64 block indices, kernels that need more
fast memory than a chip has.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import decode_attention
from repro.kernels.deposit import deposit
from repro.kernels.moe_gmm import gmm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_rows,n_bins,n_chunks", [
    (576, 3345, 32768),      # serve --traffic smoke, 32-layer world
    (40, 100, 5000),         # fewer than 128 bins: one padded time tile
])
def test_deposit_compiles(one_chip, n_rows, n_bins, n_chunks):
    idx = _struct((n_chunks,), jnp.int32, one_chip)
    vals = _struct((n_chunks,), jnp.float32, one_chip)
    _assert_mosaic(lambda r, c, v: deposit(r, c, v, n_rows, n_bins),
                   idx, idx, vals)


def test_deposit_compiles_under_x64(one_chip):
    """The fused fleet launch traces under x64; block indices stay i32."""
    with jax.enable_x64():
        idx = _struct((4096,), jnp.int32, one_chip)
        vals = _struct((4096,), jnp.float32, one_chip)
        _assert_mosaic(lambda r, c, v: deposit(r, c, v, 300, 700),
                       idx, idx, vals)


@pytest.mark.parametrize("k,n", [(4096, 1376), (1376, 4096)])
def test_gmm_compiles_llama_moe_expert_ffn(one_chip, k, n):
    """LLaMA-MoE-3.5B expert FFN: 8 experts, d_model 4096, d_ff 1376."""
    x = _struct((8, 256, k), jnp.bfloat16, one_chip)
    w = _struct((8, k, n), jnp.bfloat16, one_chip)
    _assert_mosaic(gmm, x, w)


def test_decode_attention_compiles_llama_moe(one_chip):
    """LLaMA-MoE-3.5B decode: 32 KV heads of 128, a 1,024-token cache."""
    q = _struct((4, 32, 1, 128), jnp.bfloat16, one_chip)
    kv = _struct((4, 32, 1024, 128), jnp.bfloat16, one_chip)
    pos = _struct((4,), jnp.int32, one_chip)
    _assert_mosaic(decode_attention, q, kv, kv, pos)
