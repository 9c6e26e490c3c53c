"""The Pallas kernels and the served decode step compile for a TPU v5e at
real widths.

Each kernel test compiles a kernel for a described (not attached) v5e chip
and checks that the Mosaic kernel made it into the program
(``tpu_custom_call``).  Nothing runs, so this says nothing about
results or times; it catches what interpret mode cannot: block shapes
the TPU's tiling refuses, i64 block indices, kernels that need more
fast memory than a chip has.  The decode step's test reads the layouts
the TPU compiler chose: a copy of the whole stacked KV cache there is
what the chip would run every step.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import decode_attention
from repro.kernels.deposit import deposit
from repro.kernels.moe_gmm import gmm
from repro.launch.steps import make_serve_step
from repro.models import (LayerSpec, ModelConfig, Parallel, init_cache,
                          init_params)
from repro.models.config import YarnScaling


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


# Decode widths of the benchmark's serving cells, at a few layers: the
# cache plumbing of the layer scan does not depend on depth.  The latent
# attention cell's step is compiled whole: 128 sequences of 2,048
# positions, 21 scan units.
SERVE_WIDTHS = {
    "granite-moe-3b-a800m": dict(
        cfg=dict(d_model=1536, n_heads=24, n_kv_heads=8, head_dim=64,
                 n_experts=40, top_k=8, d_ff=512, d_ff_expert=512,
                 capacity_factor=5.0, vocab_size=49155, tie_embeddings=True),
        batch=32, max_len=640),
    "deepseek-moe-16b": dict(
        cfg=dict(d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
                 n_experts=64, top_k=6, n_shared_experts=2, d_ff=1408,
                 d_ff_expert=1408, capacity_factor=64 / 6, vocab_size=102400,
                 first_layer_dense=True, first_dense_d_ff=10944),
        batch=8, max_len=640),
    "deepseek-v2-lite": dict(
        cfg=dict(d_model=2048, n_heads=16, n_kv_heads=16, n_experts=64,
                 n_experts_held=8, top_k=6, n_shared_experts=2, d_ff=1408,
                 d_ff_expert=1408, capacity_factor=64 / 6, vocab_size=102400,
                 first_layer_dense=True, first_dense_d_ff=10944,
                 pattern=(LayerSpec("mla", "moe"),), kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_scaling=YarnScaling(
                     factor=40.0, original_max_position_embeddings=4096,
                     mscale=0.707, mscale_all_dim=0.707),
                 n_layers=22, norm_eps=1e-6),
        batch=128, max_len=2048, rows_minor=("latent",)),
}


@pytest.mark.parametrize("model", list(SERVE_WIDTHS))
def test_serve_step_updates_stacked_cache_in_place(one_chip, model):
    """The decode step with its cache donated: no op copies the whole
    stacked K or V cache (or latent cache), so each step writes only the
    new rows."""
    w = SERVE_WIDTHS[model]
    cfg = ModelConfig(**{"name": model, "n_layers": 3,
                         "pattern": (LayerSpec("attn", "moe"),),
                         "param_dtype": "bfloat16",
                         "compute_dtype": "bfloat16", **w["cfg"]})
    b, max_len = w["batch"], w["max_len"]
    on_chip = functools.partial(jax.tree.map, lambda s: _struct(
        s.shape, s.dtype, one_chip))
    params = on_chip(jax.eval_shape(functools.partial(init_params, cfg),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(functools.partial(init_cache, cfg, b,
                                                     max_len)))
    tok = _struct((b, 1), jnp.int32, one_chip)
    pos = _struct((b,), jnp.int32, one_chip)
    hlo = jax.jit(make_serve_step(cfg, Parallel()), donate_argnums=(1,)) \
        .lower(params, cache, tok, pos, None).compile().as_text()
    for name, stack in cache["units"]["b0"].items():
        whole = "bf16[%s]" % ",".join(map(str, stack.shape))
        copies = [line.strip()[:160] for line in hlo.splitlines()
                  if (m := re.search(r"= (.*?) (copy|copy-start)\(", line))
                  and whole + "{" in m.group(1)]
        assert whole in hlo, "the stacked cache is not in the program"
        assert not copies, copies
        if name in w.get("rows_minor", ()):
            # The latent rows, padded to the lanes, stay minor: a row
            # written is contiguous, not a column across the positions.
            assert whole + "{3,2,1,0:" in hlo
