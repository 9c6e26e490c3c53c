"""The plain reference against the program's own forward pass, on the same
seeded weights, at a small size on the CPU (float32, full precision)."""
import functools

import jax
import numpy as np
import pytest

from bench.drivers.serve import program_config
from bench.model_dims import dims
from bench.reference import moe_lm as reference
from bench.weights import program_params, seed_key
from tiny import DEEPSEEK_LIKE, GRANITE_LIKE


@pytest.mark.parametrize("config", [DEEPSEEK_LIKE, GRANITE_LIKE],
                         ids=["deepseek-like", "granite-like"])
def test_reference_matches_program_forward(config):
    from repro.models import forward, init_params
    seed = 2**31 + 12345                  # larger than 32 signed bits
    d, cfg = dims(config), program_config(config)
    structs = jax.eval_shape(functools.partial(init_params, cfg),
                             jax.random.PRNGKey(0))
    params = program_params(structs, seed, d.n_layers, d.n_dense, d.vocab,
                            d.tied)
    tokens = np.random.default_rng(0).integers(0, d.vocab, (2, 12),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(cfg, params, {"tokens": tokens})[0])
    want = reference.logits(d, seed_key(seed), jax.numpy.float32, tokens, 0)
    assert got.shape[-1] >= d.vocab
    # The program pads its vocabulary with zero rows.
    np.testing.assert_array_equal(got[..., d.vocab:], 0.0)
    np.testing.assert_allclose(got[..., :d.vocab], want, rtol=2e-4,
                               atol=2e-5)


def test_weights_are_a_function_of_seed_name_and_layer():
    d = dims(DEEPSEEK_LIKE)
    key = seed_key(7)
    a = reference.make_layer(key, d, False, 2, jax.numpy.bfloat16)
    b = reference.make_layer(key, d, False, 2, jax.numpy.bfloat16)
    c = reference.make_layer(key, d, False, 1, jax.numpy.bfloat16)
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    assert not np.array_equal(a["moe.gate"], c["moe.gate"])
    assert not np.array_equal(
        reference.make_layer(seed_key(8), d, False, 2,
                             jax.numpy.bfloat16)["attn.q"], a["attn.q"])
