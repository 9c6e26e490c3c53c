"""The MLA reference against the program's serving path, on the same
seeded weights, at a small size on the CPU (float32, full precision)."""
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.serve_mla import program_config, program_params
from bench.mla_dims import dims
from bench.reference import mla_moe_lm as reference
from bench.weights import seed_key
from tiny_mla import MLA_LIKE

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def test_prefill_then_decode_match_reference_forward():
    """Prefill of a prompt, then teacher-forced decode steps through the
    latent cache (absorbed form), give the reference's full-forward
    logits (expanded form) at every position served."""
    from repro.models import decode_step, init_params, prefill
    seed = 2**31 + 4321                   # larger than 32 signed bits
    d, cfg = dims(MLA_LIKE), program_config(MLA_LIKE)
    assert cfg.held_share and cfg.experts_held == 4 and cfg.n_experts == 8
    structs = jax.eval_shape(functools.partial(init_params, cfg),
                             jax.random.PRNGKey(0))
    params = program_params(structs, seed, d)
    prompt, total = 9, 14
    tokens = np.random.default_rng(3).integers(0, d.vocab, (2, total),
                                               dtype=np.int32)
    got = []
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(cfg, params, {"tokens": tokens[:, :prompt]},
                                max_len=total)
        got.append(logits)
        pos = jnp.full((2,), prompt, jnp.int32)
        for i in range(prompt, total):
            logits, cache = decode_step(cfg, params, cache,
                                        tokens[:, i:i + 1], pos)
            got.append(logits)
            pos = pos + 1
    got = np.stack([np.asarray(g) for g in got], axis=1)
    want = reference.logits(d, seed_key(seed), jnp.float32, tokens,
                            prompt - 1)
    # The program pads its vocabulary with zero rows.
    np.testing.assert_array_equal(got[..., d.vocab:], 0.0)
    # Both sides compute in float32 at full matmul precision; they differ
    # in the order of sums (absorbed against expanded attention, dropless
    # buckets against every held expert on every token), a few ulps of the
    # logits' scale (~0.5) each.
    np.testing.assert_allclose(got[..., :d.vocab], want, rtol=2e-4,
                               atol=2e-5)


def test_yarn_and_softmax_scale_match_at_published_values():
    from repro.models.layers import yarn_frequencies
    from repro.models.mla import softmax_scale
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite.json")) as f:
        config = json.load(f)
    d, cfg = dims(config), program_config(config)
    assert (d.yarn_factor, d.yarn_original_max, d.qk_rope) == (40, 4096, 64)
    np.testing.assert_allclose(
        np.asarray(yarn_frequencies(64, cfg.rope_theta, cfg.rope_scaling)),
        np.asarray(reference.yarn_inv_freq(d)), rtol=1e-6)
    published = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    # Python floats on both sides, multiplied in another order.
    assert math.isclose(softmax_scale(cfg), reference.softmax_scale(d),
                        rel_tol=1e-15)
    assert math.isclose(softmax_scale(cfg), published, rel_tol=1e-12)


def test_yarn_ramps_between_kept_and_scaled_frequencies():
    """At the published values (64 rotary dims, 4096 positions, beta 32
    and 1) the correction range is frequencies 10 to 23: below it they
    keep their plain value, above it they are divided by the factor, and
    between they lie between."""
    d = dims({**MLA_LIKE, "qk_rope_head_dim": 64})
    inv = np.asarray(reference.yarn_inv_freq(d))
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    mid = slice(11, 23)
    assert np.all((inv[mid] <= plain[mid] * (1 + 1e-6))
                  & (inv[mid] >= plain[mid] / 40 * (1 - 1e-6)))
