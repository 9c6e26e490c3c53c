"""Model-zoo correctness: attention oracle, MoE oracle, decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (LayerSpec, ModelConfig, apply_placement,
                          decode_step, forward, init_params, loss_fn,
                          prefill, random_batch)
from repro.models import moe
from repro.models.attention import flash_attention
from repro.models.config import ModelConfig as MC
from repro.models.config import YarnScaling
from repro.models.moe import (capacity, dispatch_indices, moe_apply_local,
                              moe_init, reads_hit_experts, route)

F32 = jnp.float32


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=128, attn_q_chunk=8, attn_kv_chunk=8,
                compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


# --------------------------------------------------------------------- #
# Attention: chunked flash vs naive softmax oracle
# --------------------------------------------------------------------- #


def naive_attention(q, k, v, q_pos, kv_pos, sliding=0):
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    sco = jnp.einsum("bqngd,bknd->bnqgk", qg.transpose(0, 1, 2, 3, 4),
                     k) * hd**-0.5
    mask = q_pos[:, None, :, None, None] >= kv_pos[:, None, None, None, :]
    if sliding:
        mask &= (q_pos[:, None, :, None, None]
                 - kv_pos[:, None, None, None, :]) < sliding
    sco = jnp.where(mask, sco, -1e30)
    p = jax.nn.softmax(sco, axis=-1)
    out = jnp.einsum("bnqgk,bknd->bqngd", p, v)
    return out.reshape(b, s, hq, hd)


@pytest.mark.parametrize("s,qc,kc", [(32, 8, 8), (64, 16, 32), (32, 32, 32)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_matches_naive(s, qc, kc, hq, hkv):
    cfg = tiny_cfg(n_heads=hq, n_kv_heads=hkv, attn_q_chunk=qc, attn_kv_chunk=kc)
    key = jax.random.PRNGKey(0)
    b, hd = 2, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, hd), F32)
    k = jax.random.normal(ks[1], (b, s, hkv, hd), F32)
    v = jax.random.normal(ks[2], (b, s, hkv, hd), F32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    out = flash_attention(cfg, q, k, v, pos, pos)
    ref = naive_attention(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_sliding_window():
    cfg = tiny_cfg(sliding_window=8, attn_q_chunk=8, attn_kv_chunk=8)
    b, s, hq, hd = 1, 32, 4, 8
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, hd), F32)
    k = jax.random.normal(ks[1], (b, s, 2, hd), F32)
    v = jax.random.normal(ks[2], (b, s, 2, hd), F32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    out = flash_attention(cfg, q, k, v, pos, pos)
    ref = naive_attention(q, k, v, pos, pos, sliding=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------------- #
# MoE: dispatch plan properties + oracle equivalence
# --------------------------------------------------------------------- #


def test_dispatch_indices_properties():
    rng = np.random.default_rng(0)
    t, k, e, cap = 64, 2, 8, 32
    idx = jnp.asarray(rng.integers(0, e, (t, k)), jnp.int32)
    slot_token, slot_valid, copy_slot, copy_kept = dispatch_indices(idx, e, cap)
    assert bool(copy_kept.all())  # cap is generous: nothing dropped
    # every kept copy's slot belongs to its expert
    flat = np.asarray(idx).reshape(-1)
    slots = np.asarray(copy_slot)
    assert (slots // cap == flat).all()
    # slots are unique among kept copies
    assert len(np.unique(slots)) == t * k
    # slot -> token mapping is the inverse
    st, sv = np.asarray(slot_token), np.asarray(slot_valid)
    for copy_i in range(t * k):
        assert st[slots[copy_i]] == copy_i and sv[slots[copy_i]]


def test_dispatch_drops_overflow_deterministically():
    # all tokens pick expert 0 with cap 4 => 4 kept
    idx = jnp.zeros((16, 1), jnp.int32)
    _, slot_valid, _, copy_kept = dispatch_indices(idx, 4, 4)
    assert int(copy_kept.sum()) == 4
    assert int(slot_valid.sum()) == 4


def dense_moe_oracle(cfg, params, x):
    """Compute every expert on every token, combine with top-k weights."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    weights, idx, _ = route(cfg, params["router"], xt)
    outs = []
    for e in range(cfg.n_experts):
        g = jax.nn.silu(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e])
        outs.append(g @ params["w_down"][e])
    all_out = jnp.stack(outs, axis=1)                       # (T, E, d)
    sel = jnp.take_along_axis(all_out, idx[..., None], axis=1)
    y = jnp.einsum("tkd,tk->td", sel, weights)
    if cfg.n_shared_experts:
        sh = params["shared"]
        y = y + (jax.nn.silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"])) @ sh["w_down"]
    return y.reshape(b, s, d)


@pytest.mark.slow
@pytest.mark.parametrize("e,k,shared", [(8, 2, 0), (16, 4, 0), (8, 2, 2)])
def test_moe_local_matches_dense_oracle(e, k, shared):
    cfg = tiny_cfg(pattern=(LayerSpec("attn", "moe"),), n_experts=e, top_k=k,
                   d_ff_expert=16, n_shared_experts=shared,
                   capacity_factor=8.0)   # generous: dropless
    params = moe_init(jax.random.PRNGKey(0), cfg, F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), F32)
    y, aux = moe_apply_local(cfg, params, x, F32)
    ref = dense_moe_oracle(cfg, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
    assert np.isfinite(float(aux["load_balance_loss"]))


def test_moe_placement_transform_is_equivalent():
    """apply_placement permutes weights+router consistently => same output."""
    cfg = tiny_cfg(pattern=(LayerSpec("attn", "moe"),), n_experts=8, top_k=2,
                   d_ff_expert=16, capacity_factor=8.0)
    params = moe_init(jax.random.PRNGKey(0), cfg, F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), F32)
    y0, _ = moe_apply_local(cfg, params, x, F32)
    perm = np.random.default_rng(3).permutation(8)
    y1, _ = moe_apply_local(cfg, apply_placement(params, perm), x, F32)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)


def test_capacity_formula():
    cfg = tiny_cfg(pattern=(LayerSpec("attn", "moe"),), n_experts=8, top_k=2,
                   d_ff_expert=16, capacity_factor=1.25)
    assert capacity(cfg, 64, 8) == int(np.ceil(1.25 * 64 * 2 / 8))
    assert capacity(cfg, 1, 8) >= cfg.top_k


@pytest.mark.parametrize("tokens,k,e,held,reads_hit", [
    (8, 6, 64, 0, True),           # deepseek-moe-16b chat decode: 48 < 64
    (32, 8, 40, 0, False),         # granite reasoning decode: 256 >= 40
    (8 * 512, 6, 64, 0, False),    # chat prefill
    (32 * 128, 8, 40, 0, False),   # reasoning prefill
    (128, 6, 64, 8, False),        # deepseek-v2-lite's held share, decode
    (1, 6, 64, 8, False),          # a held share even where T·K < E
])
def test_reads_hit_experts_by_shape(tokens, k, e, held, reads_hit):
    """The decode MoE reads only the experts hit exactly where a full
    layer's T·K copies cannot hit every expert."""
    cfg = tiny_cfg(pattern=(LayerSpec("attn", "moe"),), n_experts=e,
                   n_experts_held=held, top_k=k, d_ff_expert=16)
    assert reads_hit_experts(cfg, tokens) is reads_hit


def test_decode_reading_hit_experts_gives_the_scanned_logits(monkeypatch):
    """A decode step whose MoE reads the expert stacks by closure (16
    experts, 3 tokens x top-2) gives the logits and cache of the step that
    scans the experts' weights with the other unit params."""
    cfg = tiny_cfg(pattern=(LayerSpec("attn", "moe"),), n_layers=3,
                   first_layer_dense=True, n_experts=16, top_k=2,
                   d_ff_expert=16, capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 3, 8
    assert reads_hit_experts(cfg, b)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0,
                                cfg.vocab_size)
    _, cache = prefill(cfg, params, {"tokens": tokens[:, :s]}, max_len=s + 4)
    pos = jnp.full((b,), s, jnp.int32)

    def step():
        return jax.jit(lambda c: decode_step(
            cfg, params, c, tokens[:, s:], pos))(cache)

    got = step()
    monkeypatch.setattr(moe, "reads_hit_experts", lambda cfg, n: False)
    want = step()
    jax.tree.map(lambda a, w: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(w)), got, want)


# --------------------------------------------------------------------- #
# Decode consistency: prefill + step == full forward
# --------------------------------------------------------------------- #


ARCH_CASES = {
    "dense_gqa": dict(),
    "qkv_bias": dict(qkv_bias=True),
    "moe": dict(pattern=(LayerSpec("attn", "moe"),), n_experts=4, top_k=2,
                d_ff_expert=16, capacity_factor=8.0),
    "mamba": dict(pattern=(LayerSpec("mamba", "dense"),), n_heads=4,
                  n_kv_heads=4),
    "mlstm": dict(pattern=(LayerSpec("mlstm", "none"),), tie_embeddings=True),
    "slstm": dict(pattern=(LayerSpec("slstm", "none"),), tie_embeddings=True),
    "hybrid": dict(pattern=(LayerSpec("attn", "dense"),
                            LayerSpec("mamba", "dense")), n_layers=4),
    "mla": dict(pattern=(LayerSpec("mla", "moe"),), n_experts=4, top_k=2,
                d_ff_expert=16, capacity_factor=2.0, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
}


@pytest.mark.slow
@pytest.mark.parametrize("case", list(ARCH_CASES))
def test_decode_matches_forward(case):
    cfg = tiny_cfg(**ARCH_CASES[case])
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0,
                                cfg.vocab_size)
    # full forward over s+1 tokens: logits at position s
    logits_full, _ = forward(cfg, params, {"tokens": tokens})
    want = logits_full[:, s, :]
    # prefill s tokens, then decode token s
    _, cache = prefill(cfg, params, {"tokens": tokens[:, :s]}, max_len=s + 4)
    got, _ = decode_step(cfg, params, cache, tokens[:, s:s + 1],
                         jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


TEACHER_CASES = {
    "moe": ARCH_CASES["moe"],           # one attention row written a layer
    # 3 tokens x top-2 of 16 experts: the MoE reads the hit experts from
    # the whole stacks at the layer's unit
    "moe_hit_experts": dict(ARCH_CASES["moe"], n_experts=16),
    "hybrid": ARCH_CASES["hybrid"],     # + a whole mamba state at the unit
    "first_layer_dense": dict(ARCH_CASES["moe"], n_layers=3,
                              first_layer_dense=True),  # its own buffer
    # one latent row a layer, absorbed decode against the expanded forward;
    # YaRN over a short original length, and a held share of the experts
    "mla_held": dict(ARCH_CASES["mla"], n_layers=3, first_layer_dense=True,
                     n_experts=8, n_experts_held=4, capacity_factor=4.0,
                     rope_scaling=YarnScaling(
                         factor=4.0, original_max_position_embeddings=8,
                         mscale=0.707, mscale_all_dim=0.5)),
}


@pytest.mark.parametrize("case", list(TEACHER_CASES))
def test_decode_steps_match_forward_ragged(case):
    """Several teacher-forced decode steps, each sequence at its own
    position, give the full forward's logits at those positions."""
    cfg = tiny_cfg(**TEACHER_CASES[case])
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(0))
    lens, n_steps = (5, 9, 7), 4
    b, total = len(lens), max(lens) + n_steps
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, total), 0,
                                cfg.vocab_size)
    logits_full, _ = forward(cfg, params, {"tokens": tokens})
    # Prefill each sequence alone at its own length, then batch the caches
    # (batch is axis 1 of the stacked unit cache, axis 0 of the first's).
    caches = [prefill(cfg, params, {"tokens": tokens[i:i + 1, :n]},
                      max_len=total)[1] for i, n in enumerate(lens)]
    cache = {"units": jax.tree.map(lambda *a: jnp.concatenate(a, axis=1),
                                   *[c["units"] for c in caches])}
    if cfg.first_layer_dense:
        cache["first"] = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                                      *[c["first"] for c in caches])
    step = jax.jit(lambda c, t, p: decode_step(cfg, params, c, t, p))
    rows = jnp.arange(b)
    pos = jnp.asarray(lens, jnp.int32)
    for _ in range(n_steps):
        got, cache = step(cache, tokens[rows, pos][:, None], pos)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(logits_full[rows, pos]),
                                   atol=2e-4, rtol=2e-4)
        pos = pos + 1


def test_training_step_reduces_loss():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = random_batch(cfg, 4, 16, seed=0)

    @jax.jit
    def step(p):
        (l, m), g = jax.value_and_grad(
            lambda pp: loss_fn(cfg, pp, batch), has_aux=True
        )(p)
        p = jax.tree.map(lambda a, b: a - 0.5 * b, p, g)
        return p, l

    losses = []
    for _ in range(8):
        params, l = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
