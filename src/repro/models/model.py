"""Composable LM: init / forward / loss / prefill / decode_step.

The layer stack is a ``lax.scan`` over repeating pattern units (HLO size
independent of depth).  Each unit applies its pattern of
(mixer, ffn) blocks; mixers are attention / latent attention (mla) /
mamba / mlstm / slstm, FFNs
are dense SwiGLU or MoE.  Decode carries a per-unit cache pytree (KV cache
for attention, recurrent state for SSM blocks) stacked along the unit axis
through the scan; each block writes only its own rows of it.

Parallelism: activations are batch-sharded; tensor parallelism comes from
weight sharding (pjit propagation); expert parallelism uses the explicit
``shard_map`` paths in ``repro.models.moe`` selected via ``Parallel``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import attention as attn
from . import mla
from . import moe as moe_mod
from . import ssm
from .config import LayerSpec, ModelConfig
from .layers import (dtype_of, embed, embedding_init, ffn_apply, ffn_init,
                     lm_head, normal_init, rmsnorm, rmsnorm_init)


@dataclasses.dataclass(frozen=True)
class Parallel:
    """How a step function should distribute work (None => single shard)."""

    mesh: Mesh | None = None
    data_axes: tuple[str, ...] = ("data",)   # batch axes ("pod","data") multi-pod
    model_axis: str = "model"

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    def resolve_moe(self, cfg: ModelConfig, seq_len: int) -> str:
        if self.mesh is None or self.model_size == 1:
            return "local"
        if cfg.n_experts % self.model_size == 0:
            if seq_len % self.model_size == 0:
                return "ep"        # sequence-sharded all-to-all dispatch
            return "ep_rep"        # replicated-token EP (decode)
        return "local"             # TP over d_ff via weight sharding


# ===================================================================== #
# Parameter init
# ===================================================================== #


def _block_init(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    km, kf = jax.random.split(key)
    p: dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, jnp.float32)}
    if spec.mixer == "attn":
        p["mixer"] = attn.attn_init(km, cfg, dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla.mla_init(km, cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = ssm.mamba_init(km, cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = ssm.mlstm_init(km, cfg, dtype)
    elif spec.mixer == "slstm":
        p["mixer"] = ssm.slstm_init(km, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model, jnp.float32)
        if spec.ffn == "dense":
            p["ffn"] = ffn_init(kf, cfg.d_model, cfg.d_ff, cfg.n_layers, dtype)
        elif spec.ffn == "moe":
            p["ffn"] = moe_mod.moe_init(kf, cfg, dtype)
        else:
            raise ValueError(spec.ffn)
    return p


def _unit_init(key, cfg: ModelConfig, dtype) -> dict:
    keys = jax.random.split(key, len(cfg.pattern))
    return {f"b{i}": _block_init(keys[i], cfg, spec, dtype)
            for i, spec in enumerate(cfg.pattern)}


def n_scan_units(cfg: ModelConfig) -> int:
    return cfg.n_units - (1 if cfg.first_layer_dense else 0)


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    k_emb, k_units, k_first, k_head = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": embedding_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal_init(k_head, (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.first_layer_dense:
        first_cfg = dataclasses.replace(
            cfg, d_ff=(cfg.first_dense_d_ff or cfg.d_ff)
        )
        params["first"] = _block_init(
            k_first, first_cfg, LayerSpec(mixer=cfg.pattern[0].mixer, ffn="dense"),
            dtype,
        )
    unit_keys = jax.random.split(k_units, n_scan_units(cfg))
    params["units"] = jax.vmap(
        functools.partial(_unit_init, cfg=cfg, dtype=dtype)
    )(unit_keys)
    return params


# ===================================================================== #
# Block application (train / prefill / decode share this)
# ===================================================================== #


def _apply_mixer(cfg, spec, bp, x, positions, par, cdt, cache, mode,
                 unit=None):
    """Returns (y, new_cache).

    With ``unit`` (decode inside the layer scan) ``cache`` is the stack
    over units and ``unit`` this block's index in it: attention writes its
    new K/V rows into the stack, a recurrent block its whole state at
    ``unit``.  The stack comes back updated in place.  Latent attention
    (``mla``) writes its latent rows the way attention writes K/V.
    """
    if spec.mixer == "attn":
        if mode == "train":
            return attn.attention_forward(cfg, bp["mixer"], x, positions, cdt), None
        if mode == "prefill":
            return attn.attention_prefill(cfg, bp["mixer"], x, positions, cache, cdt)
        return attn.attention_decode(cfg, bp["mixer"], x, positions, cache, cdt,
                                     unit)
    if spec.mixer == "mla":
        if mode == "train":
            return mla.mla_forward(cfg, bp["mixer"], x, positions, cdt), None
        if mode == "prefill":
            return mla.mla_prefill(cfg, bp["mixer"], x, positions, cache, cdt)
        return mla.mla_decode(cfg, bp["mixer"], x, positions, cache, cdt, unit)
    if spec.mixer in ("mamba", "mlstm"):
        fwd, dec = {"mamba": (ssm.mamba_forward, ssm.mamba_decode),
                    "mlstm": (ssm.mlstm_forward, ssm.mlstm_decode)}[spec.mixer]
        if mode == "train":
            return fwd(cfg, bp["mixer"], x, cdt, par), None
        step = functools.partial(dec, cfg, bp["mixer"], x, compute_dtype=cdt,
                                 par=par)
    else:
        if mode == "train":
            return ssm.slstm_forward(cfg, bp["mixer"], x, cdt), None
        step = functools.partial(ssm.slstm_decode, cfg, bp["mixer"], x,
                                 compute_dtype=cdt)
    if unit is None:
        return step(cache)
    y, state = step(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, unit, keepdims=False), cache))
    return y, jax.tree.map(
        lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s.astype(a.dtype),
                                                         unit, 0),
        cache, state)


def _apply_moe(cfg, bp_ffn, x, par: Parallel, cdt, unit=None):
    mode = par.resolve_moe(cfg, x.shape[1])
    if mode == "local":
        return moe_mod.moe_apply_local(cfg, bp_ffn, x, cdt, unit)
    mesh = par.mesh
    n_data = 1
    for a in par.data_axes:
        n_data *= mesh.shape[a]
    batch_axes = par.data_axes if len(par.data_axes) > 1 else par.data_axes[0]
    if x.shape[0] % n_data != 0:     # e.g. long-context batch=1 decode
        batch_axes = None
    in_params_spec = {k: P(par.model_axis) for k in ("w_gate", "w_up", "w_down")}
    in_params_spec["router"] = P()
    if "shared" in bp_ffn:
        in_params_spec["shared"] = jax.tree.map(lambda _: P(), bp_ffn["shared"])
    aux_spec = {"load_balance_loss": P(), "router_z_loss": P(),
                "expert_counts": P()}

    if mode == "ep":
        # sequence-sharded dispatch: tokens split over the EP axis
        x_spec = P(batch_axes, par.model_axis, None)
        fn = functools.partial(moe_mod.moe_apply_ep, cfg,
                               axis_name=par.model_axis, compute_dtype=cdt)
    elif mode == "ep_rep":
        # replicated tokens (decode): local experts + psum combine
        x_spec = P(batch_axes, None, None)
        fn = functools.partial(moe_mod.moe_apply_ep_replicated, cfg,
                               axis_name=par.model_axis, compute_dtype=cdt)
    else:
        raise ValueError(mode)
    sharded = moe_mod.sharded_moe(
        lambda p, xx: fn(p, x_local=xx),
        mesh=mesh,
        in_specs=(in_params_spec, x_spec),
        out_specs=(x_spec, aux_spec),
    )
    return sharded(bp_ffn, x)


def _apply_block(cfg, spec, bp, x, positions, par, cdt, cache, mode,
                 unit=None, moe_unit=None):
    """One (mixer, ffn) block.  ``moe_unit``: the MoE block's routed
    expert weights are the stacks over every unit and this is its index
    (decode; see ``decode_step``)."""
    aux = None
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps)
    y, new_cache = _apply_mixer(cfg, spec, bp, h, positions, par, cdt, cache,
                                mode, unit)
    x = x + y
    if spec.ffn != "none":
        h = rmsnorm(bp["norm2"], x, cfg.norm_eps)
        if spec.ffn == "dense":
            x = x + ffn_apply(bp["ffn"], h, cdt)
        else:
            out, aux = _apply_moe(cfg, bp["ffn"], h, par, cdt, moe_unit)
            x = x + out
    return x, new_cache, aux


def _apply_unit(cfg, unit_params, x, positions, par, cdt, unit_cache, mode,
                unit=None, experts=None):
    """``experts``: block name -> the routed experts' weight stacks over
    every unit, which that block's MoE reads at ``unit`` (decode)."""
    new_caches = {}
    aux_sum = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((max(cfg.n_experts, 1),), jnp.float32)
    for i, spec in enumerate(cfg.pattern):
        cache_i = None if unit_cache is None else unit_cache.get(f"b{i}")
        bp, moe_unit = unit_params[f"b{i}"], None
        if experts and f"b{i}" in experts:
            bp = {**bp, "ffn": {**bp["ffn"], **experts[f"b{i}"]}}
            moe_unit = unit
        x, nc, aux = _apply_block(
            cfg, spec, bp, x, positions, par, cdt, cache_i, mode, unit,
            moe_unit
        )
        if nc is not None:
            new_caches[f"b{i}"] = nc
        if aux is not None:
            aux_sum = aux_sum + aux["load_balance_loss"] \
                + 1e-3 * aux["router_z_loss"]
            counts = counts + aux["expert_counts"]
    return x, (new_caches or None), aux_sum, counts


# ===================================================================== #
# Full passes
# ===================================================================== #


def _embed_inputs(cfg, params, batch, cdt):
    """batch: dict with 'tokens' (B,S) and/or 'embeds' (B,S_e,d)."""
    parts = []
    if "embeds" in batch:
        parts.append(batch["embeds"].astype(cdt))
    if "tokens" in batch:
        parts.append(embed(params["embed"], batch["tokens"], cdt))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, positions


def forward(cfg: ModelConfig, params: dict, batch: dict,
            par: Parallel = Parallel(), return_router_stats: bool = False):
    """Training forward: returns (logits (B,S,V_padded), aux_loss).

    With ``return_router_stats`` also returns per-unit expert-selection
    counts (n_scan_units, n_experts) — the activation statistics that feed
    the SpaceMoE placement planner (Eq. 14 plug-in).
    """
    cdt = dtype_of(cfg.compute_dtype)
    x, positions = _embed_inputs(cfg, params, batch, cdt)
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.first_layer_dense:
        x, _, aux = _apply_block(
            cfg, LayerSpec(cfg.pattern[0].mixer, "dense"), params["first"],
            x, positions, par, cdt, None, "train",
        )

    def unit_step(carry, unit_params):
        xx, aux_acc = carry
        xx, _, aux, counts = _apply_unit(cfg, unit_params, xx, positions,
                                         par, cdt, None, "train")
        return (xx, aux_acc + aux), counts

    body = unit_step
    if cfg.remat == "unit":
        body = jax.checkpoint(unit_step, prevent_cse=False)
    with jax.named_scope("layers"):
        (x, aux_total), counts = jax.lax.scan(body, (x, aux_total),
                                              params["units"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = lm_head(table, x, cfg.tie_embeddings)
    if return_router_stats:
        return logits, aux_total, counts
    return logits, aux_total


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            par: Parallel = Parallel(), aux_weight: float = 0.01):
    """Next-token cross-entropy (+ MoE aux).  batch['labels']: (B,S) int32,
    -1 => ignore."""
    logits, aux = forward(cfg, params, batch, par)
    labels = batch["labels"]
    s = min(logits.shape[1], labels.shape[1])
    logits = logits[:, -s:].astype(jnp.float32)
    labels = labels[:, -s:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    denom = jnp.maximum(valid.sum(), 1)
    ce = nll.sum() / denom
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------- #
# Decode: cache init / prefill / single-step
# --------------------------------------------------------------------- #


def _block_cache(cfg, spec: LayerSpec, batch: int, max_len: int, cdt):
    if spec.mixer == "attn":
        return attn.init_kv_cache(cfg, batch, max_len, cdt)
    if spec.mixer == "mla":
        return mla.init_latent_cache(cfg, batch, max_len, cdt)
    if spec.mixer == "mamba":
        return ssm.mamba_init_state(cfg, batch)
    if spec.mixer == "mlstm":
        return ssm.mlstm_init_state(cfg, batch)
    if spec.mixer == "slstm":
        return ssm.slstm_init_state(cfg, batch)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked decode cache: every leaf has leading dim n_scan_units."""
    cdt = dtype_of(cfg.compute_dtype)
    unit = {f"b{i}": _block_cache(cfg, spec, batch, max_len, cdt)
            for i, spec in enumerate(cfg.pattern)}
    n = n_scan_units(cfg)
    stacked = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf[None], (n, *leaf.shape)), unit
    )
    out = {"units": stacked}
    if cfg.first_layer_dense:
        out["first"] = _block_cache(cfg, cfg.pattern[0], batch, max_len, cdt)
    return out


def _expert_stacks(cfg: ModelConfig, units: dict) -> tuple[dict, dict]:
    """Split the routed experts' weight stacks of every MoE block out of
    the stacked unit params: (the rest, block name -> its stacks)."""
    rest, stacks = dict(units), {}
    for i, spec in enumerate(cfg.pattern):
        if spec.ffn == "moe":
            b = f"b{i}"
            ffn = dict(units[b]["ffn"])
            stacks[b] = {k: ffn.pop(k) for k in ("w_gate", "w_up", "w_down")}
            rest[b] = {**units[b], "ffn": ffn}
    return rest, stacks


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                tokens: jnp.ndarray, pos: jnp.ndarray,
                par: Parallel = Parallel(), embeds: jnp.ndarray | None = None):
    """One autoregressive step.

    tokens: (B, 1) int32 (or ``embeds`` (B, 1, d) for stub frontends);
    pos: (B,) positions of these tokens.  Returns (logits (B, V), cache').

    The layer scan runs over (unit params, unit index) and carries
    ``(x, cache["units"])``: each attention block writes its B new K/V rows
    into the carried stack at its unit index and attends over that layer
    of it; each recurrent block writes its whole state at the index.  The
    returned stack is the final carry, so a donated cache is updated in
    place rather than rebuilt layer by layer.

    Where the step's tokens cannot hit every routed expert
    (``moe.reads_hit_experts``, single shard), the MoE blocks' expert
    weight stacks stay out of the scanned unit params: the scan closes
    over them and each layer reads its hit experts from the whole stacks
    at its unit index, as attention reads its cache.
    """
    cdt = dtype_of(cfg.compute_dtype)
    if embeds is not None:
        x = embeds.astype(cdt)
    else:
        x = embed(params["embed"], tokens, cdt)
    new_cache: dict = {}
    if cfg.first_layer_dense:
        x, fc, _ = _apply_block(
            cfg, LayerSpec(cfg.pattern[0].mixer, "dense"), params["first"],
            x, pos, par, cdt, cache["first"], "decode",
        )
        new_cache["first"] = fc

    unit_params, experts = params["units"], None
    if par.mesh is None and moe_mod.reads_hit_experts(
            cfg, x.shape[0] * x.shape[1]):
        unit_params, experts = _expert_stacks(cfg, unit_params)

    def unit_step(carry, xs):
        x, caches = carry
        unit_params, unit = xs
        x, caches, _, _ = _apply_unit(cfg, unit_params, x, pos, par, cdt,
                                      caches, "decode", unit, experts)
        return (x, caches), None

    units = jnp.arange(n_scan_units(cfg), dtype=jnp.int32)
    with jax.named_scope("layers"):
        (x, new_cache["units"]), _ = jax.lax.scan(
            unit_step, (x, cache["units"]), (unit_params, units)
        )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = lm_head(table, x, cfg.tie_embeddings)
    return logits[:, 0, :], new_cache


def prefill(cfg: ModelConfig, params: dict, batch: dict, max_len: int,
            par: Parallel = Parallel()):
    """Run the prompt through the stack, returning (last-token logits, cache).

    Attention blocks write K/V for positions [0, S); recurrent blocks carry
    their final state.
    """
    cdt = dtype_of(cfg.compute_dtype)
    x, positions = _embed_inputs(cfg, params, batch, cdt)
    b, s = x.shape[0], x.shape[1]
    cache = init_cache(cfg, b, max_len)
    new_cache: dict = {}
    if cfg.first_layer_dense:
        x, fc, _ = _apply_block(
            cfg, LayerSpec(cfg.pattern[0].mixer, "dense"), params["first"],
            x, positions, par, cdt, cache["first"], "prefill",
        )
        new_cache["first"] = fc

    def unit_step(x, xs):
        unit_params, unit_cache = xs
        x, nc, _, _ = _apply_unit(cfg, unit_params, x, positions, par, cdt,
                                  unit_cache, "prefill")
        return x, nc

    with jax.named_scope("layers"):
        x, new_unit_caches = jax.lax.scan(
            unit_step, x, (params["units"], cache["units"])
        )
    new_cache["units"] = new_unit_caches
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = lm_head(table, x[:, -1:, :], cfg.tie_embeddings)
    return logits[:, 0, :], new_cache
