"""Mixture-of-Experts layer (paper Sec. III-C) with placement-aware layout.

Routing follows the paper: softmax gate scores (Eq. 11), top-K selection,
combine weights normalized over the active set (Eq. 15).  Dispatch uses a
sort+gather formulation (megablocks-style, capacity-padded): memory is
O(tokens * K * d), never O(tokens * E * C) like the classic GShard one-hot
einsum — that is what makes 64-expert configs viable.

Execution paths
---------------
``Parallel.resolve_moe`` (``repro.models.model``) picks one from the mesh
and the shapes alone:

- ``moe_apply_local``: single-shard math, the served path and the oracle
  for tests.  With a held share (``cfg.n_experts_held``) it is one
  device's part of an expert-parallel layer, without the exchange.
  Inside the decode layer scan, where a step's T·K token copies cannot
  hit every expert (``reads_hit_experts``), it computes only the experts
  hit and reads only their weights, from the whole stacks (on a TPU the
  Pallas kernel ``kernels/moe_decode.py``).
- ``moe_apply_ep``: expert parallelism inside ``shard_map`` — tokens are
  sequence-sharded over the EP axis, buckets travel via ``lax.all_to_all``,
  each device runs its local expert group, and a reverse all-to-all brings
  results home.  Needs E % |EP axis| == 0 and S % |EP axis| == 0.
- ``moe_apply_ep_replicated``: the same expert split for tokens
  replicated over the EP axis (decode, where S does not divide over it):
  each device adds its experts' part and one psum combines.
- TP fallback for E not divisible by the axis (e.g. granite's 40 experts on
  16 devices): ``moe_apply_local`` with the experts' d_ff sharded over the
  axis by the weight sharding; partial outputs are psum-reduced.

SpaceMoE placement enters as a *checkpoint transform*: ``apply_placement``
permutes the stacked expert weights and the router's output columns so
that EP slot s holds the expert Theorem 1 assigns there — zero runtime
cost, identical math (router logits are permuted consistently).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import axis_size, shard_map
from ..kernels import ops
from .config import ModelConfig
from .layers import normal_init, out_proj_init


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    """A MoE layer's weights: the router over all ``n_experts``, and the
    stacks of the experts held (``cfg.experts_held``) and shared."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, d, f = cfg.experts_held, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": normal_init(kr, (d, cfg.n_experts), jnp.float32),  # fp32
        "w_gate": normal_init(kg, (e, d, f), dtype),
        "w_up": normal_init(ku, (e, d, f), dtype),
        "w_down": out_proj_init(kd, (e, f, d), dtype, cfg.n_layers),
    }
    if cfg.n_shared_experts > 0:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks, 3)
        p["shared"] = {
            "w_gate": normal_init(k1, (d, fs), dtype),
            "w_up": normal_init(k2, (d, fs), dtype),
            "w_down": out_proj_init(k3, (fs, d), dtype, cfg.n_layers),
        }
    return p


# --------------------------------------------------------------------- #
# Routing (Eq. 11 + top-K + Eq. 15 combine weights)
# --------------------------------------------------------------------- #


@jax.named_scope("moe.router")
def route(cfg: ModelConfig, router_w: jnp.ndarray, x: jnp.ndarray):
    """x: (T, d) -> (weights (T,K), idx (T,K) int32, aux dict)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)        # Eq. 15
    # Switch-style load-balance loss + router z-loss.
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                    # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[top_i.ravel()].add(
        jnp.ones_like(top_i.ravel(), jnp.float32)
    ) / (top_i.size)
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "expert_counts": ce,
    }
    return weights, top_i.astype(jnp.int32), aux


# --------------------------------------------------------------------- #
# Sort + gather dispatch to capacity-padded (E, C, d) buckets
# --------------------------------------------------------------------- #


def capacity(cfg: ModelConfig, n_tokens: int, n_buckets: int) -> int:
    c = int(np.ceil(cfg.capacity_factor * n_tokens * cfg.top_k / n_buckets))
    return max(c, cfg.top_k)


def dispatch_indices(idx: jnp.ndarray, n_experts: int, cap: int):
    """Compute the gather plan mapping (E, C) slots to token copies.

    idx: (T, K) expert choice per token copy.  Returns
      slot_token: (E*C,) index into the flattened (T*K,) copy list
                  (arbitrary valid index where unfilled),
      slot_valid: (E*C,) bool — slot actually holds a token,
      copy_slot:  (T*K,) slot of each copy (E*C where dropped),
      copy_kept:  (T*K,) bool.
    """
    tk = idx.size
    flat = idx.reshape(-1)                                   # (T*K,)
    order = jnp.argsort(flat, stable=True)                   # sort copies by expert
    sorted_e = flat[order]
    # position within expert = rank among same-expert copies
    pos_in_e = jnp.arange(tk) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    kept = pos_in_e < cap
    slot_of_sorted = sorted_e * cap + pos_in_e               # (T*K,)
    # Dropped copies target slot E*C (out of bounds) and are discarded by
    # the scatter's mode="drop"; no valid slot is ever overwritten.
    tgt = jnp.where(kept, slot_of_sorted, n_experts * cap)
    slot_token = jnp.zeros((n_experts * cap,), jnp.int32).at[tgt].set(
        order.astype(jnp.int32), mode="drop"
    )
    slot_valid = jnp.zeros((n_experts * cap,), bool).at[tgt].set(
        True, mode="drop"
    )
    copy_slot = jnp.zeros((tk,), jnp.int32).at[order].set(
        jnp.where(kept, slot_of_sorted, 0).astype(jnp.int32)
    )
    copy_kept = jnp.zeros((tk,), bool).at[order].set(kept)
    return slot_token, slot_valid, copy_slot, copy_kept


@jax.named_scope("moe.experts")
def expert_ffn(params: dict, xs: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """Batched SwiGLU over expert buckets.  xs: (E, C, d) -> (E, C, d)."""
    wg = params["w_gate"].astype(compute_dtype)
    wu = params["w_up"].astype(compute_dtype)
    wd = params["w_down"].astype(compute_dtype)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg))
    up = jnp.einsum("ecd,edf->ecf", xs, wu)
    return jnp.einsum("ecf,efd->ecd", gate * up, wd)


def reads_hit_experts(cfg: ModelConfig, n_tokens: int) -> bool:
    """Whether a decode step of ``n_tokens`` tokens computes only the
    routed experts its copies hit (``moe_apply_local`` with ``unit``): a
    full layer, not a held share, whose T·K copies cannot hit every
    expert, so that at least E - T·K experts' weights go unread."""
    return not cfg.held_share and n_tokens * cfg.top_k < cfg.n_experts


def hit_experts(idx: jnp.ndarray, n_experts: int):
    """The experts that the token copies ``idx`` (T, K) are routed to:
    their ids ascending in min(E, T·K) slots, the slots past the last hit
    repeating it, and how many there are."""
    iota = jnp.arange(n_experts, dtype=jnp.int32)
    hit = jnp.zeros((n_experts,), bool).at[idx.reshape(-1)].set(True)
    ids = jnp.sort(jnp.where(hit, iota, n_experts))[:min(n_experts, idx.size)]
    return (jnp.minimum(ids, jnp.max(jnp.where(hit, iota, 0))),
            hit.sum(dtype=jnp.int32))


def _expert_ffn_hit(stacks: dict, buckets: jnp.ndarray, idx: jnp.ndarray,
                    unit, compute_dtype):
    """``expert_ffn`` of layer ``unit`` of the (U, E, ...) weight
    ``stacks`` over the buckets (E, C, d), for the experts ``idx`` hits:
    on a TPU the Pallas kernel, which reads only those experts' weights,
    elsewhere ``expert_ffn`` over the layer's slice (its oracle).  Returns
    the outputs, zero for experts not hit, and how many experts' weights
    were read."""
    e = buckets.shape[0]
    with jax.named_scope("moe.dispatch"):
        ids, n_hit = hit_experts(idx, e)
    names = ("w_gate", "w_up", "w_down")
    weights = tuple(stacks[k] for k in names)

    def kernel(buckets, unit, ids, n_hit, *weights):
        return ops.expert_ffn_hit(buckets, *weights, unit, ids, n_hit,
                                  interpret=False), n_hit

    def einsum(buckets, unit, ids, n_hit, *weights):
        layer = {k: jax.lax.dynamic_index_in_dim(w, unit, keepdims=False)
                 for k, w in zip(names, weights)}
        return expert_ffn(layer, buckets, compute_dtype), jnp.int32(e)

    with jax.named_scope("moe.experts"):
        return jax.lax.platform_dependent(buckets, unit, ids, n_hit,
                                          *weights, tpu=kernel,
                                          default=einsum)


@jax.named_scope("moe.shared")
def _shared_ffn(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    g = jax.nn.silu(x @ params["w_gate"].astype(compute_dtype))
    u = x @ params["w_up"].astype(compute_dtype)
    return (g * u) @ params["w_down"].astype(compute_dtype)


@jax.named_scope("moe.dispatch")
def _dispatch(xt: jnp.ndarray, idx: jnp.ndarray, n_buckets: int, cap: int,
              compute_dtype):
    """Gather each token copy into its capacity-padded bucket.

    Returns the (n_buckets * cap, d) buckets and the plan ``(copy_slot,
    copy_kept)`` that ``_combine`` brings the outputs home with."""
    slot_token, slot_valid, copy_slot, copy_kept = dispatch_indices(
        idx, n_buckets, cap
    )
    copies = jnp.repeat(xt, idx.shape[1], axis=0)
    buckets = copies[slot_token] * slot_valid[:, None].astype(compute_dtype)
    return buckets, (copy_slot, copy_kept)


@jax.named_scope("moe.combine")
def _combine(flat_out: jnp.ndarray, plan, weights: jnp.ndarray,
             compute_dtype) -> jnp.ndarray:
    """(n_buckets * cap, d) bucket outputs -> (T, d): gather each copy's
    output, weight top-K."""
    copy_slot, copy_kept = plan
    t, k = weights.shape
    gathered = flat_out[copy_slot] * copy_kept[:, None].astype(compute_dtype)
    per_copy = gathered.reshape(t, k, -1)
    return jnp.einsum("tkd,tk->td", per_copy, weights.astype(compute_dtype))


def _group_part(params: dict, xt: jnp.ndarray, weights: jnp.ndarray,
                idx: jnp.ndarray, group, cap: int,
                compute_dtype) -> jnp.ndarray:
    """The part of the routed sum that one group of experts gives: the
    ``loc`` stacked in ``params`` are experts ``[group * loc, (group + 1)
    * loc)``.  Every token is routed; copies bound for other groups go to
    a trash bucket (local id ``loc``) whose output is zero.  ``group`` is
    the device's index on the expert-parallel axis, or 0 for the share of
    experts one chip holds."""
    d = xt.shape[1]
    loc = params["w_gate"].shape[0]
    is_mine = (idx // loc) == group
    local_idx = jnp.where(is_mine, idx - group * loc, loc)
    buckets, plan = _dispatch(xt, local_idx, loc + 1, cap, compute_dtype)
    buckets = buckets.reshape(loc + 1, cap, d)
    outs = expert_ffn(params, buckets[:loc], compute_dtype)
    outs = jnp.concatenate(
        [outs, jnp.zeros((1, cap, d), outs.dtype)], axis=0
    )                                                   # zero trash bucket
    return _combine(outs.reshape((loc + 1) * cap, d), plan, weights,
                    compute_dtype)


def moe_apply_local(cfg: ModelConfig, params: dict, x: jnp.ndarray,
                    compute_dtype, unit=None) -> tuple[jnp.ndarray, dict]:
    """Single-shard MoE: x (B, S, d) -> (B, S, d).  Test oracle + CPU path.

    With a held share (``cfg.n_experts_held`` < ``n_experts``) the layer is
    one device's body of expert parallelism without the exchange: it routes
    over all experts and adds only its held experts' part of the routed
    sum (group 0, the first ``n_experts_held``), plus the shared experts.

    With ``unit`` (the decode layer scan, where ``reads_hit_experts``)
    the routed experts' weights in ``params`` are the stacks over every
    unit, (U, E, ...), and ``unit`` is this layer's index in them; only
    the experts hit are computed (``_expert_ffn_hit``).  The aux dict's
    ``experts_read`` counts the experts whose weights the layer read.
    """
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    xt = x.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)
    cap = capacity(cfg, t, e)
    aux["experts_read"] = jnp.int32(cfg.experts_held)
    if cfg.held_share:
        y = _group_part(params, xt, weights, idx, 0, cap, compute_dtype)
    else:
        buckets, plan = _dispatch(xt, idx, e, cap, compute_dtype)
        buckets = buckets.reshape(e, cap, d)
        if unit is None:
            outs = expert_ffn(params, buckets, compute_dtype)
        else:
            outs, aux["experts_read"] = _expert_ffn_hit(
                params, buckets, idx, unit, compute_dtype)
        y = _combine(outs.reshape(e * cap, d), plan, weights, compute_dtype)
    if cfg.n_shared_experts > 0:
        y = y + _shared_ffn(params["shared"], xt, compute_dtype)
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------- #
# Expert-parallel path (runs inside shard_map over the EP axis)
# --------------------------------------------------------------------- #


def sharded_moe(fn, mesh, in_specs, out_specs):
    """Wrap an EP body (``moe_apply_ep`` / ``moe_apply_ep_replicated``
    partial) in ``shard_map`` via the version-compat shim.

    Replication checking is disabled: the aux outputs are per-shard sums
    the caller combines, which the checker would reject as unreplicated.
    """
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def moe_apply_ep(cfg: ModelConfig, params: dict, x_local: jnp.ndarray,
                 axis_name: str, compute_dtype) -> tuple[jnp.ndarray, dict]:
    """EP MoE body. ``x_local``: this shard's (B_loc, S_loc, d) slice; the
    stacked expert params carry only the local expert group (E_loc, ...).

    Pipeline: route -> bucket by *global* expert slot -> all_to_all (split
    by owner device) -> local expert FFN -> reverse all_to_all -> combine.
    """
    n_dev = axis_size(axis_name)
    b, s, d = x_local.shape
    t = b * s
    e = cfg.n_experts
    loc = params["w_gate"].shape[0]          # local experts
    if e != loc * n_dev:
        raise ValueError(f"bucket count {e} != {loc}x{n_dev} local stacks")
    xt = x_local.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)
    cap = capacity(cfg, t, e)

    buckets, plan = _dispatch(xt, idx, e, cap, compute_dtype)
    buckets = buckets.reshape(n_dev, loc, cap, d)             # dest-device major

    # exchange buckets: after a2a, axis 0 indexes the *source* device.
    recv = jax.lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(n_dev, loc, cap, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(loc, n_dev * cap, d)
    outs = expert_ffn(params, recv, compute_dtype)            # (loc, n*C, d)
    back = outs.reshape(loc, n_dev, cap, d).transpose(1, 0, 2, 3)
    home = jax.lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    y = _combine(home.reshape(e * cap, d), plan, weights, compute_dtype)
    if cfg.n_shared_experts > 0:
        y = y + _shared_ffn(params["shared"], xt, compute_dtype)
    return y.reshape(b, s, d), aux


def moe_apply_ep_replicated(cfg: ModelConfig, params: dict,
                            x_local: jnp.ndarray, axis_name: str,
                            compute_dtype) -> tuple[jnp.ndarray, dict]:
    """EP for replicated activations (decode path).

    Tokens are identical on every device of the EP axis (the usual decode
    layout: batch over data, activations replicated over model).  Each
    device routes all tokens but computes only its local expert group; a
    single psum combines.  Communication = one all-reduce of (T, d) —
    no all-to-all, which is the right trade at S=1.
    """
    n_dev = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s, d = x_local.shape
    t = b * s
    e = cfg.n_experts
    loc = params["w_gate"].shape[0]
    if e != loc * n_dev:
        raise ValueError(f"bucket count {e} != {loc}x{n_dev} local stacks")
    xt = x_local.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)

    y = _group_part(params, xt, weights, idx, my, capacity(cfg, t, e),
                    compute_dtype)
    y = jax.lax.psum(y, axis_name)
    if cfg.n_shared_experts > 0:
        y = y + _shared_ffn(params["shared"], xt, compute_dtype)  # replicated
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------- #
# SpaceMoE placement as a checkpoint transform
# --------------------------------------------------------------------- #


def apply_placement(moe_params: dict, slot_to_expert: np.ndarray) -> dict:
    """Permute a MoE layer's weights so EP slot s hosts expert
    ``slot_to_expert[s]`` (a ``DevicePlacementPlan.expert_perm``).

    The router columns are permuted identically, so routing semantics are
    unchanged: logits[slot] == original logits[slot_to_expert[slot]].
    """
    perm = jnp.asarray(slot_to_expert)
    out = dict(moe_params)
    out["router"] = moe_params["router"][:, perm]
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = moe_params[name][perm]
    return out
