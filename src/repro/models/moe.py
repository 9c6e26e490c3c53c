"""Mixture-of-Experts layer (paper Sec. III-C) with placement-aware layout.

Routing follows the paper: softmax gate scores (Eq. 11), top-K selection,
combine weights normalized over the active set (Eq. 15).  Dispatch uses a
sort+gather formulation (megablocks-style, capacity-padded): memory is
O(tokens * K * d), never O(tokens * E * C) like the classic GShard one-hot
einsum — that is what makes 64-expert configs viable.

Execution paths
---------------
- ``moe_apply_local``: single-shard math (also the oracle for tests).
- ``moe_apply_ep``: expert parallelism inside ``shard_map`` — tokens are
  sequence-sharded over the EP axis, buckets travel via ``lax.all_to_all``,
  each device runs its local expert group, and a reverse all-to-all brings
  results home.  Requires E % |EP axis| == 0.
- TP fallback for E not divisible by the axis (e.g. granite's 40 experts on
  16 devices): experts' d_ff is sharded over the axis instead and partial
  outputs are psum-reduced; selected automatically by the model layer.

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
from .config import ModelConfig
from .layers import normal_init, out_proj_init


# --------------------------------------------------------------------- #
# EP slotting (perf feature; paper Sec. VI-B multi-expert rule on devices)
#
# The EP all-to-all path needs the expert-stack's leading dim to divide the
# EP axis.  Slotting makes that true for ANY expert count by re-laying the
# stack into "virtual slots":
#   E >= S:  pad with dummy experts to the next multiple of S
#            (granite: 40 -> 48, 3 slots/device; dummies get no tokens);
#   E <  S:  fragment each expert's d_ff into S/E' slices after padding E
#            to a divisor of S (llama-moe: 8 experts x 2 half-experts = 16
#            slots; fragment outputs sum to the exact expert output).
# Without slotting these configs fall back to TP over d_ff, whose
# all-reduces made granite/llama-moe train cells collective-bound by ~100x
# (see EXPERIMENTS.md §Perf).
# --------------------------------------------------------------------- #
import dataclasses


@dataclasses.dataclass(frozen=True)
class Slotting:
    n_experts: int
    n_slots: int       # EP axis size the layout targets
    frag: int          # d_ff fragments per expert
    e_pad: int         # padded expert count (>= n_experts)

    @property
    def n_virtual(self) -> int:
        return self.e_pad * self.frag


def make_slotting(n_experts: int, n_slots: int) -> Slotting:
    if n_experts >= n_slots:
        e_pad = -(-n_experts // n_slots) * n_slots
        return Slotting(n_experts, n_slots, 1, e_pad)
    e_pad = n_experts
    while n_slots % e_pad:
        e_pad += 1
    return Slotting(n_experts, n_slots, n_slots // e_pad, e_pad)


def slotting_for(cfg: ModelConfig) -> Slotting | None:
    if not getattr(cfg, "moe_slotting", False) or cfg.n_experts == 0:
        return None
    return make_slotting(cfg.n_experts, cfg.moe_ep_slots)


def slotted_weights(w_gate, w_up, w_down, sl: Slotting):
    """Canonical (E,d,f)/(E,f,d) stacks -> virtual (V,d,f/frag)/(V,f/frag,d)."""
    e, d, f = w_gate.shape
    if f % sl.frag:
        raise ValueError(f"d_ff_expert={f} not divisible by frag={sl.frag}")
    pad = sl.e_pad - e
    if pad:
        w_gate = jnp.concatenate([w_gate, jnp.zeros((pad, d, f), w_gate.dtype)])
        w_up = jnp.concatenate([w_up, jnp.zeros((pad, d, f), w_up.dtype)])
        w_down = jnp.concatenate([w_down, jnp.zeros((pad, f, d), w_down.dtype)])
    fs = f // sl.frag
    # (E', d, f) -> (E', frag, d, fs) -> (V, d, fs), slot-major per expert
    wg = w_gate.reshape(sl.e_pad, d, sl.frag, fs).transpose(0, 2, 1, 3) \
        .reshape(sl.n_virtual, d, fs)
    wu = w_up.reshape(sl.e_pad, d, sl.frag, fs).transpose(0, 2, 1, 3) \
        .reshape(sl.n_virtual, d, fs)
    wd = w_down.reshape(sl.e_pad, sl.frag, fs, d).reshape(sl.n_virtual, fs, d)
    return wg, wu, wd


def virtual_indices(idx: jnp.ndarray, sl: Slotting) -> jnp.ndarray:
    """(T, K) expert ids -> (T, K*frag) virtual slot ids."""
    frag_ids = jnp.arange(sl.frag, dtype=idx.dtype)
    v = idx[..., None] * sl.frag + frag_ids          # (T, K, frag)
    return v.reshape(idx.shape[0], -1)


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": normal_init(kr, (d, e), jnp.float32),  # router kept fp32
        "w_gate": normal_init(kg, (e, d, f), dtype),
        "w_up": normal_init(ku, (e, d, f), dtype),
        "w_down": out_proj_init(kd, (e, f, d), dtype, cfg.n_layers),
    }
    sl = slotting_for(cfg)
    if sl is not None:
        p["w_gate"], p["w_up"], p["w_down"] = slotted_weights(
            p["w_gate"], p["w_up"], p["w_down"], sl
        )
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


@jax.named_scope("moe.shared")
def _shared_ffn(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    g = jax.nn.silu(x @ params["w_gate"].astype(compute_dtype))
    u = x @ params["w_up"].astype(compute_dtype)
    return (g * u) @ params["w_down"].astype(compute_dtype)


def _plan(cfg: ModelConfig, idx: jnp.ndarray, t: int):
    """Virtual-slot dispatch plan: (v_idx, n_buckets, cap, frag)."""
    sl = slotting_for(cfg)
    if sl is None:
        return idx, cfg.n_experts, capacity(cfg, t, cfg.n_experts), 1
    return (virtual_indices(idx, sl), sl.n_virtual,
            capacity(cfg, t, sl.e_pad), sl.frag)


@jax.named_scope("moe.dispatch")
def _dispatch(xt: jnp.ndarray, idx: jnp.ndarray, n_buckets: int, cap: int,
              copies_per_token: int, compute_dtype):
    """Gather each token copy into its capacity-padded bucket.

    Returns the (n_buckets * cap, d) buckets and the plan ``(copy_slot,
    copy_kept)`` that ``_combine`` brings the outputs home with."""
    slot_token, slot_valid, copy_slot, copy_kept = dispatch_indices(
        idx, n_buckets, cap
    )
    copies = jnp.repeat(xt, copies_per_token, axis=0)
    buckets = copies[slot_token] * slot_valid[:, None].astype(compute_dtype)
    return buckets, (copy_slot, copy_kept)


@jax.named_scope("moe.combine")
def _combine(flat_out: jnp.ndarray, plan, weights: jnp.ndarray, t: int,
             k: int, frag: int, compute_dtype) -> jnp.ndarray:
    """(n_buckets * cap, d) bucket outputs -> (T, d): gather each copy's
    output, sum fragments, weight top-K."""
    copy_slot, copy_kept = plan
    gathered = flat_out[copy_slot] * copy_kept[:, None].astype(compute_dtype)
    per_copy = gathered.reshape(t, k, frag, -1).sum(axis=2)
    return jnp.einsum("tkd,tk->td", per_copy, weights.astype(compute_dtype))


def moe_apply_local(cfg: ModelConfig, params: dict, x: jnp.ndarray,
                    compute_dtype) -> tuple[jnp.ndarray, dict]:
    """Single-shard MoE: x (B, S, d) -> (B, S, d).  Test oracle + CPU path."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)
    v_idx, n_b, cap, frag = _plan(cfg, idx, t)
    buckets, plan = _dispatch(xt, v_idx, n_b, cap, cfg.top_k * frag,
                              compute_dtype)
    outs = expert_ffn(params, buckets.reshape(n_b, cap, d), compute_dtype)
    y = _combine(outs.reshape(n_b * cap, d), plan, weights, t, cfg.top_k,
                 frag, compute_dtype)
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
    loc = params["w_gate"].shape[0]          # local buckets (experts/slots)
    xt = x_local.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)
    v_idx, n_b, cap, frag = _plan(cfg, idx, t)
    if n_b != loc * n_dev:
        raise ValueError(f"bucket count {n_b} != {loc}x{n_dev} local stacks")

    buckets, plan = _dispatch(xt, v_idx, n_b, cap, cfg.top_k * frag,
                              compute_dtype)
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
    y = _combine(home.reshape(n_b * cap, d), plan, weights, t, cfg.top_k,
                 frag, compute_dtype)
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
    loc = params["w_gate"].shape[0]
    xt = x_local.reshape(t, d).astype(compute_dtype)
    weights, idx, aux = route(cfg, params["router"], xt)
    v_idx, n_b, cap, frag = _plan(cfg, idx, t)
    if n_b != loc * n_dev:
        raise ValueError(f"bucket count {n_b} != {loc}x{n_dev} local stacks")

    # Map global bucket ids to local ids; foreign copies go to a trash
    # bucket (local id loc) whose output is forced to zero.
    is_mine = (v_idx // loc) == my
    local_idx = jnp.where(is_mine, v_idx - my * loc, loc)
    buckets, plan = _dispatch(xt, local_idx, loc + 1, cap, cfg.top_k * frag,
                              compute_dtype)
    buckets = buckets.reshape(loc + 1, cap, d)
    outs = expert_ffn(params, buckets[:loc], compute_dtype)
    outs = jnp.concatenate(
        [outs, jnp.zeros((1, cap, d), outs.dtype)], axis=0
    )                                                   # zero trash bucket
    y = _combine(outs.reshape((loc + 1) * cap, d), plan, weights, t,
                 cfg.top_k, frag, compute_dtype)
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
