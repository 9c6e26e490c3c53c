"""Sizes of a latent-attention (MLA) mixture-of-experts model, read from
its config file (DeepSeek-V2's ``config.json`` key names).

``model_dims.py`` reads the models with grouped-query attention; this
module reads the MLA ones into one set of plain numbers that the MLA
reference, work counts and driver share.  ``n_routed_experts`` in the file
is the share of experts this chip holds; the router's width is the
published count (``published.n_routed_experts``).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MlaDims:
    d_model: int
    n_layers: int               # served depth, dense layers included
    n_dense: int                # leading dense layers
    d_ff_dense: int
    n_experts: int              # router outputs (the published count)
    n_held: int                 # routed experts held: ids 0..n_held-1
    top_k: int
    d_ff_expert: int
    n_shared: int               # shared experts, each d_ff_expert wide
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    vocab: int
    rope_theta: float
    yarn_factor: float          # YaRN rotary scaling
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_eps: float
    tied: bool
    norm_topk: bool
    #: The GQA reference's head reads it; MLA models scale no logits.
    logits_scaling: float = 1.0

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def latent_dim(self) -> int:
        """One latent-cache row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope


def dims(c: dict) -> MlaDims:
    """The sizes as run: the published values, with the departures the
    file lists under ``assumed`` (values the program has no option for)."""
    c = {**c, **c.get("assumed", {}).get("departures", {}).get("run", {})}
    if c.get("q_lora_rank"):
        raise ValueError(f"{c['name']}: compressed queries (q_lora_rank) "
                         "are not supported")
    rs = c.get("rope_scaling") or {}
    if rs.get("type") != "yarn":
        raise ValueError(f"{c['name']}: rope_scaling {rs.get('type')!r}; "
                         "MLA models here use YaRN")
    n_dense = c.get("first_k_dense_replace", 0)
    held = c["n_routed_experts"]
    return MlaDims(
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_dense=n_dense,
        d_ff_dense=c["intermediate_size"] if n_dense else 0,
        n_experts=c.get("published", {}).get("n_routed_experts", held),
        n_held=held,
        top_k=c["num_experts_per_tok"],
        d_ff_expert=c["moe_intermediate_size"],
        n_shared=c.get("n_shared_experts", 0),
        n_heads=c["num_attention_heads"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope=c["qk_nope_head_dim"],
        qk_rope=c["qk_rope_head_dim"],
        v_head=c["v_head_dim"],
        vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original_max=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]),
        norm_topk=bool(c["norm_topk_prob"]),
    )
