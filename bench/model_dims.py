"""Sizes of a mixture-of-experts language model, read from its config file.

Config files under ``bench/configs`` keep the key names of the model's own
published ``config.json`` (DeepSeek-MoE and GraniteMoE name the same sizes
differently); this module reads either into one set of plain numbers that
the reference, the work counts and the driver share.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int               # served depth, dense layers included
    n_dense: int                # leading dense layers
    d_ff_dense: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int               # shared experts, each d_ff_expert wide
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    norm_topk: bool
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense


def dims(c: dict) -> Dims:
    """The sizes as run: the published values, with the departures the
    file lists under ``assumed`` (values the program has no option for)."""
    c = {**c, **c.get("assumed", {}).get("departures", {}).get("run", {})}
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    hd = c.get("head_dim") or d // heads
    n_exp = c.get("n_routed_experts", c.get("num_local_experts"))
    n_dense = c.get("first_k_dense_replace", 0)
    return Dims(
        d_model=d,
        n_layers=c["num_hidden_layers"],
        n_dense=n_dense,
        d_ff_dense=c["intermediate_size"] if n_dense else 0,
        n_experts=n_exp,
        top_k=c["num_experts_per_tok"],
        d_ff_expert=c.get("moe_intermediate_size", c["intermediate_size"]),
        n_shared=c.get("n_shared_experts", 0),
        n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=hd,
        vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]),
        norm_topk=bool(c["norm_topk_prob"]),
        embedding_multiplier=float(c.get("embedding_multiplier", 1.0)),
        attention_multiplier=float(c.get("attention_multiplier",
                                         hd ** -0.5)),
        residual_multiplier=float(c.get("residual_multiplier", 1.0)),
        logits_scaling=float(c.get("logits_scaling", 1.0)),
    )
