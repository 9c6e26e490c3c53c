"""Sizes of a hybrid Mamba-2 / attention mixture-of-experts model, read
from its config file (GraniteMoeHybrid's ``config.json`` key names).

``model_dims.py`` reads the models whose every mixer is attention and
``mla_dims.py`` the latent-attention ones; this module reads the hybrid
ones into one set of plain numbers that the hybrid reference, work counts
and driver share.  ``num_local_experts`` in the file is the share of
experts this chip holds; the router's width is the published count
(``published.num_local_experts``).  The served layers are the first
``num_hidden_layers`` of the published ``layer_types``.
"""
from __future__ import annotations

import dataclasses

#: Mixer of each ``layer_types`` entry.
KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class HybridDims:
    d_model: int
    n_layers: int               # served depth
    layer_types: tuple[str, ...]  # one of KINDS a served layer
    n_experts: int              # router outputs (the published count)
    n_held: int                 # routed experts held: ids 0..n_held-1
    top_k: int
    d_ff_expert: int
    n_shared: int               # shared experts, each d_ff_expert wide
    n_heads: int                # attention
    n_kv_heads: int
    head_dim: int
    ssm_heads: int              # Mamba-2
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_kernel: int
    ssm_chunk: int
    vocab: int
    norm_eps: float
    tied: bool
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    #: GraniteMoE softmaxes the top-k router logits: the renormalised
    #: top-k softmax.
    norm_topk: bool = True
    #: No leading dense layers (the GQA reference's globals read it).
    n_dense: int = 0

    @property
    def n_moe(self) -> int:
        return self.n_layers

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Convolved channels: x, then B and C of every group."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def dims(c: dict) -> HybridDims:
    """The sizes as run: the file's values, which the program runs
    without departures."""
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    hd = c.get("head_dim") or d // heads
    n = c["num_hidden_layers"]
    kinds = tuple(c["layer_types"][:n])
    shared, width = c["shared_intermediate_size"], c["intermediate_size"]
    unsupported = {
        "layer_types other than mamba and attention":
            not set(kinds) <= set(KINDS),
        "position_embedding_type other than nope":
            c["position_embedding_type"] != "nope",
        "mamba_d_head x mamba_n_heads other than mamba_expand x hidden_size":
            c["mamba_d_head"] * c["mamba_n_heads"] != c["mamba_expand"] * d,
        "a shared width that is not a whole number of experts":
            shared % width != 0,
        "mamba_conv_bias false": not c["mamba_conv_bias"],
        "mamba_proj_bias true": c["mamba_proj_bias"],
        "attention_bias true": c["attention_bias"],
        "tie_word_embeddings false": not c["tie_word_embeddings"],
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(f"{c['name']}: not supported: {bad}")
    held = c["num_local_experts"]
    return HybridDims(
        d_model=d,
        n_layers=n,
        layer_types=kinds,
        n_experts=c.get("published", {}).get("num_local_experts", held),
        n_held=held,
        top_k=c["num_experts_per_tok"],
        d_ff_expert=width,
        n_shared=shared // width,
        n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=hd,
        ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"],
        ssm_state=c["mamba_d_state"],
        ssm_groups=c["mamba_n_groups"],
        conv_kernel=c["mamba_d_conv"],
        ssm_chunk=c["mamba_chunk_size"],
        vocab=c["vocab_size"],
        norm_eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
    )
