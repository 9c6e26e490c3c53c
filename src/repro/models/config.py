"""Model configuration for the composable LM zoo.

A model is a stack of ``n_layers`` blocks described by a repeating
``pattern`` of (mixer, ffn) pairs — this one abstraction covers all ten
assigned architectures (dense / GQA / MoE / Mamba-hybrid / xLSTM) plus the
paper's LLaMA-MoE.  ``len(pattern)`` must divide ``n_layers``; the stack is
executed as ``lax.scan`` over ``n_units = n_layers // len(pattern)`` units
so HLO size is O(pattern), not O(depth).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One block of the repeating pattern."""

    mixer: str = "attn"     # "attn" | "mla" | "mamba" | "mamba2" | "mlstm" | "slstm"
    ffn: str = "dense"      # "dense" | "moe" | "none"


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it
    under ``rope_scaling`` with ``type`` "yarn"."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


#: Mixers that carry O(1) decode state in place of a KV cache.
RECURRENT = ("mamba", "mamba2", "mlstm", "slstm")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    head_dim: int = 0                    # 0 => d_model // n_heads

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    first_layer_dense: bool = False      # deepseek-moe: layer 0 is dense FFN
    first_dense_d_ff: int = 0
    capacity_factor: float = 1.25
    n_experts_held: int = 0              # this chip's share of the routed
    #   experts (the first n of an expert-parallel split); 0 => all

    # --- attention ---
    qkv_bias: bool = False               # qwen2.5
    rope_theta: float = 10000.0
    attn_logit_softcap: float = 0.0
    sliding_window: int = 0              # 0 => full causal
    rope_scaling: YarnScaling | None = None
    nope: bool = False                   # no position embedding (NoPE):
    #   attention without rotary, as ``position_embedding_type`` "nope"

    # --- latent attention (mixer "mla", DeepSeek-V2) ---
    kv_lora_rank: int = 0                # latent width cached per token
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0            # rotary part, shared by the heads
    v_head_dim: int = 0

    # --- SSM / recurrent ---
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0               # 0 => ceil(d_model / 16)
    # Mamba-2 (mixer "mamba2"): d_inner = mamba_n_heads * mamba_head_dim;
    # B and C are shared by the heads of each of mamba_n_groups groups.
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256          # SSD chunk (prefill / train)

    # --- GraniteMoE multipliers (the defaults leave a model unscaled) ---
    embedding_multiplier: float = 1.0    # on the input embeddings
    residual_multiplier: float = 1.0     # on each block's mixer and FFN out
    attention_multiplier: float = 0.0    # softmax scale; 0 => head_dim^-0.5
    logits_scaling: float = 1.0          # the logits are divided by it

    # --- embeddings / head ---
    tie_embeddings: bool = False
    vocab_pad_multiple: int = 128

    # --- numerics ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    norm_eps: float = 1e-5

    # --- modality frontend (stub; see models/frontends.py) ---
    frontend: str = ""                  # "" | "vision" | "audio"

    # --- execution knobs (perf pass) ---
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    remat: str = "unit"                  # "none" | "unit"
    flash_vjp: bool = False              # custom-VJP flash attention (bwd
    #   recomputes P chunk-wise instead of saving it; see attention.py)

    def __post_init__(self):
        if self.n_layers % len(self.pattern) != 0:
            raise ValueError(
                f"{self.name}: len(pattern)={len(self.pattern)} must divide "
                f"n_layers={self.n_layers}"
            )
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.mamba_dt_rank == 0:
            object.__setattr__(self, "mamba_dt_rank", -(-self.d_model // 16))
        if any(s.ffn == "moe" for s in self.pattern):
            if self.n_experts <= 0 or self.top_k <= 0:
                raise ValueError(f"{self.name}: MoE pattern needs n_experts/top_k")
            if self.d_ff_expert == 0:
                object.__setattr__(self, "d_ff_expert", self.d_ff)
            if not 0 <= self.n_experts_held <= self.n_experts:
                raise ValueError(f"{self.name}: n_experts_held="
                                 f"{self.n_experts_held} of {self.n_experts}")
        if any(s.mixer == "mla" for s in self.pattern) and not (
                self.kv_lora_rank and self.qk_nope_head_dim
                and self.qk_rope_head_dim and self.v_head_dim
                and self.rope_scaling is not None):
            raise ValueError(f"{self.name}: the mla mixer needs kv_lora_rank, "
                             "the nope, rope and v head sizes and YaRN "
                             "rope_scaling")
        if any(s.mixer == "mamba2" for s in self.pattern) and not (
                self.mamba_n_heads * self.mamba_head_dim == self.d_inner
                and self.mamba_n_heads % self.mamba_n_groups == 0):
            raise ValueError(
                f"{self.name}: the mamba2 mixer needs mamba_n_heads x "
                f"mamba_head_dim = d_inner ({self.d_inner}) and heads that "
                f"divide into mamba_n_groups ({self.mamba_n_groups})")

    # ------------------------------------------------------------------ #
    @property
    def n_units(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this model holds."""
        return self.n_experts_held or self.n_experts

    @property
    def held_share(self) -> bool:
        """True where only a share of the routed experts is held."""
        return self.experts_held < self.n_experts

    @property
    def latent_dim(self) -> int:
        """Width of one latent-cache row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_conv_dim(self) -> int:
        """Mamba-2's convolved channels: x, then B and C of every group."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def has_moe(self) -> bool:
        return any(s.ffn == "moe" for s in self.pattern)

    @property
    def is_recurrent(self) -> bool:
        """True if every mixer carries O(1) decode state (no KV growth)."""
        return all(s.mixer in RECURRENT for s in self.pattern)

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic prefill / O(1)-ish decode state per the assignment:
        SSM / hybrid archs run long_500k; pure full-attention archs skip."""
        return any(s.mixer in RECURRENT for s in self.pattern)

    # -- parameter counting (for roofline MODEL_FLOPS = 6*N*D) ----------- #
    def param_counts(self) -> dict[str, float]:
        """Approximate parameter counts: total and active-per-token.

        With a held share of the routed experts, the counts are of what is
        held: its experts, and a token's expected top-k hits among them.
        """
        d = self.d_model
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        total = float(emb)
        active = float(emb)
        for i in range(self.n_layers):
            spec = self.pattern[i % len(self.pattern)]
            if i == 0 and self.first_layer_dense:
                spec = LayerSpec(mixer=spec.mixer, ffn="dense")
                dff = self.first_dense_d_ff or self.d_ff
            else:
                dff = self.d_ff
            if spec.mixer == "attn":
                p = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            elif spec.mixer == "mla":
                h, r = self.n_heads, self.kv_lora_rank
                p = d * h * (self.qk_nope_head_dim + self.qk_rope_head_dim) \
                    + d * self.latent_dim + r \
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim) \
                    + h * self.v_head_dim * d
            elif spec.mixer == "mamba2":
                h, cd = self.mamba_n_heads, self.mamba_conv_dim
                p = d * (self.d_inner + cd + h) + cd * (self.mamba_d_conv + 1) \
                    + 3 * h + self.d_inner + self.d_inner * d
            elif spec.mixer == "mamba":
                di = self.d_inner
                p = d * 2 * di + di * self.mamba_d_conv \
                    + di * (self.mamba_dt_rank + 2 * self.mamba_d_state) \
                    + self.mamba_dt_rank * di + di * self.mamba_d_state + di * d
            else:  # mlstm / slstm
                di = self.d_inner
                p = d * 3 * di + 3 * di + di * d   # qkv-ish + gates + out
            total += p
            active += p
            if spec.ffn == "dense":
                total += 3 * d * dff
                active += 3 * d * dff
            elif spec.ffn == "moe":
                e = 3 * d * self.d_ff_expert
                held = self.experts_held
                total += held * e + self.n_shared_experts * e \
                    + d * self.n_experts
                active += self.top_k * held / self.n_experts * e \
                    + self.n_shared_experts * e + d * self.n_experts
        return {"total": total, "active": active}
