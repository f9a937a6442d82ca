"""Central configuration dataclasses for the repro framework.

Everything is a frozen dataclass so configs are hashable and usable as
static args under jit. Architecture configs live in ``repro.configs``;
this module defines the schema.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings for one FFN layer family."""
    num_experts: int = 0              # routed experts (0 = dense FFN)
    top_k: int = 0
    num_shared: int = 0               # always-on shared experts
    d_ff_expert: int = 0              # per-expert hidden dim
    norm_topk_prob: bool = True       # renormalize the top-k weights to sum 1
    # the experts this layer holds, [e_start, e_start + e_count): one
    # expert-parallel share (0 = all); routing still runs over every expert
    e_start: int = 0
    e_count: int = 0
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01

    @property
    def held(self) -> int:
        return self.e_count or self.num_experts


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (HF ``rope_scaling`` of type ``yarn``)."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                  # 0 -> ceil(d_model/16)


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    slstm_every: int = 8              # 1 sLSTM block per this many layers
    proj_factor: float = 2.0          # mLSTM up-projection factor
    chunk_size: int = 64              # chunked parallel mLSTM scan


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One architecture. ``family`` selects the block wiring."""
    name: str = "unnamed"
    family: str = "dense"             # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 2
    n_kv_heads: int = 2
    head_dim: int = 0                 # 0 -> d_model // n_heads
    d_ff: int = 128
    vocab_size: int = 256
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    yarn: Optional[YarnConfig] = None  # None = plain rotary embedding
    norm_eps: float = 1e-5

    # --- family-specific knobs -------------------------------------------
    moe: MoEConfig = MoEConfig()
    mamba: MambaConfig = MambaConfig()
    xlstm: XLSTMConfig = XLSTMConfig()

    # gemma2-style
    local_window: int = 0             # 0 = all-global; else alternate local/global
    query_scale: float = 0.0          # 0 -> 1/sqrt(head_dim)
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    post_block_norm: bool = False     # sandwich norms (gemma2)

    # olmo: non-parametric LayerNorm
    nonparametric_norm: bool = False

    # minicpm mup-ish scaling
    scale_emb: float = 1.0
    scale_depth: float = 0.0          # 0 = off; else residual scaled by scale_depth/sqrt(L)
    dim_model_base: int = 0           # 0 = off; logits scaled by d_model/dim_model_base

    # MLA (deepseek-v2): the latent always passes an RMSNorm
    kv_lora_rank: int = 0             # 0 = plain GQA
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # MoE layer pattern: layer i uses MoE if i >= first_dense and pattern hit
    moe_every: int = 1                # MoE FFN if (i % moe_every == moe_offset)
    moe_offset: int = 0
    first_k_dense: int = 0            # first k layers use dense FFN (deepseek)

    # hybrid (jamba): attention layer if i % attn_every == attn_offset, else mamba
    attn_every: int = 0               # 0 = all-attention
    attn_offset: int = 0

    # enc-dec (whisper)
    n_enc_layers: int = 0
    n_audio_frames: int = 1500        # stub frontend sequence length

    # vlm (qwen2-vl)
    n_vision_tokens: int = 0          # prefix of precomputed patch embeds
    mrope_sections: Tuple[int, int, int] = (0, 0, 0)  # M-RoPE t/h/w splits

    # ffn activation: "silu" | "gelu" | "gelu_tanh"
    act: str = "silu"
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    @property
    def uses_moe(self) -> bool:
        return self.moe.num_experts > 0

    def layer_kind(self, i: int) -> str:
        """'attn' | 'mamba' | 'slstm' | 'mlstm' for decoder layer i."""
        if self.family == "ssm":
            if self.xlstm.slstm_every and i % self.xlstm.slstm_every == 0:
                return "slstm"
            return "mlstm"
        if self.attn_every:
            return "attn" if i % self.attn_every == self.attn_offset else "mamba"
        return "attn"

    def layer_is_moe(self, i: int) -> bool:
        if not self.uses_moe:
            return False
        if i < self.first_k_dense:
            return False
        return i % self.moe_every == self.moe_offset

    def layer_is_local_attn(self, i: int) -> bool:
        """gemma2 alternation: even layers local, odd global."""
        return self.local_window > 0 and (i % 2 == 0)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment."""
    name: str = "train_4k"
    seq_len: int = 4096
    global_batch: int = 256
    kind: str = "train"               # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    pod: int = 1
    data: int = 16
    model: int = 16

    @property
    def num_devices(self) -> int:
        return self.pod * self.data * self.model


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    schedule: str = "cosine"          # cosine | wsd | const
    warmup_steps: int = 100
    total_steps: int = 1000
    decay_frac: float = 0.1           # WSD decay fraction
    microbatches: int = 1             # grad accumulation
    remat: str = "dots"               # none | dots | full
    grad_compress_pods: bool = False  # int8 error-feedback cross-pod reduce
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class CompressConfig:
    """COALA / baselines model compression settings."""
    method: str = "coala"             # coala | svd_llm | svd_llm_v2 | asvd | svd
    ratio: float = 0.7                # kept parameter fraction of compressed layers
    lam: float = 4.0                  # λ in Eq.(5) (paper: stable in [1,10])
    mu: float = -1.0                  # explicit μ; -1 = per-layer Eq.(5)
    alpha: float = 1.0                # Prop.4 weighting exponent (adapters)
    rank: int = 0                     # explicit rank overrides ratio when >0
    use_rsvd: bool = False            # beyond-paper randomized SVD path
    rsvd_oversample: int = 8
    rsvd_power_iters: int = 2
    adaptive_rank: bool = False       # water-filling per-layer ranks (beyond-paper)
    chunk_tokens: int = 4096          # TSQR streaming chunk size
    calib_dtype: str = "float32"
