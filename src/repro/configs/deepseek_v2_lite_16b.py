"""deepseek-v2-lite-16b [moe]: MLA (kv_lora=512) + 2 shared + 64 routed top-6.

[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json] 27 layers,
d_model 2048, 16 heads, layer 0 a dense SwiGLU of width 10944, layers 1-26
MoE: 64 routed experts of width 1408, greedy softmax top-6 with no
renormalization (``norm_topk_prob`` false; ``routed_scaling_factor`` 1, so
the program applies none),
plus 2 shared experts (width 2816). MLA without q-LoRA: kv_lora_rank 512,
q/k head = 128 nope + 64 rope, v head 128; YaRN rope (factor 40 over 4096
original positions, beta 32/1, mscale = mscale_all_dim = 0.707);
rms_norm_eps 1e-6; untied head; vocab 102400; 163840 positions.
"""
import dataclasses
from repro.config import ModelConfig, MoEConfig, YarnConfig

YARN = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    head_dim=192, vocab_size=102400, max_seq_len=163840, norm_eps=1e-6,
    rope_theta=10000.0, yarn=YARN,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_ff_expert=1408,
                  norm_topk_prob=False),
    first_k_dense=1,
)

# one dense layer and two MoE layers; 8 experts top-2 with 1 shared
SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    head_dim=48, kv_lora_rank=32, qk_nope_dim=32, qk_rope_dim=16,
    v_head_dim=32, vocab_size=256, max_seq_len=256,
    moe=MoEConfig(num_experts=8, top_k=2, num_shared=1, d_ff_expert=32,
                  norm_topk_prob=False))
