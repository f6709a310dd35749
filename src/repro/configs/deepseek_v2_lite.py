"""deepseek-v2-lite [moe, MLA]: 27L d_model=2048 16H vocab=102400
[hf:deepseek-ai/DeepSeek-V2-Lite config.json]: multi-head latent attention
with no query compression (q_lora_rank null), kv_lora=512, qk_nope=128,
qk_rope=64, v_head=128, YaRN rope (factor 40 over 4096 positions,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707); layer 0 a
dense SwiGLU of width 10944, layers 1-26 MoE: 64 routed experts of width
1408, top-6 softmax, norm_topk_prob false, routed_scaling_factor 1, plus
2 shared experts of width 1408; untied embeddings, RMSNorm eps 1e-6."""

import dataclasses

from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_head=192, d_ff=10944, vocab_size=102400,
    attention="mla",
    q_lora_rank=0, kv_lora_rank=512,
    qk_rope_dim=64, qk_nope_dim=128, v_head_dim=128,
    rope_theta=1e4,
    yarn_factor=40.0, yarn_original_max_pos=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    n_experts=64, top_k=6, d_expert=1408,
    n_shared_experts=2, norm_topk_prob=False, routed_scaling=1.0,
    first_dense_layers=1, experts_held=64,
    norm_eps=1e-6, tie_embeddings=False,
)

# One chip's share of the 8-chip deployment the benchmark states: 8 chips
# share each layer by expert parallelism, 8 routed experts and an eighth
# of the vocabulary each (token ids, logits and sampling are over the
# slice); the leading dense layer and 4 MoE layers are one pipeline stage
# (the other 22 layers lie on further stages).
ONE_CHIP = dataclasses.replace(CONFIG, name="deepseek-v2-lite-chip",
                               n_layers=5, experts_held=8, vocab_size=12800)
