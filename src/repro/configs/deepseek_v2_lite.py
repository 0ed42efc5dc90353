"""DeepSeek-V2-Lite (15.7B total / 2.4B active): multi-head latent
attention over 16 heads with no query compression, a dense first layer,
then 26 layers of 64 routed experts (top-6, softmax gating without
renormalization) and 2 shared experts; YaRN rope scaling
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

Departures from the published model, all of the program's own: rope
rotates the two halves of the 64 rope dims where HF rotates interleaved
pairs (a fixed permutation of the rope columns of ``wq`` and
``wkv_a``); the token embedding is scaled by ``sqrt(d_model)``; RMSNorm
is ``x / rms(x) * (1 + w)``.
"""
from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="decoder", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=1408, vocab_size=102400,
    layer_pattern="d" + "m" * 26,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff_dense=10944,
                  scoring="softmax"),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    tie_embeddings=False, norm_eps=1e-6,
    source="arXiv:2405.04434",
)
