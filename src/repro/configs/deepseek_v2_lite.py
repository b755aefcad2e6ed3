"""deepseek-v2-lite [https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]:
27L d_model=2048, MLA without q-LoRA (16 heads, kv_lora_rank 512, qk
nope/rope head dims 128/64, v head dim 128; YaRN factor 40 over 4,096
positions, mscale 0.707), vocab 102,400, untied embeddings.  Layer 0 is
a dense SwiGLU of width 10,944; layers 1-26 are DeepSeekMoE: 2 shared
experts (one SwiGLU of width 2,816) and 64 routed experts of width 1,408,
a softmax router in fp32, greedy top-6, not renormalized, scale 1.
Equations: DeepSeek-V2 (arXiv:2405.04434), YaRN (arXiv:2309.00071)."""
from .base import ArchConfig, MLASpec, MoESpec

CONFIG = ArchConfig(
    arch_id="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, head_dim=128,
    norm="rms", mlp="swiglu", tie_embeddings=False, rope_theta=1e4,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
    first_dense_layers=1, first_dense_ff=10944,
    moe=MoESpec(n_experts=64, top_k=6, expert_ff=1408, n_shared=2,
                dropless=True),
    mla=MLASpec(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, q_lora_rank=None, yarn_factor=40.0,
                yarn_original_max_position=4096, yarn_beta_fast=32.0,
                yarn_beta_slow=1.0, yarn_mscale=0.707,
                yarn_mscale_all_dim=0.707),
)
