"""kimi-k2-1t-a32b [https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json]:
61L d_model=7168 64H vocab=163840, MoE 384 experts top-8 (+1 shared),
expert d_ff=2048, first layer dense d_ff=18432.  Trillion-parameter
total / ~32B active.

Departures from the source:
- GQA (8 KV heads of size 112) in place of its latent attention (MLA
  with a q-LoRA of rank 1,536, kv_lora_rank 512, head sizes 128 + 64
  rope, v 128), and plain RoPE in place of its YaRN scaling;
- a softmax router with GShard capacity dispatch (`moe_ffn`) in place of
  its sigmoid scores with `noaux_tc` top-k (bias-corrected, grouped) and
  routed scaling."""
from .base import ArchConfig, MoESpec

CONFIG = ArchConfig(
    arch_id="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=8,
    d_ff=2048, vocab=163840, head_dim=112,
    norm="rms", mlp="swiglu", tie_embeddings=False,
    rope_theta=5e4,
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
    first_dense_layers=1, first_dense_ff=18432,
    moe=MoESpec(n_experts=384, top_k=8, expert_ff=2048, n_shared=1,
                capacity_factor=1.25),
)
