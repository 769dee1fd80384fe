"""granite-4.0-h-small [hybrid_moe] — 40L d_model=4096: 36 Mamba-2 layers
(128 heads of 64, d_state 128, one group, conv width 4, chunk 256) and 4
GQA attention layers with no position embedding (32 query / 8 KV heads of
128) at layers 5, 15, 25 and 35. Every layer is followed by a top-10 router
over 72 experts of width 768 (softmax over the ten chosen logits) plus one
shared SwiGLU expert of width 1536. Granite multipliers: embedding x12,
attention scores x1/128, residual branches x0.22, logits /16. Tied
embedding over 100,352 tokens; 32.2B parameters, about 9B active
[hf:ibm-granite/granite-4.0-h-small/config.json].
"""
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig, register


def full() -> ArchConfig:
    return ArchConfig(
        name="granite-4.0-h-small",
        family="hybrid_moe",
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        d_ff=768,
        vocab_size=100352,
        tie_embeddings=True,
        ssm=SSMConfig(state_size=128, head_dim=64, expand=2, conv_width=4, chunk_size=256),
        moe=MoEConfig(num_experts=72, top_k=10, expert_d_ff=768, num_shared=1,
                      shared_d_ff=1536),
        attn_layers=(5, 15, 25, 35),
        rope=False,
        embedding_multiplier=12.0,
        attention_multiplier=0.0078125,
        residual_multiplier=0.22,
        logits_scaling=16.0,
    )


def reduced() -> ArchConfig:
    return ArchConfig(
        name="granite-4.0-h-small-reduced",
        family="hybrid_moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=32,
        vocab_size=512,
        tie_embeddings=True,
        ssm=SSMConfig(state_size=16, head_dim=16, expand=2, conv_width=4, chunk_size=32),
        moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=32, num_shared=1,
                      shared_d_ff=64),
        attn_layers=(1,),
        rope=False,
        embedding_multiplier=12.0,
        attention_multiplier=1 / 16,  # 1 / head_dim, as published at 128
        residual_multiplier=0.22,
        logits_scaling=16.0,
    )


register("granite-4.0-h-small", full, reduced)
