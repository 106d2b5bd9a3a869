"""lfm2-8b-a1b [hybrid moe] — 24L d=2048, 18 gated short-conv + 6 GQA
layers (32H, kv=8, head_dim 64, q/k RMSNorm), V=65536 (tied).

Layers 0-1 keep a dense SwiGLU MLP (7168); layers 2-23 route each token
to 4 of 32 experts (1792) by sigmoid scores, picked by ``s + expert_bias``
and weighted by the renormalised ``s``.
[hf:LiquidAI/LFM2-8B-A1B]
"""
from repro.configs.base import ElasticConfig, ModelConfig, MoEConfig, register

# config.json "layer_types": conv -> "conv", full_attention -> "attn"
LAYER_TYPES = ("conv", "conv", "attn", "conv", "conv", "conv", "attn",
               "conv", "conv", "conv", "attn", "conv", "conv", "conv",
               "attn", "conv", "conv", "conv", "attn", "conv", "conv",
               "attn", "conv", "conv")


def full() -> ModelConfig:
    return ModelConfig(
        name="lfm2-8b-a1b", family="hybrid",
        n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8,
        d_ff=7168, vocab_size=65536, d_head=64,
        act="swiglu", norm="rmsnorm", norm_eps=1e-5, qk_norm=True,
        tie_embeddings=True, rope_theta=1_000_000.0, max_seq_len=128_000,
        conv_kernel=3, layer_types=LAYER_TYPES, n_dense_layers=2,
        moe=MoEConfig(n_experts=32, top_k=4, d_expert=1792,
                      router="sigmoid"),
    )


def smoke() -> ModelConfig:
    # two leading dense layers, an (attn, conv) period scanned twice, and
    # one tail layer: every part of the layer stack
    return ModelConfig(
        name="lfm2-8b-a1b-smoke", family="hybrid",
        n_layers=7, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=96, vocab_size=512, d_head=16,
        act="swiglu", norm="rmsnorm", norm_eps=1e-5, qk_norm=True,
        tie_embeddings=True, rope_theta=1_000_000.0,
        conv_kernel=3, n_dense_layers=2,
        layer_types=("conv", "conv", "attn", "conv", "attn", "conv", "attn"),
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, seq_chunk=32,
                      router="sigmoid"),
    )


def elastic(cfg: ModelConfig) -> ElasticConfig:
    # token routers around every mixer (conv or attention) and MLP; head
    # router and LoRA on the attention layers; the native experts' top-k
    # is the expert knob (all of top_k at budget 1.0: the teacher)
    return ElasticConfig(
        mlp_token_capacity=0.8, mha_token_capacity=0.8,
        mha_head_topk=cfg.n_heads // 2,
        mlp_n_experts=None, mlp_expert_topk=cfg.moe.top_k,
        lora_rank=1,
    )


register("lfm2-8b-a1b", full, smoke, elastic)
