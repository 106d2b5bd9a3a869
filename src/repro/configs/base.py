"""Config system for repro: model configs, elastic (ElastiFormer) configs, shapes.

Plain dataclasses, no external deps. Every assigned architecture provides a
``full()`` (exact published config) and a ``smoke()`` (reduced same-family
config for CPU tests) in its module, and registers itself in REGISTRY.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class MoEConfig:
    """Native mixture-of-experts MLP config (qwen2-moe, grok-1, lfm2-moe).

    Native experts are dispatched dropless: every token reaches each of its
    top-k experts, in prefill as in decode.

    ``router`` "softmax": weights ``softmax(h W_r)``, the top-k by weight.
    ``router`` "sigmoid" (lfm2-moe): scores ``s = sigmoid(h W_r)``; the
    experts are the top-k of ``s + expert_bias``, each weighted
    ``s_e / (sum of the chosen s + 1e-6)``: the bias picks the experts but
    never weights them."""
    n_experts: int
    top_k: int
    d_expert: int                  # ffn dim per expert
    n_shared_experts: int = 0      # qwen2-moe: shared (always-on) experts
    d_shared: int = 0              # ffn dim of the shared expert path
    seq_chunk: int = 2048          # dispatch seq chunking to bound buffers
    router: str = "softmax"        # softmax | sigmoid


@dataclass(frozen=True)
class ModelConfig:
    """Backbone architecture description.

    ``mixer_pattern`` is the repeating period of temporal-mixer kinds:
      'attn'   - (windowed) self attention
      'ssm'    - Mamba2 SSD block
      'rglru'  - RecurrentGemma RG-LRU block
      'xattn'  - self attention + cross attention (enc-dec decoder / VLM layer)
      'conv'   - LFM2 gated short convolution
    Layers beyond the last full period reuse the pattern prefix.
    ``layer_types``, when given, lists every layer's kind instead (a
    published layout that no period tiles). Either way the layer stack
    scans the period that repeats most after the leading dense layers.
    ``n_dense_layers``: with ``moe``, the first layers keep a dense MLP of
    width ``d_ff``.
    """
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 128
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    norm_eps: float = 1e-6
    act: str = "swiglu"             # swiglu | geglu | gelu
    qkv_bias: bool = False
    qk_norm: bool = False           # RMSNorm over head_dim on q, k before RoPE
    tie_embeddings: bool = False
    eos_id: Optional[int] = None    # stop token; serving default for requests
    rope_theta: float = 10_000.0
    max_seq_len: int = 131_072
    # attention locality: per-pattern-position window size; 0 = global.
    # e.g. gemma3: (1024,1024,1024,1024,1024,0) -> 5 local : 1 global.
    window_pattern: Tuple[int, ...] = (0,)
    mixer_pattern: Tuple[str, ...] = ("attn",)
    layer_types: Tuple[str, ...] = ()
    # MoE
    moe: Optional[MoEConfig] = None
    n_dense_layers: int = 0
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_kernel: int = 4
    # RG-LRU (recurrentgemma)
    lru_width: int = 0
    # encoder (whisper) -- a nested encoder stack
    encoder: Optional["ModelConfig"] = None
    encoder_seq: int = 0            # frames after the (stubbed) conv frontend
    # vlm
    n_image_tokens: int = 0         # patch tokens from the (stubbed) frontend
    d_frontend: int = 0             # frontend embedding dim (projected to d_model)
    dtype: str = "bfloat16"

    # ---- derived ----
    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 256)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        if self.layer_types:
            assert len(self.layer_types) >= self.n_layers, self.layer_types
            return tuple(self.layer_types[:self.n_layers])
        p = self.mixer_pattern
        return tuple(p[i % len(p)] for i in range(self.n_layers))

    @property
    def layer_moe(self) -> Tuple[bool, ...]:
        """Per layer: True where its MLP is the native MoE."""
        return tuple(self.moe is not None and i >= self.n_dense_layers
                     for i in range(self.n_layers))

    @property
    def layer_windows(self) -> Tuple[int, ...]:
        w = self.window_pattern
        return tuple(w[i % len(w)] for i in range(self.n_layers))

    @property
    def d_inner(self) -> int:       # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    def n_params(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        D, F, V = self.d_model, self.d_ff, self.padded_vocab
        n = V * D                                   # embed
        if not self.tie_embeddings:
            n += D * V                              # lm_head
        per_kind = {}
        qo = D * self.n_heads * self.d_head + self.n_heads * self.d_head * D
        kv = 2 * D * self.n_kv_heads * self.d_head
        per_kind["attn"] = qo + kv
        per_kind["xattn"] = 2 * (qo + kv)
        if self.ssm_state:
            di = self.d_inner
            per_kind["ssm"] = D * (2 * di + 2 * self.ssm_state + self.n_ssm_heads) \
                + di * D + self.conv_kernel * (di + 2 * self.ssm_state)
        if self.lru_width:
            w = self.lru_width
            per_kind["rglru"] = D * 2 * w + w * D + 2 * w * w + self.conv_kernel * w
        per_kind["conv"] = 4 * D * D + self.conv_kernel * D
        if self.qk_norm:
            per_kind["attn"] += 2 * self.d_head
        n_dense = (3 if self.act in ("swiglu", "geglu") else 2) * D * F
        n_moe = 0
        if self.moe is not None:
            m = self.moe
            n_moe = m.n_experts * 3 * D * m.d_expert + D * m.n_experts
            if m.n_shared_experts:
                n_moe += 3 * D * m.d_shared
            if m.router == "sigmoid":
                n_moe += m.n_experts  # expert_bias
        for k, is_moe in zip(self.layer_kinds, self.layer_moe):
            n_mlp = n_moe if is_moe else n_dense
            n += per_kind.get(k, per_kind.get("attn", 0)) + (n_mlp if k != "ssm" else 0)
            n += 2 * D  # norms
        if self.encoder is not None:
            n += self.encoder.n_params() - self.encoder.padded_vocab * self.encoder.d_model * 2
        return n

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.moe is None:
            return self.n_params()
        m = self.moe
        D = self.d_model
        full_moe = m.n_experts * 3 * D * m.d_expert
        act_moe = m.top_k * 3 * D * m.d_expert
        return self.n_params() - sum(self.layer_moe) * (full_moe - act_moe)


@dataclass(frozen=True)
class ElasticConfig:
    """ElastiFormer routing configuration (the paper's contribution).

    capacities are fractions in (0, 1]; None disables that router.

    DEPRECATED for new code: this bakes every capacity/top-k into the trace
    (one compile per budget). Prefer the split API in ``repro.core.policy``
    — a static ``ElasticSpec`` (what routers exist) plus a runtime
    ``ElasticPolicy`` pytree passed as a traced argument, so one compiled
    model serves every compute budget. Every entry point still accepts
    ``ElasticConfig`` through a shim; ``to_spec_policy()`` converts
    explicitly (see docs/elastic_policy.md for the migration table).
    """
    mlp_token_capacity: Optional[float] = 0.8    # input subset sel. around MLP
    mha_token_capacity: Optional[float] = None   # input subset sel. around MHA/mixer
    depth_capacity: Optional[float] = None       # whole-layer (depth) token sel.
    mha_head_topk: Optional[int] = None          # param subset sel.: active heads
    mlp_n_experts: Optional[int] = None          # moefy dense MLP into M experts
    mlp_expert_topk: Optional[int] = None        # active experts (<= mlp_n_experts)
    vlm_token_capacity: Optional[float] = None   # image-token sel. before decoder
    vlm_router: str = "linear"                   # linear | mlp
    vlm_router_hidden: int = 0                   # hidden dim for mlp router (0 -> d)
    lora_rank: int = 0                           # LoRA on q/v projections
    layers: str = "all"                          # all | even  (paper §5.2)
    router_dtype: str = "float32"
    distill_loss: str = "topk_kl"                # topk_kl|fwd_kl|rev_kl|cosine
    distill_topk: int = 50
    distill_temp: float = 1.0
    lambda_load: float = 1.0
    lambda_topk: float = 1.0
    routing_impl: str = "ragged"                 # ragged | gather | dense_mask
    kernel_backend: str = "auto"                 # auto | pallas | interpret | ref
    kv_dtype: str = "fp32"                       # fp32 | bf16 | int8 (KV cache storage)
    weight_dtype: str = "fp32"                   # fp32 | bf16 | int8 (base weights)

    def applies_to_layer(self, idx: int) -> bool:
        return self.layers == "all" or idx % 2 == 0

    def to_spec_policy(self):
        """Split into the new (ElasticSpec, ElasticPolicy) pair."""
        from repro.core.policy import policy_from_config, spec_from_config
        return spec_from_config(self), policy_from_config(self)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str        # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# archs for which long_500k applies (sub-quadratic / local-attention mixers)
LONG_CONTEXT_ARCHS = {"mamba2-780m", "recurrentgemma-2b", "gemma3-27b"}


def shape_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch in LONG_CONTEXT_ARCHS
    return True


REGISTRY: dict = {}


def register(name: str, full_fn, smoke_fn, elastic_fn=None):
    REGISTRY[name] = {"full": full_fn, "smoke": smoke_fn,
                      "elastic": elastic_fn or default_elastic}


def default_elastic(cfg: ModelConfig) -> ElasticConfig:
    """Paper-default ElastiFormer setting for a backbone."""
    has_attn = any(k in ("attn", "xattn") for k in cfg.layer_kinds)
    native_moe = cfg.moe is not None
    return ElasticConfig(
        mlp_token_capacity=0.8,
        mha_token_capacity=0.8 if has_attn else None,
        mha_head_topk=max(1, cfg.n_heads // 2) if has_attn else None,
        mlp_n_experts=None if (native_moe or cfg.family == "ssm") else 16,
        mlp_expert_topk=(cfg.moe.top_k if native_moe else 9),
        vlm_token_capacity=0.6 if cfg.family in ("vlm", "encdec") else None,
        lora_rank=1 if has_attn else 0,
    )


def get_config(name: str, variant: str = "full") -> ModelConfig:
    return REGISTRY[name][variant]()


def get_elastic(name: str, cfg: Optional[ModelConfig] = None) -> ElasticConfig:
    cfg = cfg or get_config(name)
    return REGISTRY[name]["elastic"](cfg)


def list_archs():
    return sorted(REGISTRY)
