"""The program's configuration of an lfm2_moe configuration file."""
from __future__ import annotations

KINDS = {"conv": "conv", "full_attention": "attn"}


def model_config(conf: dict, overrides: dict):
    """The program's ModelConfig and ElasticConfig for a configuration file:
    the registry entry with every size the file states."""
    import dataclasses
    from repro.configs import MoEConfig, get_config, get_elastic
    base = get_config(conf["registry_name"])
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    if conf["conv_bias"]:
        raise SystemExit("bench: the program's short conv has no bias")
    if not (conf["use_expert_bias"] and conf["norm_topk_prob"]
            and conf["routed_scaling_factor"] == 1):
        raise SystemExit("bench: the program's sigmoid router takes an "
                         "expert bias, renormalised top-k weights and a "
                         "routed scaling of 1")
    cfg = dataclasses.replace(
        base, n_layers=conf["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=conf["num_key_value_heads"], d_head=D // H,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        conv_kernel=conf["conv_L_cache"],
        layer_types=tuple(KINDS[k] for k in conf["layer_types"]),
        n_dense_layers=conf["num_dense_layers"],
        moe=MoEConfig(n_experts=conf["num_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_expert=conf["moe_intermediate_size"],
                      router="sigmoid"),
        dtype=conf["torch_dtype"], eos_id=None)
    if cfg.padded_vocab != cfg.vocab_size:
        raise SystemExit("bench: the vocabulary must be a multiple of 256")
    el = conf["elastic"]
    ecfg = dataclasses.replace(
        get_elastic(conf["registry_name"], cfg),
        mha_token_capacity=el["mha_token_capacity"],
        mlp_token_capacity=el["mlp_token_capacity"],
        mha_head_topk=el["mha_head_topk"],
        mlp_expert_topk=el["mlp_expert_topk"],
        lora_rank=el["lora_rank"], **overrides)
    return cfg, ecfg
