"""The program's configuration of a qwen2 configuration file."""
from __future__ import annotations


def model_config(conf: dict, overrides: dict):
    """The program's ModelConfig and ElasticConfig for a configuration file:
    the registry entry with every size the file states."""
    import dataclasses
    from repro.configs import get_config, get_elastic
    base = get_config(conf["registry_name"])
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    cfg = dataclasses.replace(
        base, n_layers=conf["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=conf["num_key_value_heads"], d_head=D // H,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"], tie_embeddings=False,
        dtype=conf["torch_dtype"], eos_id=None)
    if cfg.padded_vocab != cfg.vocab_size:
        raise SystemExit("bench: the vocabulary must be a multiple of 128")
    el = conf["elastic"]
    ecfg = dataclasses.replace(
        get_elastic(conf["registry_name"], cfg),
        mha_token_capacity=el["mha_token_capacity"],
        mlp_token_capacity=el["mlp_token_capacity"],
        mha_head_topk=el["mha_head_topk"],
        mlp_n_experts=el["mlp_n_experts"] or None,
        mlp_expert_topk=el["mlp_expert_topk"] or None,
        lora_rank=el["lora_rank"], **overrides)
    return cfg, ecfg
