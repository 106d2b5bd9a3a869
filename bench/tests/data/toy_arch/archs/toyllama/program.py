"""The program's configuration of a toyllama configuration file: a dense
decoder without q/k/v biases and with the LM head tied to the embedding,
one token router before the MLP."""


def model_config(conf: dict, overrides: dict):
    from repro.configs import ElasticConfig, ModelConfig
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    cfg = ModelConfig(
        name=conf["name"], family="dense", n_layers=conf["num_hidden_layers"],
        d_model=D, n_heads=H, n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        d_head=D // H, act="swiglu", norm="rmsnorm", qkv_bias=False,
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=conf["rope_theta"], dtype=conf["torch_dtype"])
    ecfg = ElasticConfig(
        mlp_token_capacity=conf["elastic"]["mlp_token_capacity"],
        **overrides)
    return cfg, ecfg
