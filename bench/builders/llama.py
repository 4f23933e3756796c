"""Builder of a Llama-style decoder: RMSNorm, RoPE, GQA, SwiGLU, untied
output head.  The default builder of a configuration file that names
none."""


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    act = config["hidden_act"]
    if act != "silu":
        raise ValueError(f"{config['name']}: hidden_act {act!r}, not silu")
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        head_dim=config["head_dim"],
        attention="gqa", pos_emb="rope", rope_theta=config["rope_theta"],
        norm="rmsnorm", activation="swiglu",
        tie_embeddings=config["tie_word_embeddings"],
        max_seq=config["max_position_embeddings"],
        dtype=config["torch_dtype"],
    )
