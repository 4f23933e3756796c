"""Test builder of a token-choice mixture-of-experts decoder: GQA
attention, RMSNorm, RoPE, SwiGLU experts with shared experts beside them,
an untied output head.  Its configuration file is ``tiny_moe.json``."""


def model_config(config: dict):
    from repro.configs.base import ModelConfig, MoEConfig

    return ModelConfig(
        name=config["name"], family="moe",
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
        moe=MoEConfig(num_experts=config["num_experts"],
                      top_k=config["num_experts_per_tok"],
                      d_ff_expert=config["moe_intermediate_size"],
                      num_shared_experts=config["n_shared_experts"]),
    )
