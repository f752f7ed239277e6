"""LFM2-MoE blocks (gated short convolutions among full-attention layers, a
dense feed-forward in the leading layers and routed experts after): the
published ``config.json`` keys (``model_type: lfm2_moe``) to the program's
``ModelConfig`` fields. Every expert is held here, so the file's
``num_experts`` is both the router's width and what this chip holds; depth is
the cut (``num_hidden_layers`` layers, ``layer_types`` its first entries)."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_dense_layers": "num_dense_layers",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_token",
    "conv_L_cache": "conv_L_cache",
    "use_expert_bias": "use_expert_bias",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_eps": "rms_norm_eps",
}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    if doc.get("model_type") != "lfm2_moe" or doc.get("conv_bias"):
        raise ValueError("the lfm2 block is model_type lfm2_moe without a conv bias")
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    out["routed_scaling_factor"] = float(out["routed_scaling_factor"])
    out["rope_theta"] = float(doc["rope_parameters"]["rope_theta"])
    out["layer_types"] = tuple(doc["layer_types"][:doc["num_hidden_layers"]])
    out["block"] = "lfm2"
    out["router_scoring"] = "sigmoid"  # lfm2_moe's router; config.json has no key for it
    out["num_routed_experts"] = doc["num_experts"]
    out["head_dim"] = doc["hidden_size"] // doc["num_attention_heads"]  # assumed: no head_dim key
    out["tie_embeddings"] = True  # assumed: the family's convention
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
