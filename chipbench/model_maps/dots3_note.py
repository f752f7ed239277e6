"""dots3-note blocks (latent attention in two geometries, a learned indexer on
the full layers, window layers whose pages have a lifetime of their own, the
DeepSeek-V3 expert layer): the published ``config.json`` keys to the program's
``ModelConfig`` fields. The file's own ``n_routed_experts`` and ``vocab_size``
are what this chip HOLDS (the share convention, README); the published counts
stand beside them and the router keeps its published width. The mechanisms the
published switches turn on are the block's own, so a file that switches one off
is refused here instead of being served as something else."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "published_vocab_size": "published_vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "index_n_heads": "index_n_heads",
    "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "sliding_window_size": "sliding_window",
    "swa_num_attention_heads": "swa_num_heads",
    "swa_q_lora_rank": "swa_q_lora_rank",
    "swa_kv_lora_rank": "swa_kv_lora_rank",
    "swa_qk_nope_head_dim": "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim": "swa_qk_rope_head_dim",
    "swa_v_head_dim": "swa_v_head_dim",
    "n_routed_experts": "num_experts",
    "published_n_routed_experts": "num_routed_experts",
    "first_expert_held": "expert_offset",
    "num_experts_per_tok": "num_experts_per_token",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "num_dense_layers",
    "norm_topk_prob": "norm_topk_prob",
    "rms_norm_eps": "rms_norm_eps",
}
# The published switches the block implements one way only.
AS_PUBLISHED = {"attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
                "scoring_func": "sigmoid", "topk_method": "noaux_tc", "hidden_act": "silu",
                "attention_bias": False, "tie_word_embeddings": False, "rope_scaling": None,
                "moe_layer_freq": 1}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    differs = {k: doc.get(k) for k, v in AS_PUBLISHED.items() if doc.get(k) != v}
    if differs:
        raise ValueError(f"the dots3 block is dots3-note as published ({AS_PUBLISHED}); this file differs in {differs}")
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    out["rms_norm_eps"] = float(doc["rms_norm_eps"])
    out["rope_theta"] = float(doc["rope_theta"])
    out["swa_rope_theta"] = float(doc["swa_rope_theta"])
    out["routed_scaling_factor"] = float(doc["routed_scaling_factor"])
    out["layer_types"] = tuple(doc["layer_types"])
    out["mla_scale_q_lora"] = out["mla_scale_kv_lora"] = bool(doc["apply_mla_qkv_lora_rescale"])
    out["router_scoring"] = "sigmoid"
    out["use_expert_bias"] = True  # noaux_tc: the choice is made on s + e_score_correction_bias
    out["block"] = "dots3"
    out["num_kv_heads"] = 1  # one latent a token, shared by every head
    out["head_dim"] = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    out["tie_embeddings"] = False
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
