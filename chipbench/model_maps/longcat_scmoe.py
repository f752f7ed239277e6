"""LongCat-Flash blocks (latent attention, a shortcut-connected double layer,
routed and zero-compute experts): the published ``config.json`` keys to the
program's ``ModelConfig`` fields. The file's own ``n_routed_experts`` and
``vocab_size`` are what this chip HOLDS (the share convention, README); the
published counts stand beside them and the router keeps its published width."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "published_vocab_size": "published_vocab_size",
    "hidden_size": "hidden_size",
    "ffn_hidden_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_scale_q_lora": "mla_scale_q_lora",
    "mla_scale_kv_lora": "mla_scale_kv_lora",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_routed_experts": "num_experts",
    "published_n_routed_experts": "num_routed_experts",
    "first_expert_held": "expert_offset",
    "zero_expert_num": "zero_expert_num",
    "moe_topk": "num_experts_per_token",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    if doc.get("attention_method") != "MLA" or doc.get("zero_expert_type") != "identity":
        raise ValueError("the longcat block is latent attention (MLA) with identity zero-compute experts")
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    out["routed_scaling_factor"] = float(out["routed_scaling_factor"])
    out["rope_theta"] = float(out["rope_theta"])
    out["block"] = "longcat"
    out["num_kv_heads"] = 1  # one latent a token, shared by every head
    out["head_dim"] = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    out["tie_embeddings"] = False  # the head is untied (assumed: config.json has no key for it)
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
