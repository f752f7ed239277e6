"""DeepSeek-V2 blocks (latent attention with a low-rank query and static YaRN
on the rope lanes, a dense first layer, then group-limited softmax routing over
all the routed experts beside shared ones): the published ``config.json`` keys
to the program's ``ModelConfig`` fields. The file holds whole layers (every
expert, every head, the whole vocabulary: a mesh shares them, ``served.extra_flags``
says over how many chips), so the share convention's ``published_*`` keys are
not used. The mechanisms the published switches turn on are the block's own, so
a file that switches one off is refused here instead of being served as
something else."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
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
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_token",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "num_dense_layers",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "topk_method": "topk_method",
}
# The published ``rope_scaling`` keys (type "yarn") -> ModelConfig field.
YARN_KEYS = {"factor": "yarn_factor", "original_max_position_embeddings": "yarn_original_max_position",
             "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow", "mscale": "yarn_mscale",
             "mscale_all_dim": "yarn_mscale_all_dim"}
# The published switches the block implements one way only.
AS_PUBLISHED = {"scoring_func": "softmax", "topk_method": "group_limited_greedy", "hidden_act": "silu",
                "attention_bias": False, "tie_word_embeddings": False, "norm_topk_prob": False, "moe_layer_freq": 1}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    differs = {k: doc.get(k) for k, v in AS_PUBLISHED.items() if doc.get(k) != v}
    if differs or (doc.get("rope_scaling") or {}).get("type") != "yarn":
        raise ValueError(f"the deepseek block is DeepSeek-V2 as published ({AS_PUBLISHED}, rope_scaling of type "
                         f"'yarn'); this file differs in {differs or doc.get('rope_scaling')}")
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    out.update({field: doc["rope_scaling"][key] for key, field in YARN_KEYS.items()})
    for name in ("yarn_factor", "yarn_beta_fast", "yarn_beta_slow", "yarn_mscale", "yarn_mscale_all_dim"):
        out[name] = float(out[name])
    out["rms_norm_eps"] = float(doc["rms_norm_eps"])
    out["rope_theta"] = float(doc["rope_theta"])
    out["routed_scaling_factor"] = float(doc["routed_scaling_factor"])
    out["num_routed_experts"] = doc["n_routed_experts"]  # every routed expert is held
    out["block"] = "deepseek"
    out["num_kv_heads"] = 1  # one latent a token, shared by every head
    out["head_dim"] = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    out["tie_embeddings"] = False
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
