"""Dense grouped-query attention blocks (Llama, Qwen2, Mistral): the
published ``config.json`` keys to the program's ``ModelConfig`` fields."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    assumed = doc.get("assumed", {})
    out["head_dim"] = doc.get("head_dim") or assumed.get("head_dim") or (
        doc["hidden_size"] // doc["num_attention_heads"])
    out["attn_bias"] = bool(assumed.get("qkv_bias", False))
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
