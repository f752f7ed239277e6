"""MiniCPM-SALA blocks (block-sparse attention layers among lightning
linear-attention layers): the published ``config.json`` keys to the program's
``ModelConfig`` fields. What the file does not state (the sparse layers' sizes)
comes from the configuration's ``assumed.sparse_config``; the mechanisms the
published flags switch on are the block's own, so a file that switches one
off is refused here instead of being served as something else."""

# Published key in the configuration's file -> ModelConfig field.
MODEL_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "lightning_nh": "lightning_heads",
    "lightning_head_dim": "lightning_head_dim",
    "rms_norm_eps": "rms_norm_eps",
    "scale_emb": "scale_emb",
    "scale_depth": "scale_depth",
    "dim_model_base": "dim_model_base",
}
# The published switches the block implements one way only.
AS_PUBLISHED = {"lightning_use_rope": True, "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
                "use_output_norm": True, "attn_use_output_gate": True, "tie_word_embeddings": False,
                "hidden_act": "silu"}
SPARSE_KEYS = {"kernel_size": "sparse_kernel_size", "kernel_stride": "sparse_kernel_stride",
               "block_size": "sparse_block_size", "topk": "sparse_topk", "init_blocks": "sparse_init_blocks",
               "window_size": "sparse_window_size", "dense_len": "sparse_dense_len"}


def fields(doc: dict) -> dict:
    """ModelConfig keyword arguments from a configuration file's document."""
    differs = {k: doc.get(k) for k, v in AS_PUBLISHED.items() if doc.get(k) != v}
    if differs or doc.get("lightning_nkv") != doc.get("lightning_nh"):
        raise ValueError(f"the sala block is MiniCPM-SALA as published ({AS_PUBLISHED}, lightning_nkv = "
                         f"lightning_nh); this file differs in {differs or 'lightning_nkv'}")
    out = {field: doc[key] for key, field in MODEL_KEYS.items()}
    for key in ("scale_emb", "scale_depth", "rms_norm_eps"):
        out[MODEL_KEYS[key]] = float(doc[key])
    out["rope_theta"] = float(doc["rope_theta"])
    out["mixer_types"] = tuple(doc["mixer_types"])
    out.update({field: int(doc["assumed"]["sparse_config"][key]) for key, field in SPARSE_KEYS.items()})
    out["block"] = "sala"
    out["tie_embeddings"] = False
    out["max_position"] = int(doc["served"]["max_model_len"])
    out["name"] = doc["name"]
    return out
