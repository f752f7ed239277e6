"""Weight-only int8 quantization.

Decode is weight-bandwidth-bound (2 bytes/param/step in bf16); storing
the big matmul weights as int8 with per-output-channel scales halves the
traffic, and XLA:TPU fuses the int8→bf16 dequant into the matmul operand
read (measured 2.4x on v5e decode-shaped matmuls, tools notes). This is
also what fits llama-8b on a single 16GB v5e chip.

Reference analogue: the quantized-serving configs the reference reaches
through its engines (vLLM/TRT-LLM int8/fp8 weight formats); here the
format is ours: ``w_int8 [in, out]`` + ``scale bf16 [out]`` per weight,
with ``<name>_scale`` leaves riding the same pytree (model._w dequants).

Quantized leaves: per-layer matmul weights, the embedding table, and the
untied lm_head. Norms stay high-precision.
"""

from __future__ import annotations

from typing import Any

import numpy as np

# Leaves quantized along their OUTPUT channel (last axis).
_LAYER_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# A block="sala" layer's, sparse or lightning (engine/sala.py draws them int8,
# one stack a name and kind; model._dot_q dequantizes them as it does the dense
# block's): the dense block's and the mixer's output gate.
SALA_LAYER_WEIGHTS = (*_LAYER_WEIGHTS[:4], "w_ogate", *_LAYER_WEIGHTS[4:])


def quantize_np(w: np.ndarray, axis: int = -1) -> tuple[np.ndarray, np.ndarray]:
    """→ (int8 weights, float32 per-channel scales) with symmetric
    absmax scaling along ``axis``'s complement (scale per output slice)."""
    w = np.asarray(w, np.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    absmax = np.max(np.abs(w), axis=reduce_axes)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    shape = [1] * w.ndim
    shape[axis % w.ndim] = -1
    q = np.clip(np.rint(w / scale.reshape(shape)), -127, 127).astype(np.int8)
    return q, scale


def quantize_layer_stacks_np(layers: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Quantize the stacked [L, in, out] layer weights in place-style:
    returns a new dict with int8 leaves + ``<name>_scale`` [L, out].
    MoE expert stacks are left unquantized (their einsum path has no
    int8 dequant fusion yet)."""
    out = dict(layers)
    for name in _LAYER_WEIGHTS:
        if name not in layers:
            continue
        w = np.asarray(layers[name], np.float32)  # [L, in, out]
        absmax = np.max(np.abs(w), axis=1)        # [L, out]
        scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        out[name] = np.clip(
            np.rint(w / scale[:, None, :]), -127, 127
        ).astype(np.int8)
        out[name + "_scale"] = scale
    return out


def quantize_params_np(params: dict[str, Any]) -> dict[str, Any]:
    """Host-side quantization of a full (numpy) params pytree."""
    out = dict(params)
    out["layers"] = quantize_layer_stacks_np(
        {k: np.asarray(v) for k, v in params["layers"].items()}
    )
    emb_q, emb_s = quantize_np(np.asarray(params["embed"]), axis=0)  # scale per vocab row
    out["embed"] = emb_q
    out["embed_scale"] = emb_s
    if "lm_head" in params:
        q, s = quantize_np(np.asarray(params["lm_head"]), axis=-1)   # [D, V] → scale per V
        out["lm_head"] = q
        out["lm_head_scale"] = s
    return out


def _int8_layer_specs(cfg) -> dict[str, tuple[tuple, int]]:
    """name → (stacked shape, fan_in) for the quantized layer matmuls."""
    d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    return {
        "wq": ((L, d, cfg.q_size), d), "wk": ((L, d, cfg.kv_size), d),
        "wv": ((L, d, cfg.kv_size), d), "wo": ((L, cfg.q_size, d), cfg.q_size),
        "w_gate": ((L, d, i), d), "w_up": ((L, d, i), d), "w_down": ((L, i, d), i),
    }


def random_int8_params_device(
    cfg, seed: int = 0, dtype: str = "bfloat16", sharding=None
) -> dict[str, Any]:
    """Random int8 params for geometries whose bf16 random init would not
    fit HBM (8B on one v5e), every leaf generated ON the accelerator:
    int8 from birth and no weight upload. With ``sharding`` (a
    parallel.ModelSharding) the tree is born sharded over its mesh, so no
    device — and no host — ever holds the whole model; the values do not
    depend on the sharding. Values are benchmark-plausible (small scales
    keep the forward finite); decode timing is weight-value-independent."""
    if getattr(cfg, "num_experts", 0):
        raise NotImplementedError("int8 random init not wired for MoE configs")
    import jax
    import jax.numpy as jnp

    if cfg.block == "sala":
        # The block's own stacks (SALA_LAYER_WEIGHTS of each kind of layer), a
        # tensor at a time; single-device, as EngineArgs holds the block to.
        from dynamo_tpu.engine import sala

        return sala.init_params(cfg, jax.random.PRNGKey(seed), jnp.dtype(dtype), quant="int8")

    ndt = jnp.bfloat16 if dtype == "bfloat16" else jnp.dtype(dtype)
    d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    attn_bias = getattr(cfg, "attn_bias", False)

    def build():
        key = jax.random.PRNGKey(seed)

        def q(idx, shape, fan_in):
            w = jax.random.randint(
                jax.random.fold_in(key, idx), shape, -127, 128, jnp.int8
            )
            s = jnp.full((L, shape[-1]), (fan_in ** -0.5) / 64.0, jnp.float32)
            return w, s

        layers: dict[str, Any] = {}
        for idx, (name, (shape, fan)) in enumerate(_int8_layer_specs(cfg).items()):
            w, s = q(idx, shape, fan)
            layers[name] = w
            layers[name + "_scale"] = s
        layers["attn_norm"] = jnp.ones((L, d), ndt)
        layers["mlp_norm"] = jnp.ones((L, d), ndt)
        if attn_bias:
            bkey = jax.random.fold_in(key, 31)
            layers["bq"] = (jax.random.normal(bkey, (L, cfg.q_size)) * 0.02).astype(ndt)
            layers["bk"] = (jax.random.normal(jax.random.fold_in(bkey, 1), (L, cfg.kv_size)) * 0.02).astype(ndt)
            layers["bv"] = (jax.random.normal(jax.random.fold_in(bkey, 2), (L, cfg.kv_size)) * 0.02).astype(ndt)
        params: dict[str, Any] = {
            "embed": jax.random.randint(
                jax.random.fold_in(key, 90), (cfg.vocab_size, d), -127, 128, jnp.int8
            ),
            "embed_scale": jnp.full((cfg.vocab_size,), (d ** -0.5) / 64.0, jnp.float32),
            "layers": layers,
            "final_norm": jnp.ones((d,), ndt),
        }
        if not cfg.tie_embeddings:
            w, s = q(91, (d, cfg.vocab_size), d)
            params["lm_head"] = w
            params["lm_head_scale"] = s[0]
        return params

    if sharding is not None:
        return sharding.born_sharded(build)
    return jax.jit(build)()
