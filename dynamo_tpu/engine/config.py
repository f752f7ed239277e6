"""Engine + model configuration.

Reference analogue: engine args passthrough (components/backends/vllm/src/
dynamo/vllm/args.py) — but here the engine is ours, so the config is too.
All shapes that reach jit are derived here and static.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters of the three kinds of block the engine
    runs. ``block="llama"`` (default): GQA attention then one FFN (dense
    SwiGLU, or ``_moe`` when ``num_experts``). ``block="lfm2"`` (LFM2-MoE,
    engine/lfm2.py): a per-layer operator list (``layer_types``: a gated
    short convolution or GQA attention with a norm on every query and key
    head) and a per-layer feed-forward (dense for the first
    ``num_dense_layers``, routed experts after); the cache holds K and V
    pages for the attention layers and, beside them under the same block
    ids, the convolution layers' last inputs. ``block="longcat"``
    (LongCat-Flash, engine/longcat.py): per layer two latent-attention
    (MLA) sub-blocks and two dense FFNs, with one shortcut-connected
    expert block that reads the first sub-block's normed stream and is
    added back at the layer's end; the cache holds one latent vector per
    token and sub-block."""

    name: str = "test-tiny"
    vocab_size: int = 512
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position: int = 8192
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # QKV projection bias (Qwen2-family). o_proj stays bias-free, as in
    # the architecture.
    attn_bias: bool = False
    # Mixture-of-experts (0 = dense FFN). Experts shard over the ``ep``
    # mesh axis (parallel/mesh.py) — the reference reaches wide-EP only
    # through engine flags (trtllm_utils.py:140-143, sglang wide-EP docs);
    # here it is a first-class model family.
    num_experts: int = 0
    num_experts_per_token: int = 2
    moe_intermediate_size: int | None = None  # per-expert FFN width (default: intermediate_size)
    # -- block="longcat" ---------------------------------------------------
    block: str = "llama"
    # Latent attention (MLA), named as published: low-rank query and shared
    # latent KV. The cache row is kv_lora_rank + qk_rope_head_dim values, no
    # head axis.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False   # q *= sqrt(hidden / q_lora_rank)
    mla_scale_kv_lora: bool = False  # c_kv *= sqrt(hidden / kv_lora_rank)
    # The expert layer's share: ``num_experts`` routed experts are HELD
    # here, indices [expert_offset, expert_offset + num_experts) of the
    # published ``num_routed_experts``; the router keeps its published
    # width (routed + zero-compute) and what absent experts would add is
    # left out. ``zero_expert_num`` identity experts cost no weights.
    num_routed_experts: int = 0
    expert_offset: int = 0
    zero_expert_num: int = 0
    routed_scaling_factor: float = 1.0
    # The published vocabulary beside the rows held here (``vocab_size``).
    published_vocab_size: int = 0
    # The router's arithmetic (engine/longcat.py:route). "softmax": choice on
    # ``p + bias``, weights ``scaling * p``. "sigmoid": ``s = sigmoid(logits)``,
    # choice on ``s + bias`` where ``use_expert_bias``, weights ``s`` there,
    # divided by their sum where ``norm_topk_prob``, times the scaling.
    router_scoring: str = "softmax"
    use_expert_bias: bool = False
    norm_topk_prob: bool = False
    # -- block="lfm2" ------------------------------------------------------
    # Named as published (lfm2_moe): each layer's operator ("conv" or
    # "full_attention"), how many leading layers carry a dense feed-forward,
    # and the taps of the causal depthwise convolution. A sequence's state in
    # a conv layer is that layer's last ``conv_L_cache - 1`` inputs.
    layer_types: tuple[str, ...] = ()
    num_dense_layers: int = 0
    conv_L_cache: int = 3
    # -- block="sala" (MiniCPM-SALA, engine/sala.py) -----------------------
    # Each layer's mixer as published ("minicpm4": block-sparse GQA attention;
    # "lightning-attn": linear attention with a [head_dim, head_dim] state a
    # head), the lightning heads, the stream's scalings (x0 = scale_emb *
    # embed; a branch times scale_depth / sqrt(layers); logits on the normed
    # stream / (hidden / dim_model_base)), and the sparse layers' sizes
    # (InfLLM-v2: compressed keys of ``sparse_kernel_size`` tokens every
    # ``sparse_kernel_stride``, ``sparse_topk`` blocks of ``sparse_block_size``
    # tokens kept past ``sparse_dense_len`` visible positions, the first
    # ``sparse_init_blocks`` and those of the last ``sparse_window_size``
    # positions among them).
    mixer_types: tuple[str, ...] = ()
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # -- block="dots3" (dots3-note, engine/dots3.py) ------------------------
    # ``layer_types`` names each layer "full_attention" (MLA over every cached
    # token, of which a learned indexer keeps ``index_topk`` a query: the
    # DeepSeek-V3.2 indexer, ``index_n_heads`` heads of ``index_head_dim``
    # against one key a token) or "sliding_attention" (MLA at the ``swa_*``
    # sizes over the last ``sliding_window`` positions, the query's own among
    # them). ``num_dense_layers`` leading layers carry a dense feed-forward,
    # every later one ``num_shared_experts`` shared experts beside the routed.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    num_shared_experts: int = 0
    # -- block="deepseek" (DeepSeek-V2, engine/deepseek.py) ------------------
    # One MLA attention (low-rank query) and one feed-forward a layer: dense
    # for the first ``num_dense_layers``, then ``num_experts`` routed experts
    # (all of them: a mesh holds whole layers) beside ``num_shared_experts``
    # shared ones. ``topk_method`` "group_limited_greedy" is the router's third
    # arithmetic (engine/longcat.py:route): softmax, the experts in ``n_group``
    # consecutive groups, the ``topk_group`` groups of the highest best score
    # kept, the top-k among their experts. The ``yarn_*`` keys are the
    # published ``rope_scaling`` of type "yarn" (``yarn_factor`` 0: none),
    # static: applied at every length (engine/deepseek.py:yarn_inv_freq).
    topk_method: str = "greedy"
    n_group: int = 0
    topk_group: int = 0
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token holds in the cache per attention sub-block."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_page_width(self) -> int:
        """The cache row as stored: ``latent_dim`` padded to whole 128-lane
        tiles (576 → 640), which page DMAs need."""
        return -(-self.latent_dim // 128) * 128

    @property
    def swa(self) -> "ModelConfig":
        """A ``block="dots3"`` model's window layers as a latent geometry of
        their own: this configuration with the ``swa_*`` sizes in the places
        the MLA pieces read (heads, ranks, head sizes, rope base)."""
        return dataclasses.replace(
            self, num_heads=self.swa_num_heads, q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank, qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim, v_head_dim=self.swa_v_head_dim,
            rope_theta=self.swa_rope_theta)

    @property
    def full_layers(self) -> tuple[int, ...]:
        """The layers of a ``block="dots3"`` model that keep every token."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "full_attention")

    @property
    def window_layers(self) -> tuple[int, ...]:
        """Its layers that keep the last ``sliding_window`` positions."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "sliding_attention")

    def _dots3_params(self, experts_counted: int) -> int:
        d, v, ie = self.hidden_size, self.vocab_size, self.moe_intermediate_size or self.intermediate_size

        def mla(c: "ModelConfig") -> int:
            h = c.num_heads
            return (d * c.q_lora_rank + c.q_lora_rank * h * (c.qk_nope_head_dim + c.qk_rope_head_dim)
                    + d * c.latent_dim + c.kv_lora_rank * h * (c.qk_nope_head_dim + c.v_head_dim)
                    + h * c.v_head_dim * d + d * h + c.q_lora_rank + c.kv_lora_rank + 2 * d)

        indexer = (self.q_lora_rank * self.index_n_heads * self.index_head_dim
                   + d * self.index_head_dim + d * self.index_n_heads + 2 * self.index_head_dim)
        n_moe = self.num_layers - self.num_dense_layers
        ff = (self.num_dense_layers * 3 * d * self.intermediate_size
              + n_moe * (d * self.router_width + self.router_width
                         + (self.num_shared_experts + experts_counted) * 3 * d * ie))
        return (2 * v * d + d + len(self.full_layers) * (mla(self) + indexer)
                + len(self.window_layers) * mla(self.swa) + ff)

    def _deepseek_params(self, experts_counted: int) -> int:
        d, v, h = self.hidden_size, self.vocab_size, self.num_heads
        ie = self.moe_intermediate_size or self.intermediate_size
        mla = (d * self.q_lora_rank + self.q_lora_rank * h * (self.qk_nope_head_dim + self.qk_rope_head_dim)
               + d * self.latent_dim + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
               + h * self.v_head_dim * d + self.q_lora_rank + self.kv_lora_rank + 2 * d)
        n_moe = self.num_layers - self.num_dense_layers
        return (2 * v * d + d + self.num_layers * mla + self.num_dense_layers * 3 * d * self.intermediate_size
                + n_moe * (d * self.num_experts + (self.num_shared_experts + experts_counted) * 3 * d * ie))

    @property
    def cache_layers(self) -> int:
        if self.block == "dots3":
            return len(self.full_layers)
        if self.block == "sala":
            return len(self.sparse_layers)
        if self.block == "lfm2":
            return len(self.attn_layers)
        return 2 * self.num_layers if self.block == "longcat" else self.num_layers

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """The layers of a ``block="lfm2"`` model that hold K and V pages."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "full_attention")

    @property
    def conv_layers(self) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "conv")

    @property
    def expert_layers(self) -> tuple[int, ...]:
        """The layers whose feed-forward is routed experts (lfm2: all past
        the leading dense ones)."""
        return tuple(range(self.num_dense_layers, self.num_layers))

    @property
    def conv_state_slots(self) -> int:
        return self.conv_L_cache - 1

    @property
    def sparse_layers(self) -> tuple[int, ...]:
        """The layers of a ``block="sala"`` model that hold K and V pages."""
        return tuple(i for i, t in enumerate(self.mixer_types) if t == "minicpm4")

    @property
    def lightning_layers(self) -> tuple[int, ...]:
        """Its layers that carry a matrix state a head instead."""
        return tuple(i for i, t in enumerate(self.mixer_types) if t == "lightning-attn")

    @property
    def lightning_size(self) -> int:
        return self.lightning_heads * self.lightning_head_dim

    @property
    def state_values(self) -> int:
        """The values of one sequence's lightning state over every such layer."""
        return len(self.lightning_layers) * self.lightning_heads * self.lightning_head_dim ** 2

    def _sala_params(self) -> int:
        d, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        ffn = 3 * d * i + 2 * d
        sparse = 3 * d * self.q_size + 2 * d * self.kv_size + 2 * self.head_dim
        light = 5 * d * self.lightning_size + 2 * self.lightning_head_dim + self.lightning_size
        head = 0 if self.tie_embeddings else d * v
        return (v * d + d + head + len(self.sparse_layers) * (sparse + ffn)
                + len(self.lightning_layers) * (light + ffn))

    @property
    def router_width(self) -> int:
        return (self.num_routed_experts or self.num_experts) + self.zero_expert_num

    def _longcat_layer_params(self, experts: int) -> int:
        d, i, h = self.hidden_size, self.intermediate_size, self.num_heads
        ie = self.moe_intermediate_size or i
        mla = (
            d * self.q_lora_rank + self.q_lora_rank
            + self.q_lora_rank * h * (self.qk_nope_head_dim + self.qk_rope_head_dim)
            + d * self.latent_dim + self.kv_lora_rank
            + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
            + h * self.v_head_dim * d
        )
        return (
            2 * (mla + 3 * d * i + 2 * d)            # two sub-blocks, four norms
            + d * self.router_width + self.router_width  # router and its bias
            + experts * 3 * d * ie
        )

    def _lfm2_params(self, experts_counted: float) -> int:
        """Embedding (tied), operators, norms, dense feed-forwards, routers
        and ``experts_counted`` experts an expert layer."""
        d, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        ie, E = self.moe_intermediate_size or i, self.num_experts
        conv = 3 * d * d + d * d + d * self.conv_L_cache
        attn = d * self.q_size + 2 * d * self.kv_size + self.q_size * d + 2 * self.head_dim
        n_moe = len(self.expert_layers)
        head = 0 if self.tie_embeddings else d * v
        return int(
            v * d + d + head + 2 * d * self.num_layers
            + len(self.conv_layers) * conv + len(self.attn_layers) * attn
            + self.num_dense_layers * 3 * d * i
            + n_moe * (d * E + E + experts_counted * 3 * d * ie)
        )

    def param_count(self) -> int:
        d, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        if self.block == "sala":
            return self._sala_params()
        if self.block == "dots3":
            return self._dots3_params(self.num_experts)
        if self.block == "deepseek":
            return self._deepseek_params(self.num_experts)
        if self.block == "lfm2":
            return self._lfm2_params(self.num_experts)
        if self.block == "longcat":
            head = 0 if self.tie_embeddings else d * v
            return (v * d + d + head
                    + self.num_layers * self._longcat_layer_params(self.num_experts))
        if self.num_experts:
            ie = self.moe_intermediate_size or i
            ffn = self.num_experts * 3 * d * ie + d * self.num_experts  # experts + router
        else:
            ffn = 3 * d * i
        per_layer = (
            d * self.q_size + 2 * d * self.kv_size + self.q_size * d  # attn
            + ffn
            + 2 * d                                                   # norms
        )
        if self.attn_bias:
            per_layer += self.q_size + 2 * self.kv_size
        head = 0 if self.tie_embeddings else d * v
        return v * d + self.num_layers * per_layer + d + head

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts active)."""
        if not self.num_experts:
            return self.param_count()
        d, v = self.hidden_size, self.vocab_size
        if self.block == "lfm2":
            return self._lfm2_params(self.num_experts_per_token)
        if self.block == "deepseek":
            return self._deepseek_params(self.num_experts_per_token)
        if self.block == "dots3":
            return self._dots3_params(
                self.num_experts_per_token * self.num_experts // max(self.router_width, 1))
        if self.block == "longcat":
            # Of a token's top-k, the share that lands on experts held here
            # (zero-compute and absent ones touch no weights).
            held = self.num_experts_per_token * self.num_experts / self.router_width
            head = 0 if self.tie_embeddings else d * v
            return int(v * d + d + head
                       + self.num_layers * self._longcat_layer_params(0)
                       + self.num_layers * held * 3 * d
                       * (self.moe_intermediate_size or self.intermediate_size))
        ie = self.moe_intermediate_size or self.intermediate_size
        per_layer = (
            d * self.q_size + 2 * d * self.kv_size + self.q_size * d
            + self.num_experts_per_token * 3 * d * ie + d * self.num_experts
            + 2 * d
        )
        head = 0 if self.tie_embeddings else d * v
        return v * d + self.num_layers * per_layer + d + head

    @staticmethod
    def preset(name: str) -> "ModelConfig":
        presets = {
            # CPU-testable toy model
            "test-tiny": ModelConfig(),
            # ~1.2B params — fits v5e-lite HBM in bf16 with headroom for KV
            "llama-1b": ModelConfig(
                name="llama-1b", vocab_size=32000, hidden_size=2048,
                intermediate_size=5632, num_layers=22, num_heads=32,
                num_kv_heads=4, head_dim=64, rope_theta=500000.0,
                max_position=131072, tie_embeddings=True,
            ),
            # Llama-3.2-3B-class
            "llama-3b": ModelConfig(
                name="llama-3b", vocab_size=128256, hidden_size=3072,
                intermediate_size=8192, num_layers=28, num_heads=24,
                num_kv_heads=8, head_dim=128, rope_theta=500000.0,
                max_position=131072, tie_embeddings=True,
            ),
            # Llama-3.1-8B-class (multi-chip / bf16-tight on one v5e)
            "llama-8b": ModelConfig(
                name="llama-8b", vocab_size=128256, hidden_size=4096,
                intermediate_size=14336, num_layers=32, num_heads=32,
                num_kv_heads=8, head_dim=128, rope_theta=500000.0,
                max_position=131072, tie_embeddings=False,
            ),
            # Qwen2.5-7B-class (QKV bias; fits one v5e with int8)
            "qwen2-7b": ModelConfig(
                name="qwen2-7b", vocab_size=152064, hidden_size=3584,
                intermediate_size=18944, num_layers=28, num_heads=28,
                num_kv_heads=4, head_dim=128, rope_theta=1000000.0,
                max_position=32768, tie_embeddings=False, attn_bias=True,
            ),
            # Mixtral-style MoE (test/dev scale; EP over the ep mesh axis)
            "moe-tiny": ModelConfig(
                name="moe-tiny", vocab_size=512, hidden_size=128,
                intermediate_size=256, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=32, num_experts=4,
                num_experts_per_token=2,
            ),
            # DeepSeek-V3-ish wide-EP geometry (BASELINE config #5 shape:
            # many small experts, top-8; real weights need a loader ext.)
            "moe-wide": ModelConfig(
                name="moe-wide", vocab_size=32000, hidden_size=2048,
                intermediate_size=8192, num_layers=12, num_heads=16,
                num_kv_heads=4, head_dim=128, num_experts=64,
                num_experts_per_token=8, moe_intermediate_size=1024,
            ),
            # LongCat-Flash block at toy widths (CPU tests): one of 4
            # shares of 8 routed experts beside 4 zero-compute ones.
            "longcat-tiny": ModelConfig(
                name="longcat-tiny", block="longcat", vocab_size=512,
                published_vocab_size=512, hidden_size=128,
                intermediate_size=256, num_layers=2, num_heads=4,
                num_kv_heads=1, head_dim=48, rope_theta=10000.0,
                tie_embeddings=False, q_lora_rank=64, kv_lora_rank=96,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                mla_scale_q_lora=True, mla_scale_kv_lora=True,
                num_experts=2, num_routed_experts=8, expert_offset=2,
                zero_expert_num=4, num_experts_per_token=3,
                moe_intermediate_size=64, routed_scaling_factor=6.0,
            ),
            # LFM2-MoE block at toy widths (CPU tests): the pattern's first 6
            # layers (conv, conv, attention, conv, conv, conv), 1 dense layer,
            # 8 experts with 2 a token.
            "lfm2-tiny": ModelConfig(
                name="lfm2-tiny", block="lfm2", vocab_size=512, hidden_size=128,
                intermediate_size=256, num_layers=6, num_heads=4, num_kv_heads=2,
                head_dim=32, rope_theta=1000000.0, tie_embeddings=True,
                layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv"),
                num_dense_layers=1, conv_L_cache=3, num_experts=8,
                num_routed_experts=8, num_experts_per_token=2,
                moe_intermediate_size=64, routed_scaling_factor=1.0,
                router_scoring="sigmoid", use_expert_bias=True, norm_topk_prob=True,
            ),
            # MiniCPM-SALA block at toy widths (CPU tests): sparse, lightning x3,
            # sparse, lightning; compressed keys of 4 tokens every 2, blocks of
            # 8, 5 kept past 32 visible positions (the first and those of the
            # last 8 positions among them).
            "sala-tiny": ModelConfig(
                name="sala-tiny", block="sala", vocab_size=512, hidden_size=128,
                intermediate_size=256, num_layers=6, num_heads=4, num_kv_heads=2,
                head_dim=32, rope_theta=10000.0, rms_norm_eps=1e-6, tie_embeddings=False,
                mixer_types=("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn",
                             "minicpm4", "lightning-attn"),
                lightning_heads=4, lightning_head_dim=32, scale_emb=12.0, scale_depth=1.4,
                dim_model_base=32, sparse_kernel_size=4, sparse_kernel_stride=2,
                sparse_block_size=8, sparse_topk=5, sparse_init_blocks=1,
                sparse_window_size=8, sparse_dense_len=32,
            ),
            # dots3-note block at toy widths (CPU tests): a dense-FFN full layer,
            # then [full, window, window, window] twice; the indexer keeps 12
            # tokens a query, the window 7 positions of a second, wider latent;
            # 4 of 8 experts held, 2 a token, one shared.
            "dots3-tiny": ModelConfig(
                name="dots3-tiny", block="dots3", vocab_size=512, published_vocab_size=512,
                hidden_size=128, intermediate_size=256, num_layers=9, num_heads=4,
                num_kv_heads=1, head_dim=48, rope_theta=10000.0, tie_embeddings=False,
                q_lora_rank=64, kv_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, mla_scale_q_lora=True, mla_scale_kv_lora=True,
                layer_types=("full_attention",) + ("full_attention",) + ("sliding_attention",) * 3
                + ("full_attention",) + ("sliding_attention",) * 3,
                num_dense_layers=1, index_n_heads=4, index_head_dim=32, index_topk=12,
                sliding_window=7, swa_num_heads=2, swa_q_lora_rank=64, swa_kv_lora_rank=112,
                swa_qk_nope_head_dim=48, swa_qk_rope_head_dim=16, swa_v_head_dim=32,
                swa_rope_theta=5000.0, num_experts=4, num_routed_experts=8, expert_offset=2,
                num_experts_per_token=2, num_shared_experts=1, moe_intermediate_size=64,
                routed_scaling_factor=1.0, router_scoring="sigmoid", use_expert_bias=True,
                norm_topk_prob=True,
            ),
            # DeepSeek-V2 block at toy widths (CPU tests): a dense-FFN layer, then
            # two expert layers of 16 experts in 4 groups of which 2 are kept, 4
            # a token, two shared; YaRN on the 16 rope lanes. Every count divides
            # by 4: the same model runs whole on one device and under --tp 4.
            "deepseek-tiny": ModelConfig(
                name="deepseek-tiny", block="deepseek", vocab_size=512, hidden_size=128,
                intermediate_size=256, num_layers=3, num_heads=8, num_kv_heads=1, head_dim=48,
                rope_theta=10000.0, rms_norm_eps=1e-6, tie_embeddings=False, max_position=4096,
                q_lora_rank=64, kv_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, num_dense_layers=1, num_experts=16, num_routed_experts=16,
                num_experts_per_token=4, num_shared_experts=2, moe_intermediate_size=64,
                routed_scaling_factor=16.0, topk_method="group_limited_greedy", n_group=4,
                topk_group=2, yarn_factor=40.0, yarn_original_max_position=64, yarn_beta_fast=32.0,
                yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
            ),
            # Llama-3-70B-class (BASELINE.md north-star target, multi-host)
            "llama-70b": ModelConfig(
                name="llama-70b", vocab_size=128256, hidden_size=8192,
                intermediate_size=28672, num_layers=80, num_heads=64,
                num_kv_heads=8, head_dim=128, rope_theta=500000.0,
                max_position=131072, tie_embeddings=False,
            ),
        }
        if name not in presets:
            raise ValueError(f"unknown model preset {name!r}; have {sorted(presets)}")
        return presets[name]


# Adaptive tree budgets: the per-row draft-node cap, as a multiple of
# spec_tokens. Bounding hot rows at 2x keeps the verify-shape lattice at
# two S1 values (S+1 and 2S+1) instead of one compile per allocation.
SPEC_BUDGET_MAX_MULT = 2


def spec_verify_widths(spec_tokens: int, adaptive: bool) -> tuple[int, ...]:
    """Query widths (draft nodes + 1) of the verify passes an engine
    dispatches: the uniform S+1 first, and with adaptive budgets last the
    wider shape a hot row's draft past S upgrades the pass to. The engine
    dispatches and warms these; the runner's start line names the
    attention path of each."""
    widths = [spec_tokens + 1]
    if adaptive:
        widths.append(SPEC_BUDGET_MAX_MULT * spec_tokens + 1)
    return tuple(widths)


# Packed prefill (EngineArgs.pack_shapes): the row counts a pack may have, and
# how many T buckets each gets. An admission wave under the token budget is a
# handful of prompts; every (rows, T) is a program compiled at start.
PACK_ROWS = (2, 4)
PACK_T_BUCKETS = 2


def _pow2_buckets(lo: int, hi: int, factor: int = 2) -> tuple[int, ...]:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= factor
    out.append(hi)
    return tuple(dict.fromkeys(out))


@dataclass
class EngineArgs:
    """Runtime shape/capacity knobs. Every jitted shape derives from here."""

    model: ModelConfig = field(default_factory=ModelConfig)
    block_size: int = 16                 # KV page size (tokens)
    num_kv_blocks: int = 256             # G1 (HBM) pool size
    max_num_seqs: int = 8                # max concurrent sequences in decode
    max_model_len: int = 2048            # max prompt+gen tokens per sequence
    max_prefill_tokens: int = 2048       # longest single prefill chunk
    # bf16 for weights/activations; fp32 sampling.
    dtype: str = "bfloat16"
    # TP mesh axis size (1 = single chip). Sharding rules in parallel/.
    tp: int = 1
    enforce_eager: bool = False          # skip jit (debug)
    prefix_caching: bool = True
    # Weight format: "none" = dtype weights; "int8" = weight-only int8
    # with per-output-channel scales (engine/quant.py) — halves weight
    # bandwidth (the decode bottleneck) and fits llama-8b on one v5e.
    quant: str = "none"
    # KV cache storage format: "none" = pages in ``dtype``; "int8" =
    # pages stored int8 with per-position-per-head fp32 scales riding a
    # parallel array alongside the cache (model.KVCache.k_scale/v_scale,
    # same symmetric absmax scheme as engine/quant.py). Near-halves
    # kv_bytes_per_block, so auto_kv_blocks fits ~2x the sequences in
    # the same HBM budget — a capacity AND batch-size win in the weight-
    # bandwidth-bound decode regime. Every consumer dequantizes at read
    # (XLA gather paths and the Pallas kernels, in-register); every
    # tier/transfer hop (G2/G3 offload, disagg export, peer fetch) moves
    # int8+scale payloads, halving those bytes too. Scales are per
    # WRITTEN POSITION (not per sealed block) so a token's stored value
    # never depends on which path wrote it (prefill / decode window /
    # spec verify) or on later writes — the property that keeps greedy
    # streams byte-stable across pipeline depths and spec modes.
    kv_quant: str = "none"
    # Attention backend (ops/paged_attention.py): "auto" → Pallas kernel
    # on TPU (single-device), XLA gather on CPU. Forced to "xla" under a
    # tp/dp mesh (pallas_call is opaque to GSPMD partitioning).
    attn_impl: str = "auto"
    # Fused decode substeps per host sync (model.multi_decode). >1 is the
    # key throughput lever when host↔device roundtrips are slow; tokens
    # stream in bursts of this size. 1 = classic per-step loop.
    decode_steps: int = 8
    # Emit coalescing: when a stream's consumer lags (GIL-bound frontend
    # path), decode-window deltas already queued merge into one frame up
    # to this many tokens before hitting the wire — strictly less
    # per-token Python work with zero added latency (only backlog merges).
    # 0 disables (one frame per decode window).
    delta_max_tokens: int = 64
    # Optional bounded wait (ms) to gather MORE deltas per frame beyond
    # the backlog: adds up to this much inter-token latency. 0 (default)
    # never waits. Keep ≤ one decode-window duration.
    delta_max_ms: float = 0.0
    # Max prompt tokens admitted per scheduler step (prefill-vs-decode
    # fairness knob). Each admitted prompt still prefills in
    # max_prefill_tokens chunks; this budget only gates how many requests
    # join between decode windows. Too small trickle-admits under bursts —
    # every K-step window then runs at a tiny batch (measured 5x
    # throughput loss on ramp-up); too large starves running decodes.
    # 0 = admit until slots are full.
    admission_budget_tokens: int = 8192
    # Multi-tenant QoS (runtime/qos.py, docs/qos.md): when True the
    # scheduler orders admission and preemption by (priority class,
    # age) — waiting interactive requests admit before batch, and KV-
    # pressure preemption evicts the lowest class/newest-prefill victim
    # first. Requests without a priority all land in one class, which
    # makes the ordering EXACTLY the pre-QoS FIFO/newest-first rules —
    # byte-identical streams for no-QoS traffic either way. False pins
    # every request to one class regardless of wire priority.
    qos_scheduling: bool = True
    # Keep decode windows in flight: window w+1 is dispatched chaining
    # from w's on-device outputs before w is fetched, hiding the
    # host↔device sync round trip (cost on the attached chip not yet
    # measured). Stops are then discovered up to pipeline_depth windows
    # late (≤ depth × decode_steps wasted tokens per finished sequence).
    # Full-sampler batches always run unpipelined.
    pipeline_windows: bool = True
    # Max decode windows dispatched-but-not-fetched at once (0 = drain
    # each window before dispatching the next, i.e. unpipelined; 1 = the
    # classic one-window pipeline). Depth 2 lets the host ride out a full
    # fetch roundtrip of jitter without ever idling the device; deeper
    # only adds stop-discovery latency. Fetches are started async at
    # dispatch (copy_to_host_async) and harvested by readiness polling,
    # so the host blocks only when the pipeline is full.
    pipeline_depth: int = 2
    # Prefill T-bucket ladder: "fine" (default) inserts 1.5x midpoints
    # into the pow2 ladder through the common range (≤512), halving the
    # worst-case pad; "coarse" is the legacy 2x/4x ladder (fewest
    # compiles); a comma list ("64,128,384") pins an explicit schedule
    # (values round up to block_size multiples; max_prefill_tokens is
    # always appended). Each bucket × table-width pair is one compile,
    # paid inside the first request that needs it unless the cache is warm.
    prefill_buckets_spec: str = "fine"
    # Split a suffix whose bucket pad is large into [bucket-sized chunk,
    # re-bucketed tail] chunked-prefill dispatches: a 600-token suffix
    # runs as 512 + (88→96) instead of padding a whole 1024 row. Exact
    # (chunked prefill is exact); costs one extra dispatch, so only
    # splits that save ≥ 2 blocks of padding are taken.
    prefill_tail_split: bool = True
    # Alternative-logprob width: requests asking for top_logprobs get up
    # to this many ranked alternatives; ONE static width keeps the
    # compile matrix at 2x (with/without) instead of per-N variants.
    # OpenAI caps chat top_logprobs at 20.
    top_logprobs_max: int = 8
    # KV tier stack (block_manager/tiers.py): G2 host-RAM blocks (0 = off)
    # and optional G3 disk spill directory.
    host_kv_blocks: int = 0
    disk_kv_dir: str | None = None
    disk_kv_blocks: int = 4096
    # G4 fleet-SHARED pool: a directory mounted by EVERY engine (NFS,
    # multi-engine-host tmpfs, fused object store). Blocks spill here
    # from G3 keyed by the salted hash chain, so identical prefixes
    # produced by different engines dedup to one file and any engine can
    # onboard a peer's cold prefix without recompute or a live holder.
    fleet_kv_dir: str | None = None
    fleet_kv_blocks: int = 16384
    # Speculative decoding (engine/drafter.py + model.spec_verify): max
    # draft tokens verified per pass (0 = off). Decode is weight-
    # bandwidth-bound — one verify pass streams the weights ONCE and can
    # emit up to spec_tokens+1 tokens per sequence, so acceptance rate
    # directly multiplies tokens-per-weight-pass. Drafts come from
    # host-side n-gram prompt lookup (free — no draft model); greedy
    # rows accept by exact match (byte-identical to the dense path),
    # sampled rows use rejection sampling (distribution unchanged).
    spec_tokens: int = 0
    # n-gram match length for the prompt-lookup drafter: the last
    # spec_ngram generated/prompt tokens are matched against the
    # sequence's own history and the continuation of the most recent
    # earlier occurrence becomes the draft.
    spec_ngram: int = 3
    # Adaptive acceptance EMA per sequence: update weight, the EMA below
    # which a row stops proposing drafts, and how many decode iterations
    # an EMA-disabled row waits before re-probing with a (naturally
    # short, EMA-scaled) draft. Rows whose drafter simply finds no match
    # are NOT throttled — that scan is an O(new tokens) dict lookup and
    # never forces a pipeline drain by itself. Keeps adversarial
    # (incompressible) workloads at the dense path's cost instead of
    # paying rejected verify work forever.
    spec_ema_alpha: float = 0.3
    spec_ema_disable: float = 0.2
    spec_probe_every: int = 16
    # Tree speculation (SpecInfer-style): max branching factor per draft
    # node. 1 = linear drafts only (the PR 5 path, byte-for-byte);
    # >= 2 swaps in the tree drafter (engine/drafter.TreeDrafter):
    # wherever the per-sequence n-gram index has recorded SEVERAL
    # distinct continuations of the trailing context the draft branches,
    # and a Lookahead-style Jacobi pool (model-predicted continuations
    # harvested from every verify pass's logits) drafts on generic
    # traffic with zero history hits. The whole tree still verifies in
    # ONE weight stream via the topology-masked multi-query gather, so
    # the node budget stays spec_tokens — width buys coverage of
    # alternative branches, not extra bandwidth.
    spec_tree_width: int = 1
    # Max tree path depth (0 = spec_tokens). Depth bounds the best-case
    # accepted run; width x depth should comfortably exceed spec_tokens
    # or the budget can never branch.
    spec_tree_depth: int = 0
    # Verify forward shape: True (default) = single-pass fused forward —
    # ONE weight stream scores the whole draft, the bandwidth win.
    # False = teacher-forced scan of the dense decode step — bitwise
    # identical to the dense path on every backend (fused matmul
    # reduction order can differ at the last ulp on some backends, which
    # perturbs reported logprob values, not sampling decisions); keeps
    # only the one-dispatch/one-fetch saving. Parity/debug mode and the
    # golden suite's byte-identity anchor.
    spec_fused: bool = True
    # Streaming KV export flow control (dynamo_tpu/transfer): max host
    # bytes of published-but-unacked chunks one export may buffer. A
    # consumer that stops pulling aborts the stream at this budget (the
    # decode side falls back to local prefill) instead of growing the
    # prefill worker's heap without bound.
    transfer_buffer_bytes: int = 256 << 20
    # Proactive defrag (planner/balancer.py composition): at this KV
    # pool usage fraction the engine fires its migration-offer hook for
    # the CHEAPEST running sequence — relocating it to a pool peer
    # BEFORE allocation failure forces a recompute-preemption. The same
    # hook the preemption boundary already uses (preempt_offer_grace_s),
    # fired ahead of pressure instead of at the cliff. 0 = off (the
    # offer still fires at the preemption boundary as before).
    kv_pressure_offer: float = 0.0
    # Batch-level dispatch gate: speculate only when the EMA-weighted
    # expected tokens per row-pass, mean(1 + ema_i * draft_len_i),
    # clears this threshold. Protects mixed batches (a few drafting rows
    # must not drop everyone else from K-token windows to 1-token
    # passes) and ramp phases where loops have not formed yet. 0 = always
    # speculate when any draft exists (golden tests use this).
    spec_gate: float = 1.5
    # Batch-level adaptive tree budgets (engine.alloc_spec_budgets):
    # instead of a uniform spec_tokens draft-node allowance per row, each
    # verify pass reallocates the FIXED batch node budget
    # (rows x spec_tokens) by acceptance EMA — draft nodes move from
    # EMA-cold rows to hot ones (hot rows may draft up to 2x spec_tokens;
    # every non-cooling row keeps a >= 1-node probe so it can re-heat).
    # Grammar-constrained rows are typically the hottest, so the whole
    # batch's weight-pass amortization improves at EQUAL total budget.
    # False = the uniform per-row allowance (PR 10 behavior). Correctness
    # is allocation-independent: greedy streams stay byte-identical to
    # dense for any budget split.
    spec_budget_adaptive: bool = True
    # Tokenizer spec dict ({"type": "byte"} / {"type": "hf", ...}) the
    # engine compiles grammar token-mask FSMs over (engine/grammar.py).
    # None = byte tokenizer. Must match the serving tokenizer or masks
    # would legalize undecodable ids; the worker wires its own spec.
    grammar_tokenizer: dict | None = None
    # Multi-LoRA multiplexing (engine/lora.py + block_manager/adapters.py):
    # number of device-resident adapter SLOTS in the HBM adapter bank
    # (0 = LoRA off, no bank allocated, every dispatch byte-identical to
    # pre-LoRA builds). Many more adapters than slots may be registered —
    # they page in on first request through the G2/G3 tier economy and
    # page out cold under second-chance eviction pressure; slots pinned
    # by running sequences are never victims. Each batch row carries an
    # adapter_slot index (-1 = base) and the q/k/v/o projections add the
    # low-rank delta via a batched gathered matmul, so mixed-adapter
    # batches ride the normal prefill/decode/spec dispatches.
    lora_slots: int = 0
    # Static bank rank (max over registered adapters; smaller ranks
    # zero-pad). One rank keeps the compiled dispatch lattice at 2x
    # (with/without adapters) instead of per-rank variants.
    lora_rank: int = 8

    def __post_init__(self):
        # Fail fast on a mistyped ladder spec: anything that is not a
        # named schedule must parse as a comma list of ints, or the error
        # would otherwise surface as a bare int() ValueError deep inside
        # the first bucket_prefill call.
        if self.prefill_buckets_spec not in ("fine", "coarse"):
            self._parse_bucket_list(self.prefill_buckets_spec)
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8'; got {self.kv_quant!r}"
            )
        if self.spec_tree_width < 1:
            raise ValueError(
                f"spec_tree_width must be >= 1; got {self.spec_tree_width}"
            )
        if self.spec_tree_depth < 0:
            raise ValueError(
                f"spec_tree_depth must be >= 0 (0 = spec_tokens); got "
                f"{self.spec_tree_depth}"
            )
        if self.lora_slots < 0:
            raise ValueError(f"lora_slots must be >= 0; got {self.lora_slots}")
        if not 0.0 <= self.kv_pressure_offer <= 1.0:
            raise ValueError(
                f"kv_pressure_offer must be in [0, 1]; got {self.kv_pressure_offer}"
            )
        if self.lora_slots > 0 and self.lora_rank <= 0:
            raise ValueError(
                f"lora_rank must be positive when lora_slots > 0; got {self.lora_rank}"
            )
        if self.model.block == "longcat":
            # What cannot carry a latent page, or this block's layer, says
            # so here by name instead of mis-shaping it inside a request.
            refused = [
                what for on, what in (
                    (self.kv_quant != "none", "--kv-quant int8 (no int8 latent cache)"),
                    (self.spec_tokens > 0, "speculation (--spec-tokens; spec_verify_impl)"),
                    (self.lora_slots > 0, "LoRA banks (--lora-slots)"),
                    (self.quant != "none", "--quant int8 (engine/quant.py)"),
                    (self.tp > 1, "--tp (the latent kernel and the grouped expert product are single-device)"),
                    (bool(self.host_kv_blocks or self.disk_kv_dir or self.fleet_kv_dir),
                     "KV tiers (--host-kv-blocks, --disk-kv-dir, --fleet-kv-dir)"),
                ) if on
            ]
            if refused:
                raise ValueError(
                    f"model {self.model.name!r} has block='longcat' (latent "
                    f"pages, shortcut-connected expert layer), which cannot "
                    f"run with: {'; '.join(refused)}"
                )
        if self.model.block == "lfm2":
            m = self.model
            if len(m.layer_types) != m.num_layers or set(m.layer_types) - {"conv", "full_attention"}:
                raise ValueError(
                    f"model {m.name!r}: layer_types must name 'conv' or 'full_attention' "
                    f"for each of its {m.num_layers} layers; got {m.layer_types!r}")
            refused = [
                what for on, what in (
                    (self.kv_quant != "none", "--kv-quant int8 (no int8 form of the conv-state pool)"),
                    (self.quant != "none", "--quant int8 (engine/quant.py)"),
                    (self.spec_tokens > 0, "speculation (--spec-tokens; a rejected draft cannot roll the conv state back)"),
                    (self.lora_slots > 0, "LoRA banks (--lora-slots)"),
                    (self.tp > 1, "--tp (the conv-state pool and the grouped expert product are single-device)"),
                    (bool(self.host_kv_blocks or self.disk_kv_dir or self.fleet_kv_dir),
                     "KV tiers (--host-kv-blocks, --disk-kv-dir, --fleet-kv-dir)"),
                    (self.block_size % m.conv_state_slots != 0,
                     f"--block-size {self.block_size} (a block ends on a whole turn of the "
                     f"{m.conv_state_slots} conv-state slots)"),
                ) if on
            ]
            if refused:
                raise ValueError(
                    f"model {m.name!r} has block='lfm2' (conv state beside K and V "
                    f"pages, routed experts), which cannot run with: {'; '.join(refused)}"
                )
        if self.model.block == "sala":
            m = self.model
            kinds = {"minicpm4", "lightning-attn"}
            if len(m.mixer_types) != m.num_layers or set(m.mixer_types) - kinds or not m.sparse_layers \
                    or m.mixer_types[0] != "minicpm4":
                raise ValueError(
                    f"model {m.name!r}: mixer_types must name 'minicpm4' or 'lightning-attn' for each "
                    f"of its {m.num_layers} layers, the first a 'minicpm4'; got {m.mixer_types!r}")
            forced = m.sparse_init_blocks + m.sparse_window_size // m.sparse_block_size + 1
            if (m.sparse_kernel_size != 2 * m.sparse_kernel_stride
                    or m.sparse_block_size != 4 * m.sparse_kernel_stride or forced > m.sparse_topk
                    or m.sparse_dense_len % m.sparse_block_size):
                raise ValueError(
                    f"model {m.name!r}: the sparse layers take compressed keys of two strides, four "
                    f"to a block, a dense_len of whole blocks and the {forced} forced blocks inside "
                    f"the top-k; got kernel {m.sparse_kernel_size}, stride {m.sparse_kernel_stride}, "
                    f"block {m.sparse_block_size}, topk {m.sparse_topk}, dense_len {m.sparse_dense_len}")
            refused = [
                what for on, what in (
                    (self.kv_quant != "none", "--kv-quant int8 (no int8 form of the state pool)"),
                    (self.spec_tokens > 0, "speculation (--spec-tokens; a rejected draft cannot roll the matrix state back)"),
                    (self.lora_slots > 0, "LoRA banks (--lora-slots)"),
                    (self.tp > 1, "--tp (the state pool and its step kernel are single-device)"),
                    (bool(self.host_kv_blocks or self.disk_kv_dir or self.fleet_kv_dir),
                     "KV tiers (--host-kv-blocks, --disk-kv-dir, --fleet-kv-dir)"),
                    (self.block_size != m.sparse_block_size,
                     f"--block-size {self.block_size} (a page is the sparse layers' block of "
                     f"{m.sparse_block_size} tokens)"),
                ) if on
            ]
            if refused:
                raise ValueError(
                    f"model {m.name!r} has block='sala' (a matrix state a lightning layer beside "
                    f"the sparse layers' pages), which cannot run with: {'; '.join(refused)}"
                )
        if self.model.block == "dots3":
            m = self.model
            n_win = next((i for i, t in enumerate(m.layer_types[2:]) if t == "full_attention"),
                         len(m.layer_types) - 2)
            period = ("full_attention",) + ("sliding_attention",) * n_win
            if (len(m.layer_types) != m.num_layers or m.num_dense_layers != 1 or not n_win
                    or (m.num_layers - 1) % len(period)
                    or m.layer_types != ("full_attention",) + period * ((m.num_layers - 1) // len(period))):
                raise ValueError(
                    f"model {m.name!r}: layer_types must be one leading 'full_attention' layer (the dense "
                    f"feed-forward's) and whole periods of one 'full_attention' and its 'sliding_attention' "
                    f"layers after it, {m.num_layers} in all; got {m.layer_types!r}")
            refused = [
                what for on, what in (
                    (self.kv_quant != "none", "--kv-quant int8 (no int8 latent cache or index keys)"),
                    (self.spec_tokens > 0, "speculation (--spec-tokens; a draft's positions would each choose their own tokens)"),
                    (self.lora_slots > 0, "LoRA banks (--lora-slots)"),
                    (self.quant != "none", "--quant int8 (engine/quant.py)"),
                    (self.tp > 1, "--tp (the latent kernels and the grouped expert product are single-device)"),
                    (bool(self.host_kv_blocks or self.disk_kv_dir or self.fleet_kv_dir),
                     "KV tiers (--host-kv-blocks, --disk-kv-dir, --fleet-kv-dir)"),
                ) if on
            ]
            if refused:
                raise ValueError(
                    f"model {m.name!r} has block='dots3' (latent pages with index keys, and a second pool "
                    f"of window pages with a lifetime of its own), which cannot run with: {'; '.join(refused)}"
                )
        if self.model.block == "deepseek":
            m = self.model
            if (m.topk_method != "group_limited_greedy" or not m.n_group or m.num_experts % m.n_group
                    or not 0 < m.topk_group <= m.n_group or m.num_dense_layers != 1
                    or m.num_experts_per_token > m.topk_group * (m.num_experts // max(m.n_group, 1))):
                raise ValueError(
                    f"model {m.name!r}: block='deepseek' routes by topk_method 'group_limited_greedy' over "
                    f"n_group groups that divide its experts, keeps topk_group of them with room for a token's "
                    f"experts, and has one leading dense layer; got {m.topk_method!r}, {m.num_experts} experts, "
                    f"n_group {m.n_group}, topk_group {m.topk_group}, {m.num_experts_per_token} a token, "
                    f"{m.num_dense_layers} dense layers")
            shared = m.num_shared_experts * (m.moe_intermediate_size or m.intermediate_size)
            split = {"heads": m.num_heads, "experts": m.num_experts, "vocabulary rows": m.vocab_size,
                     "dense feed-forward width": m.intermediate_size, "shared experts' width": shared}
            refused = [
                what for on, what in (
                    (self.kv_quant != "none", "--kv-quant int8 (no int8 latent cache)"),
                    (self.spec_tokens > 0, "speculation (--spec-tokens; spec_verify_impl)"),
                    (self.lora_slots > 0, "LoRA banks (--lora-slots)"),
                    (self.quant != "none", "--quant int8 (engine/quant.py)"),
                    (any(n % self.tp for n in split.values()),
                     f"--tp {self.tp} (it has to divide " + ", ".join(
                         f"the {n} {what}" for what, n in split.items() if n % self.tp) + ")"),
                    (bool(self.host_kv_blocks or self.disk_kv_dir or self.fleet_kv_dir),
                     "KV tiers (--host-kv-blocks, --disk-kv-dir, --fleet-kv-dir)"),
                ) if on
            ]
            if refused:
                raise ValueError(
                    f"model {m.name!r} has block='deepseek' (latent pages, group-limited expert layers "
                    f"shared over the tp mesh), which cannot run with: {'; '.join(refused)}")
        if self.max_model_len % self.block_size:
            self.max_model_len = ((self.max_model_len // self.block_size) + 1) * self.block_size
        if self.max_prefill_tokens % self.block_size:
            # prefill chunks must be block-aligned (model.py scatter contract)
            self.max_prefill_tokens = (
                (self.max_prefill_tokens // self.block_size) + 1
            ) * self.block_size

    @property
    def blocks_per_seq(self) -> int:
        return self.max_model_len // self.block_size

    # Bucket ladders are cached_properties: bucket_prefill/bucket_decode/
    # bucket_table run on the scheduler hot thread (plan_prefill_chunks
    # probes the ladder O(buckets) times per admitted suffix), so the
    # tuple must be built once, not re-derived per access. EngineArgs is
    # effectively frozen after construction; replace() makes a new
    # instance with a fresh cache.
    @functools.cached_property
    def prefill_buckets(self) -> tuple[int, ...]:
        # Prefill is where the FLOPs are: every padded token runs the
        # full model, so the ladder's stride IS the pad waste (r5 bench:
        # pad_ratio 1.45 on the legacy 2x/4x ladder). "fine" adds 1.5x
        # midpoints to the pow2 ladder through the common range (≤512,
        # where real ShareGPT prompts live) and stays 2x beyond — the
        # tail-split planner (plan_prefill_chunks) covers the long range
        # without more buckets. Values stay block_size-aligned (model.py
        # scatter contract) and each (Bp x T x W) combination is still a
        # separate compile, so the ladder is a knob, not a free lunch.
        spec = self.prefill_buckets_spec
        bs = self.block_size
        if spec not in ("fine", "coarse"):
            vals = sorted({
                min(-(-x // bs) * bs, self.max_prefill_tokens)
                for x in self._parse_bucket_list(spec)
            })
            return tuple(dict.fromkeys(vals + [self.max_prefill_tokens]))
        lo = min(max(bs * 2, 32), self.max_prefill_tokens)
        out = []
        b = lo
        while b < self.max_prefill_tokens:
            out.append(b)
            if spec == "fine":
                mid = -(-(b * 3 // 2) // bs) * bs  # 1.5x, block-aligned
                if b < 512 and mid < self.max_prefill_tokens and mid > b:
                    out.append(mid)
                b *= 2
            else:
                b *= 2 if b < 512 else 4
        out.append(self.max_prefill_tokens)
        return tuple(dict.fromkeys(sorted(out)))

    @functools.cached_property
    def decode_buckets(self) -> tuple[int, ...]:
        # Floor of 8, 4x stride: decode steps are parameter-bandwidth-
        # bound and padded rows cost ~nothing in the Pallas attention
        # path, so coarse batch buckets trade a little sampler work for
        # a much smaller compile matrix (multi_decode variants are the
        # most expensive compiles).
        return _pow2_buckets(min(8, self.max_num_seqs), self.max_num_seqs, factor=4)

    @functools.cached_property
    def table_buckets(self) -> tuple[int, ...]:
        """Block-table width ladder. Decode/prefill attention cost scales
        with the table width actually passed (model.py derives W from the
        shape), so short sequences must not pay for max_model_len — each
        batch uses the smallest bucket covering its longest sequence
        (VERDICT r2 weak #3). Two buckets only: the Pallas decode kernel
        does work proportional to TRUE lengths (padded table width costs
        ~one skipped grid step per dead chunk), so a wide table is nearly
        free on TPU; the small bucket keeps short-prompt prefill on the
        XLA gather path (CPU, mesh, int8 KV) and CPU tests cheap. The
        prefill kernel walks true lengths too (PR 34), so on a TPU the
        small bucket buys nothing any more but a second set of programs
        (PERF.md section 7)."""
        small = min(8, self.blocks_per_seq)
        return tuple(dict.fromkeys((small, self.blocks_per_seq)))

    def bucket_table(self, n_blocks: int) -> int:
        for b in self.table_buckets:
            if n_blocks <= b:
                return b
        raise ValueError(
            f"sequence of {n_blocks} blocks exceeds blocks_per_seq={self.blocks_per_seq}"
        )

    def bucket_prefill(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prefill of {n} tokens exceeds max_prefill_tokens={self.max_prefill_tokens}")

    @staticmethod
    def _parse_bucket_list(spec: str) -> list[int]:
        """Parse an explicit comma-list bucket spec; the ONE shared parse
        for __post_init__ (fail fast at construction) and the ladder
        builder, so validation can't drift from use."""
        try:
            vals = [int(x) for x in spec.split(",") if x.strip()]
        except ValueError:
            vals = []
        if not vals or any(v <= 0 for v in vals):
            raise ValueError(
                f"prefill_buckets_spec must be 'fine', 'coarse' or a comma "
                f"list of positive ints; got {spec!r}"
            )
        return vals

    def plan_prefill_chunks(self, sfx: int) -> list[int]:
        """Chunk plan for one suffix of ``sfx`` tokens (≤ max_prefill_tokens):
        ``[sfx]`` = one dispatch padded to its bucket, or ``[c1, sfx-c1]``
        when splitting the tail into a smaller bucket saves ≥ 2 blocks of
        padding. ``c1`` is a bucket value, hence block-aligned, so the
        second chunk starts on a block boundary (model.py scatter
        contract). Chunked prefill is exact, so the split never changes
        tokens — only the padded-FLOPs bill."""
        direct = self.bucket_prefill(sfx)
        if not self.prefill_tail_split or direct == sfx:
            return [sfx]
        best, best_cost = [sfx], direct
        for c1 in self.prefill_buckets:
            if c1 >= sfx:
                break
            cost = c1 + self.bucket_prefill(sfx - c1)
            # <= : on cost ties the LARGEST first chunk wins (600 →
            # [512, 88→96], not [96, 504]) — one bucket-sized chunk plus
            # a small tail, as documented.
            if cost <= best_cost:
                best, best_cost = [c1, sfx - c1], cost
        if direct - best_cost >= 2 * self.block_size:
            return best
        return [sfx]

    def pack_shapes(self, limit: int) -> tuple[tuple[int, int], ...]:
        """The packed prefill programs (rows, T) a limit of ``limit`` padded
        tokens a dispatch allows (the runner derives it from the model's
        weight bytes against its operations a token, ``runner.pack_limit``):
        for each row count of the pow2 ladder, the ``PACK_T_BUCKETS`` largest
        T buckets with rows x T inside the limit and inside ``max_prefill_tokens``
        (so no pack's temporaries pass the largest single's). Under the
        limit a dispatch is bound by the weights it streams and padding a row
        up costs no second stream, so small T buckets would buy a pack
        nothing but programs; each shape is one compile (wide table only).
        A limit that cannot hold the ladder's top row count at the smallest
        bucket gets no program at all. That floor is a choice, not
        arithmetic: such a model reaches its ridge within a row or two of
        the shortest suffixes, programs for pairs of those served 0 to 1
        dispatch a window where they were tried on the chip (PERF.md s.6,
        PR 38), and each is set-up time and device memory."""
        if PACK_ROWS[-1] * self.prefill_buckets[0] > limit:
            return ()
        out = []
        for rows in PACK_ROWS:
            fits = [t for t in self.prefill_buckets
                    if rows * t <= min(limit, self.max_prefill_tokens)]
            out += [(rows, t) for t in fits[-PACK_T_BUCKETS:]]
        return tuple(out)

    def plan_prefill_packs(
        self, suffixes: list[int], shapes
    ) -> list[tuple[list[int], int, int]]:
        """A wave's single-chunk suffixes (token counts) as dispatches:
        ``(indices into suffixes, rows, T bucket)`` each. Longest first, into
        the largest program of ``shapes`` (the (rows, T) that exist:
        ``pack_shapes`` of the model's limit, once compiled) that more than
        half fill, at the smallest T that holds the longest member: 5 go as
        4 + 1, and 3 as one program of 4 with an inactive row, which the
        limit has already counted (one more weight stream would cost more).
        A suffix no shape holds goes alone (rows 1) at its own bucket, as
        every suffix does while ``shapes`` is empty."""
        order = sorted(range(len(suffixes)), key=lambda i: -suffixes[i])
        by_rows: dict[int, list[int]] = {}
        for rows, t in shapes:
            by_rows.setdefault(rows, []).append(t)
        out: list[tuple[list[int], int, int]] = []
        i = 0
        while i < len(order):
            longest, left = suffixes[order[i]], len(order) - i
            rows, t_pad = 1, self.bucket_prefill(longest)
            for r in sorted(by_rows, reverse=True):
                holds = [t for t in by_rows[r] if t >= longest]
                if r < 2 * left and holds:
                    rows, t_pad = r, min(holds)
                    break
            out.append((order[i:i + rows], rows, t_pad))
            i += rows
        return out

    def bucket_decode(self, n: int) -> int:
        for b in self.decode_buckets:
            if n <= b:
                return b
        raise ValueError(f"decode batch {n} exceeds max_num_seqs={self.max_num_seqs}")

    @property
    def effective_pipeline_depth(self) -> int:
        """pipeline_windows is the master enable; depth 0 = unpipelined."""
        return max(0, self.pipeline_depth) if self.pipeline_windows else 0

    def kv_bytes_per_block(self) -> int:
        """HBM bytes one block costs across all layers, k+v, derived
        from the KV STORAGE dtype — not ``dtype`` alone, which silently
        mis-sized ``auto_kv_blocks`` 2x under kv_quant=int8. int8 pages
        carry a per-position-per-head fp32 scale array (model.KVCache),
        so the real cost is 1 byte/elem + 4/head_dim bytes/elem of scale
        overhead (~3% at head_dim=128 → ~1.94x more blocks per byte)."""
        m = self.model
        if m.block in ("longcat", "deepseek"):
            # One pool, 2L cache layers (L for "deepseek"), the row padded to whole lane tiles.
            itemsize = 2 if self.dtype == "bfloat16" else 4
            return m.cache_layers * self.block_size * m.latent_page_width * itemsize
        if m.block in ("lfm2", "sala", "dots3"):
            return sum(self.pool_bytes_per_block().values())
        elems = self.block_size * m.num_kv_heads * m.head_dim
        if self.kv_quant == "int8":
            # int8 page + fp32 scale per (position, kv head).
            per_layer = elems + self.block_size * m.num_kv_heads * 4
        else:
            itemsize = 2 if self.dtype == "bfloat16" else 4
            per_layer = elems * itemsize
        return 2 * m.num_layers * per_layer

    def kv_page_bytes(self) -> int:
        """Bytes of one page of one cache layer: a block's K and V side by
        side in the pool (``KVCache.kv[layer, block]``; the latent rows of a
        ``block="longcat"`` model), which is what ONE DMA descriptor of the
        paged kernels moves: the size the layout acts through (PERF.md
        section 6, PR 46)."""
        m = self.model
        itemsize = 2 if self.dtype == "bfloat16" else 4
        if m.block in ("longcat", "dots3", "deepseek"):
            return self.block_size * m.latent_page_width * itemsize
        return 2 * self.block_size * m.kv_size * (1 if self.kv_quant == "int8" else itemsize)

    def pool_bytes_per_block(self) -> dict[str, int]:
        """What a block costs in each pool it has a page in, by kind: "kv"
        alone, and for a ``block="lfm2"`` model the K and V of its attention
        layers under "kv" and its conv layers' state under "conv"."""
        m = self.model
        itemsize = 2 if self.dtype == "bfloat16" else 4
        if m.block == "sala":
            # The state pool is slots, not blocks: state_pool_bytes.
            per_layer = m.kv_size * itemsize * len(m.sparse_layers)
            return {"kv": 2 * self.block_size * per_layer,
                    "ckeys": (self.block_size // m.sparse_kernel_stride) * per_layer}
        if m.block == "dots3":
            # The full layers' latents and index keys; the window layers' pool
            # has blocks of its own: window_pool_bytes.
            per_token = len(m.full_layers) * self.block_size * itemsize
            return {"kv": per_token * m.latent_page_width, "ikeys": per_token * m.index_head_dim}
        if m.block != "lfm2":
            return {"kv": self.kv_bytes_per_block()}
        return {
            "kv": 2 * len(m.attn_layers) * self.block_size * m.kv_size * itemsize,
            "conv": len(m.conv_layers) * m.conv_state_slots * m.hidden_size * itemsize,
        }

    @property
    def state_slots(self) -> int:
        """Slots of a ``block="sala"`` model's state pool (0 for any other),
        sized from bytes as the pages are: the pool gets the bytes that
        ``num_kv_blocks`` pages take (the snapshots an idle session leaves and
        the pair it runs in weigh about what its pages do at the contexts such
        a model is served for), and never fewer than two slots a running
        sequence (its live state and the snapshot its decode left at the last
        block boundary), two to spare and slot 0, the sink of padded rows. No
        knob of its own: the policy is block_manager/pool.py's."""
        if self.model.block != "sala":
            return 0
        by_bytes = self.num_kv_blocks * self.kv_bytes_per_block() // self.state_slot_bytes()
        return max(by_bytes, 2 * self.max_num_seqs + 3)

    def state_slot_bytes(self) -> int:
        """One slot: a sequence's state in the cache's dtype (engine/sala.py)."""
        return self.model.state_values * (2 if self.dtype == "bfloat16" else 4)

    def state_pool_bytes(self) -> int:
        return self.state_slots * self.state_slot_bytes()

    # -- a block="dots3" model's window pool (0 for any other) -------------
    # Sized from ``max_num_seqs``, the window and the prefill chunk alone: no
    # flag. A running sequence holds the blocks of its last ``sliding_window``
    # positions and of the decode window's steps past them
    # (``window_table_width``); a prefill chunk those before its first
    # position and its own (``window_prefill_width``).

    @property
    def window_back_blocks(self) -> int:
        """Blocks that hold the ``sliding_window - 1`` positions before a
        block boundary: what a prefix hit needs resident to resume there."""
        w = self.model.sliding_window if self.model.block == "dots3" else 0
        return -(-(w - 1) // self.block_size) if w else 0

    @property
    def window_table_width(self) -> int:
        """Entries of a decode row's window table."""
        if not self.window_back_blocks:
            return 0
        return (self.model.sliding_window - 1 + max(self.decode_steps, 1) - 1) // self.block_size + 2

    @property
    def window_prefill_width(self) -> int:
        """Entries of a prefill row's window table: the blocks behind its
        first position and a whole chunk's."""
        if not self.window_back_blocks:
            return 0
        return self.window_back_blocks + self.max_prefill_tokens // self.block_size + 1

    @property
    def window_blocks(self) -> int:
        """Blocks of the window pool: every slot's live window and as much
        again for the boundaries finished turns and shared prompts leave
        cached, four prefill chunks in flight, and block 0, the sink."""
        if not self.window_back_blocks:
            return 0
        return 2 * self.max_num_seqs * self.window_table_width + 4 * self.window_prefill_width + 1

    def window_bytes_per_block(self) -> int:
        m = self.model
        itemsize = 2 if self.dtype == "bfloat16" else 4
        return len(m.window_layers) * self.block_size * m.swa.latent_page_width * itemsize

    def window_pool_bytes(self) -> int:
        return self.window_blocks * self.window_bytes_per_block() if self.window_blocks else 0

    @property
    def state_operand_width(self) -> int:
        """Columns of the per-row operand a block with a second cache takes
        beside its page table in a prefill (the runner's ``state=``): a
        ``block="sala"`` row's six state slots, a ``block="dots3"`` row's
        window table behind its first block's index; 0 for any other."""
        if self.model.block == "sala":
            return 6
        return 1 + self.window_prefill_width if self.window_back_blocks else 0

    def replace(self, **kw) -> "EngineArgs":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def auto_kv_blocks(hbm_bytes_free: int, args: "EngineArgs", utilization: float = 0.9) -> int:
        """vLLM-style: size the G1 pool from free HBM after weights."""
        per_block = args.kv_bytes_per_block()
        if args.model.block == "sala":
            per_block *= 2  # the state pool takes as many bytes again (state_slots)
        hbm_bytes_free -= args.window_pool_bytes()  # a block="dots3" model's second pool comes first
        n = int(hbm_bytes_free * utilization) // per_block
        return max(n, args.blocks_per_seq * 2)
