"""Model architecture configs + the name registry.

Maps Ollama-style model names (the scheduler routes on these —
reference: server/src/services/JobScheduler.ts:317-360 selects workers by
model name string) to architecture configs. Dimensions follow the public
HF configs for each family; `hf_config()` round-trips to a transformers
config so golden tests can instantiate the torch twin locally.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from gridllm_tpu.ops.layers import RopeScaling, yarn_factors


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """CLIP-style vision tower (llava family). Defaults = CLIP-ViT-L/14-336,
    the tower every llava-1.5 checkpoint ships."""
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    image_size: int = 336
    patch_size: int = 14
    layer_norm_eps: float = 1e-5
    # HF semantics: hidden_states index fed to the projector (-2 = output
    # of the penultimate encoder layer; llava-1.5 default)
    feature_layer: int = -2
    # id of the per-image placeholder token in the TEXT vocab; the engine
    # expands each to num_patches copies and the prefill splices projected
    # patch embeddings over them
    image_token: int = 32_000

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    # llama | qwen2 | qwen3 | gemma2 | mixtral | smallthinker |
    # deepseek_v2 | olmo_hybrid | laguna | kimi_linear | longcat_flash |
    # granite_hybrid | llava | bert_embed
    # (engine._model_module picks the module)
    family: str = "llama"
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None      # None → hidden_size // num_heads
    rope_theta: float = 500_000.0
    rope_scaling: RopeScaling | None = None
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    max_seq_len: int = 8192
    # MoE (mixtral, smallthinker): an expert's width is intermediate_size
    # unless moe_intermediate_size says otherwise (deepseek_v2, whose
    # intermediate_size is the leading dense layers' width)
    num_experts: int = 0
    experts_per_token: int = 2
    expert_act: str = "silu"         # "silu" (SwiGLU) | "relu" (ReGLU)
    moe_intermediate_size: int = 0
    # deepseek_v2: experts every token takes, unweighted (one SwiGLU of
    # num_shared_experts x the expert width); the top-k weights as the
    # softmax gave them (not renormalised) times routed_scaling_factor;
    # the first first_k_dense layers are dense SwiGLUs of intermediate_size
    num_shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    # what the router scores the experts with: "softmax" over all of them,
    # or "sigmoid" of each (laguna)
    router_score: str = "softmax"
    # a selection bias beside the scores (kimi_linear; the sigmoid gate's
    # e_score_correction_bias): added to CHOOSE the top-k, never to weigh
    router_bias: bool = False
    # the experts this chip holds of each routed layer: `experts_held` of
    # them from index `experts_first` (None = all of num_experts). The
    # router keeps num_experts outputs and its top-k; a pick of an expert
    # that is not held contributes nothing here (it lives on another chip
    # of the expert-parallel group, whose exchange is not run)
    experts_held: int | None = None
    experts_first: int | None = None
    # the rows of the vocabulary this chip holds (None = all of
    # vocab_size): a slice of the embedding and the head, one chip of the
    # group that shares the vocabulary (longcat_flash). A sliced vocabulary
    # is a smaller vocabulary: the engine serves `vocab_rows` ids, the
    # logits and the sampling are over them
    vocab_held: int | None = None
    # zero-compute experts (longcat_flash): the router is num_experts +
    # zero_experts wide, and a pick at or past num_experts adds its weight
    # times the token itself (identity) with no product: it is neither
    # held nor absent, and every chip computes it for its own tokens
    zero_experts: int = 0
    # latent attention (MLA, deepseek_v2): the cache row of a token in a
    # layer is ONE latent of kv_lora_rank and one RoPE key of
    # qk_rope_head_dim, shared by every head (`cache_heads`, `cache_dim`);
    # a query/key head is qk_nope_head_dim + qk_rope_head_dim (= head_dim)
    # and a value head v_head_dim. 0 = K and V per KV head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # longcat_flash's latent attention: a low-rank query (q = W_qb
    # RMSNorm(W_qa h); 0 = one full product), the query times
    # sqrt(hidden / q_lora_rank) and the normed latent times sqrt(hidden /
    # kv_lora_rank) (`mla_scales`), and attn_sublayers attention
    # sublayers a block, each with pages of its own: a block owns that
    # many layers of the pool (`cache_layers`)
    q_lora_rank: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    attn_sublayers: int = 1
    # smallthinker: the router reads the PRE-attention normed state, not
    # the post-attention one the experts compute on
    router_pre_attn: bool = False
    # layers of different MIXERS (olmo_hybrid), one entry a layer:
    # "linear_attention" (a gated delta rule: no pages, a recurrent state
    # a slot) or "full_attention" (pages); () = every layer attends. The
    # pattern is whole periods that end in a full layer, and perhaps a
    # shorter last one (`layer_period`, `layer_tail`).
    # A linear layer has linear_num_heads heads with keys of
    # linear_key_head_dim and values of linear_value_head_dim behind a
    # depthwise causal convolution of linear_conv_kernel taps;
    # linear_allow_neg_eigval doubles beta to (0, 2);
    # linear_channel_decay: the log decay is a value a head a KEY CHANNEL
    # (Kimi Delta Attention), not one a head. rope_theta 0 = no rotary
    # embedding anywhere
    layer_types: tuple[str, ...] = ()
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    linear_allow_neg_eigval: bool = False
    linear_channel_decay: bool = False
    # a state-space layer (granite_hybrid's Mamba-2) is a linear layer
    # WITHOUT the delta: its keys and queries (B and C) are one a GROUP of
    # heads, not one a head (linear_groups of them, 0 = a head's own)
    linear_groups: int = 0
    # the granite family's four multipliers: the embedding times
    # embedding_multiplier, each block's two residual branches times
    # residual_multiplier, attention scores times attention_multiplier
    # (that family's reader requires it; no other family reads it) and the
    # logits DIVIDED by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # attention variants
    attn_logit_softcap: float = 0.0
    sliding_window: int = 0          # 0 → full attention
    # layers that differ in kind (gemma2's window, smallthinker's window
    # and RoPE), one entry a layer; () = every layer alike. window_layout[l] == 0: layer l attends globally
    # whatever sliding_window says; rope_layout[l] == 0: no positional
    # encoding in layer l (NoPE)
    window_layout: tuple[int, ...] = ()
    rope_layout: tuple[int, ...] = ()
    # layers of different SHAPES (laguna): each layer's query heads, one
    # entry a layer; () = num_heads everywhere. Such a family stacks its
    # layers by kind (window layers, global layers), so each kind has a
    # RoPE rule of its own: rope_theta, rope_scaling and
    # partial_rotary_factor (the share of a head's values that rotate) are
    # the GLOBAL layers', window_rope_theta the window layers' (whole
    # head, unscaled; 0 = rope_theta's). attn_gate: a sigmoid gate a head,
    # from the layer's normed input, on the attention output before wo
    heads_layout: tuple[int, ...] = ()
    window_rope_theta: float = 0.0
    partial_rotary_factor: float = 1.0
    attn_gate: bool = False
    attn_bias: bool = False          # qwen2: bias on q/k/v projections
    qk_norm: bool = False            # qwen3: per-head RMSNorm on q/k pre-rope
    # gemma2: logits scale by qpas**-0.5 (None → head_dim), lm-head
    # logits tanh-capped
    query_pre_attn_scalar: float | None = None
    final_logit_softcap: float = 0.0
    # embeddings (bert_embed family)
    pooling: str = "mean"            # "mean" | "cls"
    # multimodal: accepts image inputs (the per-model capability gate the
    # engine rejects on); llava family carries the tower config here
    vision: bool = False
    vision_cfg: VisionConfig | None = None
    # kernel dispatch: None = env/auto policy (ops.attention); the engine
    # sets False on its config copy when serving under a device mesh
    use_pallas: bool | None = None

    def __post_init__(self):
        # a depth cut keeps the first layers: a layout longer than the
        # depth is cut to it, so that two configs of one depth compare equal
        for name in ("window_layout", "rope_layout", "layer_types",
                     "heads_layout"):
            layout = tuple(getattr(self, name))
            if layout and len(layout) < self.num_layers:
                raise ValueError(
                    f"{self.name}: {name} has {len(layout)} entries for "
                    f"{self.num_layers} layers")
            object.__setattr__(self, name, layout[:self.num_layers])
        first, held = self.held_experts
        if self.experts_held is not None and not (
                0 <= first and 0 < held and first + held <= self.num_experts):
            raise ValueError(
                f"{self.name}: experts [{self.experts_first}, +"
                f"{self.experts_held}) are not among {self.num_experts}")
        if self.vocab_held is not None and not (
                0 < self.vocab_held <= self.vocab_size):
            raise ValueError(
                f"{self.name}: {self.vocab_held} rows are not a slice of a "
                f"vocabulary of {self.vocab_size}")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def cache_heads(self) -> int:
        """Heads of a cache row as the paged pool stores it: one for a
        latent cache, whatever num_kv_heads says of the published model.
        The olmo_hybrid family stores more than eight heads that eight
        does not divide (30) as the next multiple (32, zero heads behind
        the real ones: the kernels slice a page's head axis in sublane
        tiles of eight, and the array is tiled to that in memory whatever
        its shape says); it alone, because it alone pads its q, k and v
        to match (`models/olmo_hybrid.py` `_pool_heads`): another
        family's writes are of num_kv_heads and need a pool of as many."""
        if self.kv_lora_rank:
            return 1
        kvh = self.num_kv_heads
        if self.family == "olmo_hybrid" and kvh > 8:
            return -(-kvh // 8) * 8
        return kvh

    @property
    def cache_dim(self) -> int:
        """Values a cache head of one token holds (before lane padding):
        the latent and its RoPE key, or a K (= V) head."""
        if self.kv_lora_rank:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return self.head_dim_

    @property
    def kv_row_values(self) -> int:
        """Values one token's row of one layer holds in the pool."""
        return self.cache_heads * self.cache_dim * (1 if self.kv_lora_rank
                                                    else 2)

    @property
    def cache_kinds(self) -> tuple[str, ...]:
        """What a slot holds of its past: "kv" (K and V rows in pages),
        "latent" (one latent row a token in pages), "state" (a recurrent
        state a slot beside the pages), "window" (a ring of the window
        layers' last rows a slot beside the pages)."""
        rows = "latent" if self.kv_lora_rank else "kv"
        if self.linear_layers:
            return (rows, "state")
        return (rows, "window") if self.ring_layers else (rows,)

    @property
    def ring_layers(self) -> int:
        """Layers whose K and V a slot keeps in a ring of its own, a
        window (and a launch) of rows whatever the context's length, not
        in pages of the one table. In numbers a ring frees rows where
        `window + a launch's rows + one page` (`ring_pages`) is under the
        positions a request has: 512 + 512 + 128 = 1,152 against 16,384
        for the one family that holds them. What is tested is narrower,
        and a limit: the window layers of a family whose layers are
        stacked by kind (`heads_layout`), because only there does each
        kind's stack read a pool of its own. A family of ONE stack over
        one pool (window as a traced scalar: gemma2, smallthinker, a
        windowed mistral) keeps one table however much a ring would free
        (ROADMAP M2)."""
        if not self.heads_layout:
            return 0
        return sum(w > 0 for w in self.layer_windows)

    @property
    def linear_layers(self) -> int:
        """Layers whose mixer keeps a recurrent state, not pages."""
        return sum(t == "linear_attention" for t in self.layer_types)

    @property
    def cache_layers(self) -> int:
        """Layers that own pages: the page pool's leading axis. A block of
        several attention sublayers (`attn_sublayers`) owns one each."""
        return (self.num_layers - self.linear_layers
                - self.ring_layers) * self.attn_sublayers

    @property
    def vocab_rows(self) -> int:
        """Rows of the embedding and the head held here."""
        return self.vocab_held or self.vocab_size

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed experts and, behind them, the
        zero-compute ones."""
        return self.num_experts + self.zero_experts

    @property
    def mla_scales(self) -> tuple[float, float]:
        """(what the low-rank query is multiplied by, what the normed
        latent is): sqrt(hidden / rank) where the config says so, else 1."""
        e = self.hidden_size
        return ((e / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0,
                (e / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0)

    def ring_pages(self, launch_rows: int, page_size: int) -> int:
        """Pages of a slot's ring in each window layer: the window, the
        rows one launch may write and one page more (a launch starts
        anywhere in a page), in whole pages."""
        return -(-(self.sliding_window + launch_rows) // page_size) + 1

    @property
    def layer_period(self) -> int:
        """Length of the mixers' repeating pattern: linear layers then one
        full layer (1 = every layer attends), in whole periods and perhaps
        a shorter last one of the same form (`layer_tail` layers: fewer
        linear layers, then a full one). Anything else is refused."""
        if not self.layer_types:
            return 1
        p = self.layer_types.index("full_attention") + 1 if (
            "full_attention" in self.layer_types) else 0
        lin, full = ("linear_attention",), ("full_attention",)
        n, tail = divmod(self.num_layers, p) if p else (0, 0)
        want = (lin * (p - 1) + full) * n + (
            lin * (tail - 1) + full if tail else ())
        if not p or self.layer_types != want:
            raise ValueError(
                f"{self.name}: layer_types is not whole periods of linear "
                "layers ending in a full one (the last perhaps shorter): "
                f"{self.layer_types}")
        return p

    @property
    def layer_tail(self) -> int:
        """Layers of the shorter last period (0: whole periods only)."""
        return self.num_layers % self.layer_period

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the experts this chip holds of a routed
        layer: all of them unless `experts_held` says otherwise."""
        if self.experts_held is None:
            return 0, self.num_experts
        return self.experts_first or 0, self.experts_held

    @property
    def routes_elsewhere(self) -> bool:
        """Whether a router pick may land on no expert held here: a share
        (`experts_held`) or zero-compute experts behind the routed ones."""
        return self.experts_held is not None or bool(self.zero_experts)

    @property
    def conv_channels(self) -> int:
        """Channels the linear layers' convolution runs over: q, k, v (a
        state-space layer's x, B, C: keys and queries a group)."""
        kq = self.linear_groups or self.linear_num_heads
        return (2 * kq * self.linear_key_head_dim
                + self.linear_num_heads * self.linear_value_head_dim)

    @property
    def layer_windows(self) -> tuple[int, ...]:
        """Each layer's window (0 = global)."""
        on = self.window_layout or (1,) * self.num_layers
        return tuple(self.sliding_window if f else 0 for f in on)

    def hf_config(self) -> Any:
        """Equivalent transformers config (for golden tests, local only)."""
        common = dict(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_layers,
            num_attention_heads=self.num_heads,
            num_key_value_heads=self.num_kv_heads,
            rope_theta=self.rope_theta,
            rms_norm_eps=self.rms_eps,
            tie_word_embeddings=self.tie_embeddings,
            max_position_embeddings=self.max_seq_len,
            attention_bias=False,
        )
        if self.family in ("smallthinker", "deepseek_v2", "olmo_hybrid",
                           "laguna", "kimi_linear", "longcat_flash"):
            raise NotImplementedError(
                f"{self.family} has no transformers twin here: its "
                "reference is under benchmark/reference/")
        if self.family == "mixtral":
            from transformers import MixtralConfig

            return MixtralConfig(
                num_local_experts=self.num_experts,
                num_experts_per_tok=self.experts_per_token,
                sliding_window=self.sliding_window or None,
                **common,
            )
        if self.family == "bert_embed":
            from transformers import BertConfig

            return BertConfig(
                vocab_size=self.vocab_size,
                hidden_size=self.hidden_size,
                num_hidden_layers=self.num_layers,
                num_attention_heads=self.num_heads,
                intermediate_size=self.intermediate_size,
                max_position_embeddings=self.max_seq_len,
                layer_norm_eps=self.rms_eps,
            )
        if self.family == "llava":
            from transformers import CLIPVisionConfig, LlamaConfig, LlavaConfig

            vc = self.vision_cfg or VisionConfig()
            return LlavaConfig(
                vision_config=CLIPVisionConfig(
                    hidden_size=vc.hidden_size,
                    intermediate_size=vc.intermediate_size,
                    num_hidden_layers=vc.num_layers,
                    num_attention_heads=vc.num_heads,
                    image_size=vc.image_size,
                    patch_size=vc.patch_size,
                    layer_norm_eps=vc.layer_norm_eps,
                ),
                text_config=LlamaConfig(**common),
                image_token_index=vc.image_token,
                vision_feature_layer=vc.feature_layer,
                vision_feature_select_strategy="default",
                projector_hidden_act="gelu",
            )
        if self.family == "gemma2":
            from transformers import Gemma2Config

            return Gemma2Config(
                head_dim=self.head_dim_,
                sliding_window=self.sliding_window,
                attn_logit_softcapping=self.attn_logit_softcap,
                final_logit_softcapping=self.final_logit_softcap,
                query_pre_attn_scalar=self.query_pre_attn_scalar
                or self.head_dim_,
                **common,
            )
        if self.family == "qwen2":
            from transformers import Qwen2Config

            common.pop("attention_bias")  # qwen2 hardcodes qkv bias
            return Qwen2Config(**common)
        if self.family == "qwen3":
            from transformers import Qwen3Config

            return Qwen3Config(head_dim=self.head_dim_, **common)
        if self.sliding_window:  # windowed llama skeleton = mistral v0.1
            from transformers import MistralConfig

            common.pop("attention_bias")
            return MistralConfig(
                sliding_window=self.sliding_window,
                head_dim=self.head_dim_,
                **common,
            )
        from transformers import LlamaConfig

        if self.rope_scaling is not None:
            common["rope_scaling"] = {
                "rope_type": "llama3",
                "factor": self.rope_scaling.factor,
                "low_freq_factor": self.rope_scaling.low_freq_factor,
                "high_freq_factor": self.rope_scaling.high_freq_factor,
                "original_max_position_embeddings": self.rope_scaling.original_max_position_embeddings,
            }
        # explicit head_dim: models like mistral-nemo:12b have
        # head_dim != hidden_size // num_heads
        return LlamaConfig(head_dim=self.head_dim_, **common)


_LLAMA3_SCALING = RopeScaling(
    factor=8.0, low_freq_factor=1.0, high_freq_factor=4.0,
    original_max_position_embeddings=8192,
)

# Registry keyed by Ollama model names (BASELINE.md configs 1-5) plus
# tiny/debug configs used by tests and the synthetic bench path.
REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    REGISTRY[cfg.name] = cfg
    return cfg


register(ModelConfig(
    name="llama3.2:1b", vocab_size=128_256, hidden_size=2048,
    intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
    head_dim=64, rope_theta=500_000.0, rope_scaling=_LLAMA3_SCALING,
    tie_embeddings=True, max_seq_len=131_072,
))
register(ModelConfig(
    name="llama3.2:3b", vocab_size=128_256, hidden_size=3072,
    intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
    head_dim=128, rope_theta=500_000.0, rope_scaling=_LLAMA3_SCALING,
    tie_embeddings=True, max_seq_len=131_072,
))
register(ModelConfig(
    name="llama3:8b", vocab_size=128_256, hidden_size=4096,
    intermediate_size=14_336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=500_000.0, max_seq_len=8192,
))
register(ModelConfig(
    name="llama3.1:8b", vocab_size=128_256, hidden_size=4096,
    intermediate_size=14_336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=500_000.0, rope_scaling=_LLAMA3_SCALING, max_seq_len=131_072,
))
register(ModelConfig(
    name="llama3:70b", vocab_size=128_256, hidden_size=8192,
    intermediate_size=28_672, num_layers=80, num_heads=64, num_kv_heads=8,
    rope_theta=500_000.0, max_seq_len=8192,
))
register(ModelConfig(
    name="qwen2.5:0.5b", family="qwen2", vocab_size=151_936, hidden_size=896,
    intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
    head_dim=64, rope_theta=1_000_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=32_768, attn_bias=True,
))
register(ModelConfig(
    name="qwen2.5:7b", family="qwen2", vocab_size=152_064, hidden_size=3584,
    intermediate_size=18_944, num_layers=28, num_heads=28, num_kv_heads=4,
    head_dim=128, rope_theta=1_000_000.0, rms_eps=1e-6,
    max_seq_len=32_768, attn_bias=True,
))
register(ModelConfig(
    name="qwen3:0.6b", family="qwen3", vocab_size=151_936, hidden_size=1024,
    intermediate_size=3072, num_layers=28, num_heads=16, num_kv_heads=8,
    head_dim=128, rope_theta=1_000_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=40_960, qk_norm=True,
))
register(ModelConfig(
    name="qwen3:8b", family="qwen3", vocab_size=151_936, hidden_size=4096,
    intermediate_size=12_288, num_layers=36, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1_000_000.0, rms_eps=1e-6,
    max_seq_len=40_960, qk_norm=True,
))
# llava-1.5 (BASELINE vision parity): vicuna/llama2 text stack + CLIP-L/14
# tower. vocab 32064 = llama2's 32000 padded with the <image>/<pad> extras
# the llava-hf checkpoints ship.
register(ModelConfig(
    name="llava:7b", family="llava", vocab_size=32_064, hidden_size=4096,
    intermediate_size=11_008, num_layers=32, num_heads=32, num_kv_heads=32,
    rope_theta=10_000.0, max_seq_len=4096, rms_eps=1e-5,
    vision=True, vision_cfg=VisionConfig(),
))
register(ModelConfig(
    name="llava:13b", family="llava", vocab_size=32_064, hidden_size=5120,
    intermediate_size=13_824, num_layers=40, num_heads=40, num_kv_heads=40,
    rope_theta=10_000.0, max_seq_len=4096, rms_eps=1e-5,
    vision=True, vision_cfg=VisionConfig(),
))

# mistral (llama skeleton; v0.3 dropped the sliding window, v0.1-class
# checkpoints with one are supported via ModelConfig.sliding_window)
register(ModelConfig(
    name="mistral:7b", vocab_size=32_768, hidden_size=4096,
    intermediate_size=14_336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=1_000_000.0, max_seq_len=32_768, rms_eps=1e-5,
))
register(ModelConfig(
    name="mistral-nemo:12b", vocab_size=131_072, hidden_size=5120,
    intermediate_size=14_336, num_layers=40, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1_000_000.0, max_seq_len=131_072, rms_eps=1e-5,
))

# gemma2 (public HF configs; Ollama's gemma2 tags): even layers slide
# (HF layer_idx % 2 == 0), odd ones attend globally
_GEMMA2_LAYOUT = (1, 0)
register(ModelConfig(
    name="gemma2:2b", family="gemma2", vocab_size=256_000, hidden_size=2304,
    intermediate_size=9216, num_layers=26, num_heads=8, num_kv_heads=4,
    head_dim=256, rope_theta=10_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=8192, sliding_window=4096, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, query_pre_attn_scalar=256,
    window_layout=_GEMMA2_LAYOUT * 13,
))
register(ModelConfig(
    name="gemma2:9b", family="gemma2", vocab_size=256_000, hidden_size=3584,
    intermediate_size=14_336, num_layers=42, num_heads=16, num_kv_heads=8,
    head_dim=256, rope_theta=10_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=8192, sliding_window=4096, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, query_pre_attn_scalar=256,
    window_layout=_GEMMA2_LAYOUT * 21,
))
register(ModelConfig(
    name="gemma2:27b", family="gemma2", vocab_size=256_000, hidden_size=4608,
    intermediate_size=36_864, num_layers=46, num_heads=32, num_kv_heads=16,
    head_dim=128, rope_theta=10_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=8192, sliding_window=4096, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, query_pre_attn_scalar=144,
    window_layout=_GEMMA2_LAYOUT * 23,
))

register(ModelConfig(
    name="mixtral:8x7b", family="mixtral", vocab_size=32_000,
    hidden_size=4096, intermediate_size=14_336, num_layers=32,
    num_heads=32, num_kv_heads=8, rope_theta=1_000_000.0,
    num_experts=8, experts_per_token=2, max_seq_len=32_768, rms_eps=1e-5,
))

# SmallThinker-21BA3B-Instruct (PowerInfer, config.json): 64 ReGLU experts
# of 768, top-6, no shared expert, the router before attention; layers 0,
# 4, 8, ... attend globally with no positional encoding, the rest slide
# over 4096 keys with RoPE
_ST_LAYOUT = (0, 1, 1, 1)
register(ModelConfig(
    name="smallthinker:21b", family="smallthinker", vocab_size=151_936,
    hidden_size=2560, intermediate_size=768, num_layers=52, num_heads=28,
    num_kv_heads=4, head_dim=128, rope_theta=1_500_000.0, rms_eps=1e-6,
    max_seq_len=16_384, num_experts=64, experts_per_token=6,
    expert_act="relu", router_pre_attn=True, sliding_window=4096,
    window_layout=_ST_LAYOUT * 13, rope_layout=_ST_LAYOUT * 13,
))

# DeepSeek-V2-Lite (deepseek-ai, config.json): latent attention (no
# q_lora), one leading dense layer of 10,944, then 64 experts of 1,408
# top-6 with 2 shared, softmax scores not renormalised, YaRN factor 40.
# num_kv_heads is the published 16; the pool holds one latent row a token
# (cache_heads / cache_dim)
_DSV2_YARN = RopeScaling(
    rope_type="yarn", factor=40.0, beta_fast=32.0, beta_slow=1.0,
    mscale=0.707, mscale_all_dim=0.707,
    original_max_position_embeddings=4096,
)
register(ModelConfig(
    name="deepseek-v2-lite:16b", family="deepseek_v2", vocab_size=102_400,
    hidden_size=2048, intermediate_size=10_944, num_layers=27, num_heads=16,
    num_kv_heads=16, head_dim=192, rope_theta=10_000.0,
    rope_scaling=_DSV2_YARN, rms_eps=1e-6, max_seq_len=163_840,
    num_experts=64, experts_per_token=6, moe_intermediate_size=1408,
    num_shared_experts=2, norm_topk_prob=False, routed_scaling_factor=1.0,
    first_k_dense=1, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
))
# Olmo-Hybrid-7B (allenai, config.json): gated delta-rule layers 3:1 with
# full attention (30 heads of 128, a query group of one, QK-norm over the
# whole width, no rotary embedding: rope_theta null), 30 linear heads with
# keys of 96 and values of 192 behind a convolution of 4
_OLMO_HYBRID_PERIOD = ("linear_attention",) * 3 + ("full_attention",)
register(ModelConfig(
    name="olmo-hybrid:7b", family="olmo_hybrid", vocab_size=100_352,
    hidden_size=3840, intermediate_size=11_008, num_layers=32, num_heads=30,
    num_kv_heads=30, head_dim=128, rope_theta=0.0, rms_eps=1e-6,
    max_seq_len=65_536, layer_types=_OLMO_HYBRID_PERIOD * 8,
    linear_num_heads=30, linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel=4, linear_allow_neg_eigval=True,
))
# granite-4.0-h-micro (ibm-granite, config.json): Mamba-2 layers 9:1 with
# attention without positions (32 heads of 64 over 8 KV heads, scores
# times 1/64), the attention layer at place 5 of each ten; 64 state-space
# heads of 64 with a state of 128, B and C one group, a convolution of 4
# with a bias; a shared SwiGLU of 8,192, no routed expert; the family's
# four multipliers; the head tied to the embedding. rope_theta is the
# published key's value and rotates nothing (position_embedding_type nope)
_GRANITE_H_PERIOD = (("linear_attention",) * 5 + ("full_attention",)
                     + ("linear_attention",) * 4)
register(ModelConfig(
    name="granite4:h-micro", family="granite_hybrid", vocab_size=100_352,
    hidden_size=2048, intermediate_size=8192, num_layers=40, num_heads=32,
    num_kv_heads=8, head_dim=64, rope_theta=10_000.0, rms_eps=1e-5,
    tie_embeddings=True, max_seq_len=131_072, experts_per_token=0,
    layer_types=_GRANITE_H_PERIOD * 4,
    linear_num_heads=64, linear_key_head_dim=128, linear_value_head_dim=64,
    linear_conv_kernel=4, linear_groups=1,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
))
# Laguna-XS.2 (poolside, config.json): layer 0 global with a dense SwiGLU
# of 8,192, then window layers (512 keys, 64 heads, RoPE 10,000 on the
# whole head) 3:1 with global ones (48 heads, YaRN x64 on half a head),
# 8 KV heads everywhere, a sigmoid gate a head, 256 experts of 512 top-8
# behind a sigmoid router (normalised, x 2.5) and one shared expert
_LAGUNA_YARN = RopeScaling(
    rope_type="yarn", factor=64.0, beta_fast=64.0, beta_slow=1.0,
    mscale=1.0, mscale_all_dim=0.0, original_max_position_embeddings=4096,
)
_LAGUNA_LAYOUT = (0, 1, 1, 1)
register(ModelConfig(
    name="laguna-xs2:33b", family="laguna", vocab_size=100_352,
    hidden_size=2048, intermediate_size=8192, num_layers=40, num_heads=48,
    num_kv_heads=8, head_dim=128, rope_theta=500_000.0,
    rope_scaling=_LAGUNA_YARN, partial_rotary_factor=0.5,
    window_rope_theta=10_000.0, rms_eps=1e-6, max_seq_len=262_144,
    num_experts=256, experts_per_token=8, moe_intermediate_size=512,
    num_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    router_score="sigmoid", first_k_dense=1, attn_gate=True,
    sliding_window=512, window_layout=_LAGUNA_LAYOUT * 10,
    heads_layout=(48, 64, 64, 64) * 10,
))
# Kimi-Linear-48B-A3B-Instruct (moonshotai, config.json; arXiv:2510.26692):
# Kimi Delta Attention (a delta rule whose decay is a value a head a key
# channel, 32 heads of 128 behind a convolution of 4) 3:1 with latent
# attention without positions (MLA, mla_use_nope: 32 heads of 128 + 64, a
# latent of 512, no q_lora); published full_attn_layers 4, 8, .., 24, 27
# (1-based): six periods of four and a last one of three. Layer 1 is a KDA
# layer with a dense SwiGLU of 9,216; then 256 experts of 1,024 top-8
# behind a sigmoid router with a selection bias (normalised, x 2.446) and
# one shared expert. head_dim is the published 72 (hidden / heads); the
# latent mixer's heads are qk_nope_head_dim + qk_rope_head_dim
_KIMI_LAYERS = tuple(
    "full_attention" if i in (4, 8, 12, 16, 20, 24, 27)
    else "linear_attention" for i in range(1, 28))
_KIMI_LINEAR = register(ModelConfig(
    name="kimi-linear:48b", family="kimi_linear", vocab_size=163_840,
    hidden_size=2304, intermediate_size=9216, num_layers=27, num_heads=32,
    num_kv_heads=32, head_dim=72, rope_theta=10_000.0, rms_eps=1e-5,
    max_seq_len=1_048_576, num_experts=256, experts_per_token=8,
    moe_intermediate_size=1024, num_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.446, router_score="sigmoid", router_bias=True,
    first_k_dense=1, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, layer_types=_KIMI_LAYERS,
    linear_num_heads=32, linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel=4, linear_channel_decay=True,
))
# one chip of the four that share each layer (expert-parallel over 4): the
# router's 256 outputs and top-8 as published, experts 0-63 held here
register(dataclasses.replace(
    _KIMI_LINEAR, name="kimi-linear:48b-ep4", experts_held=64,
    experts_first=0))
# LongCat-Flash (meituan-longcat, config.json of -Chat and -Omni alike;
# the language model of -Omni): 28 blocks of TWO latent-attention
# sublayers (64 heads of 128 + 64, a latent of 512, a low-rank query of
# 1,536, both scaled by sqrt(hidden / rank)), two dense SwiGLUs of 12,288
# and one expert layer that leaves after the first attention and rejoins
# at the block's end (the shortcut): 512 experts of 2,048 and 256
# zero-compute (identity) ones behind one softmax router of 768, top-12
# chosen with a selection bias, not renormalised, x 6; no shared expert
_LONGCAT_FLASH = register(ModelConfig(
    name="longcat-flash:560b", family="longcat_flash", vocab_size=131_072,
    hidden_size=6144, intermediate_size=12_288, num_layers=28, num_heads=64,
    num_kv_heads=64, head_dim=192, rope_theta=10_000_000.0, rms_eps=1e-5,
    max_seq_len=131_072, num_experts=512, experts_per_token=12,
    moe_intermediate_size=2048, norm_topk_prob=False,
    routed_scaling_factor=6.0, router_bias=True, zero_experts=256,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, q_lora_rank=1536, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, attn_sublayers=2,
))
# one chip of the 32 that share each block (expert-parallel over 32): the
# router's 768 outputs and top-12 as published, experts 0-15 held here,
# and of the embedding and the head an eighth (rows 0-16,383: the
# vocabulary split eight ways)
register(dataclasses.replace(
    _LONGCAT_FLASH, name="longcat-flash:560b-ep32", experts_held=16,
    experts_first=0, vocab_held=16_384))
register(ModelConfig(
    name="all-minilm", family="bert_embed", vocab_size=30_522,
    hidden_size=384, intermediate_size=1536, num_layers=6, num_heads=12,
    num_kv_heads=12, rms_eps=1e-12, max_seq_len=512, pooling="mean",
))
register(ModelConfig(
    name="mxbai-embed-large", family="bert_embed", vocab_size=30_522,
    hidden_size=1024, intermediate_size=4096, num_layers=24, num_heads=16,
    num_kv_heads=16, rms_eps=1e-12, max_seq_len=512, pooling="cls",
))

# Tiny configs: architecture-faithful, test/bench-sized.
register(ModelConfig(
    name="tiny-llama", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    rope_theta=10_000.0, max_seq_len=256, tie_embeddings=False,
))
register(ModelConfig(
    name="tiny-mixtral", family="mixtral", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, max_seq_len=256,
    num_experts=4, experts_per_token=2,
))
# smallthinker's shape in small: one period of (global NoPE, 3 x window
# RoPE), a window shorter than the test contexts, 7 query heads a KV head
register(ModelConfig(
    name="tiny-smallthinker", family="smallthinker", vocab_size=256,
    hidden_size=64, intermediate_size=32, num_layers=4, num_heads=14,
    num_kv_heads=2, head_dim=16, rope_theta=10_000.0, rms_eps=1e-6,
    max_seq_len=256, num_experts=8, experts_per_token=3,
    expert_act="relu", router_pre_attn=True, sliding_window=8,
    window_layout=_ST_LAYOUT, rope_layout=_ST_LAYOUT,
))
# deepseek-v2-lite's shape in small: one dense layer then three expert
# layers, a latent of 32 with a RoPE key of 16, one shared expert, top-3
# not renormalised, YaRN on with an original context shorter than the
# test contexts
register(ModelConfig(
    name="tiny-deepseek-v2", family="deepseek_v2", vocab_size=256,
    hidden_size=64, intermediate_size=96, num_layers=4, num_heads=4,
    num_kv_heads=4, head_dim=32, rope_theta=10_000.0,
    rope_scaling=RopeScaling(
        rope_type="yarn", factor=4.0, beta_fast=32.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707,
        original_max_position_embeddings=64),
    rms_eps=1e-6, max_seq_len=256, num_experts=8, experts_per_token=3,
    moe_intermediate_size=32, num_shared_experts=1, norm_topk_prob=False,
    first_k_dense=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16,
))
# olmo-hybrid's shape in small: two periods of three delta-rule layers and
# a full one, a value head twice a key head, four heads so that their
# values fill one lane tile (4 x 32 = 128)
register(ModelConfig(
    name="tiny-olmo-hybrid", family="olmo_hybrid", vocab_size=256,
    hidden_size=64, intermediate_size=128, num_layers=8, num_heads=4,
    num_kv_heads=4, head_dim=16, rope_theta=0.0, rms_eps=1e-6,
    max_seq_len=256, layer_types=_OLMO_HYBRID_PERIOD * 2,
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=32,
    linear_conv_kernel=4, linear_allow_neg_eigval=True,
))
# laguna's shape in small: layer 0 (global, dense) and one period (three
# window layers, a global one), 6 / 8 query heads over 2 KV heads, a
# window shorter than the test contexts, YaRN on half a head with an
# original context shorter than they are, 16 experts top-4 and one shared
register(ModelConfig(
    name="tiny-laguna", family="laguna", vocab_size=256, hidden_size=64,
    intermediate_size=96, num_layers=5, num_heads=6, num_kv_heads=2,
    head_dim=16, rope_theta=500_000.0,
    rope_scaling=RopeScaling(
        rope_type="yarn", factor=4.0, beta_fast=64.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=0.0,
        original_max_position_embeddings=32),
    partial_rotary_factor=0.5, window_rope_theta=10_000.0, rms_eps=1e-6,
    max_seq_len=256, num_experts=16, experts_per_token=4,
    moe_intermediate_size=32, num_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, router_score="sigmoid", first_k_dense=1,
    attn_gate=True, sliding_window=8, window_layout=_LAGUNA_LAYOUT * 2,
    heads_layout=(6, 8, 8, 8) * 2,
))
# kimi-linear's shape in small: a period of four and a short last one of
# three (KDA KDA KDA MLA | KDA KDA MLA), the first layer dense, a value
# head twice a key head (4 x 32 = one lane tile), 16 experts top-4 with a
# selection bias and one shared; this chip holds experts 4-7 (the second
# of four shares), so that everything served goes through the share
register(ModelConfig(
    name="tiny-kimi-linear", family="kimi_linear", vocab_size=256,
    hidden_size=64, intermediate_size=128, num_layers=7, num_heads=4,
    num_kv_heads=4, head_dim=16, rope_theta=10_000.0, rms_eps=1e-5,
    max_seq_len=256, num_experts=16, experts_per_token=4,
    moe_intermediate_size=32, num_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.446, router_score="sigmoid", router_bias=True,
    first_k_dense=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16,
    layer_types=("linear_attention",) * 3 + ("full_attention",)
    + ("linear_attention",) * 2 + ("full_attention",),
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=32,
    linear_conv_kernel=4, linear_channel_decay=True,
    experts_held=4, experts_first=4,
))
# longcat-flash's shape in small: two blocks (four pool layers), a
# low-rank query, both latent scales, 16 experts and 8 zero-compute ones
# top-4 with a selection bias (a token in three picks a zero-compute
# expert by chance alone; eight leave no test to chance); this chip holds
# experts 4-7 (the second of four shares: neither the first nor all)
register(ModelConfig(
    name="tiny-longcat-flash", family="longcat_flash", vocab_size=256,
    hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
    num_kv_heads=4, head_dim=32, rope_theta=10_000.0, rms_eps=1e-5,
    max_seq_len=256, num_experts=16, experts_per_token=4,
    moe_intermediate_size=32, norm_topk_prob=False,
    routed_scaling_factor=6.0, router_bias=True, zero_experts=8,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
    v_head_dim=16, q_lora_rank=24, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, attn_sublayers=2,
    experts_held=4, experts_first=4,
))
# granite-hybrid's shape in small: two periods of `m m A m` (the attention
# layer inside the period, not at its end), every multiplier different
# from 1 and the attention scale not head_dim^-0.5, one B/C group, four
# state-space heads of 32 (4 x 32 = one lane tile) over a state of 16,
# 4 query heads over 2 KV heads, the head tied
register(ModelConfig(
    name="tiny-granite-hybrid", family="granite_hybrid", vocab_size=256,
    hidden_size=64, intermediate_size=128, num_layers=8, num_heads=4,
    num_kv_heads=2, head_dim=16, rope_theta=10_000.0, rms_eps=1e-5,
    tie_embeddings=True, max_seq_len=256, experts_per_token=0,
    layer_types=(("linear_attention",) * 2 + ("full_attention",)
                 + ("linear_attention",)) * 2,
    linear_num_heads=4, linear_key_head_dim=16, linear_value_head_dim=32,
    linear_conv_kernel=4, linear_groups=1,
    embedding_multiplier=3.0, residual_multiplier=0.4,
    attention_multiplier=0.125, logits_scaling=2.0,
))
register(ModelConfig(
    name="tiny-qwen2", family="qwen2", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, rms_eps=1e-6, max_seq_len=256,
    attn_bias=True,
))
register(ModelConfig(
    name="tiny-qwen3", family="qwen3", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, rms_eps=1e-6, max_seq_len=256,
    qk_norm=True,
))
register(ModelConfig(
    name="tiny-bert", family="bert_embed", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=4,
    rms_eps=1e-12, max_seq_len=128,
))
register(ModelConfig(
    name="tiny-mistral", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, max_seq_len=256, sliding_window=8,
))
# mistral-nemo's shape in small: heads x head_dim (128) is not the hidden
# size (64), 8 query / 4 KV heads so that tp:4 really splits the KV heads,
# vocabulary divisible by 4, untied, no window
register(ModelConfig(
    name="tiny-nemo", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=8, num_kv_heads=4,
    head_dim=16, rope_theta=1_000_000.0, max_seq_len=256,
))
register(ModelConfig(
    name="tiny-gemma2", family="gemma2", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, rms_eps=1e-6, tie_embeddings=True,
    max_seq_len=256, sliding_window=8, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, query_pre_attn_scalar=24,
    window_layout=_GEMMA2_LAYOUT,
))
register(ModelConfig(
    name="tiny-llava", family="llava", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=10_000.0, max_seq_len=512,
    vision=True, vision_cfg=VisionConfig(
        hidden_size=32, intermediate_size=64, num_layers=3, num_heads=2,
        image_size=28, patch_size=14, image_token=250,
    ),
))


def get_config(name: str) -> ModelConfig:
    if name in REGISTRY:
        return REGISTRY[name]
    # Ollama-style tag normalization: suffixes live in the TAG, after the
    # colon — "llama3.2:3b-instruct-fp16" → "llama3.2:3b". Splitting the
    # whole name at '-' would break hyphenated model names
    # ("mistral-nemo:12b-instruct" must not become "mistral").
    if ":" in name:
        model, tag = name.split(":", 1)
        base = f"{model}:{tag.split('-')[0]}"
        if base in REGISTRY:
            return REGISTRY[base]
    raise KeyError(f"unknown model: {name!r} (known: {sorted(REGISTRY)})")


_HF_FAMILY = {
    "llama": "llama",
    "mistral": "llama",  # llama skeleton (+ optional sliding window)
    "qwen2": "qwen2",
    "qwen3": "qwen3",
    "gemma2": "gemma2",
    "mixtral": "mixtral",
    "smallthinker": "smallthinker",
    "deepseek_v2": "deepseek_v2",
    "olmo_hybrid": "olmo_hybrid",
    "laguna": "laguna",
    "kimi_linear": "kimi_linear",
    "longcat_flash": "longcat_flash",
    "granitemoehybrid": "granite_hybrid",
    "bert": "bert_embed",
}


def config_from_hf_dir(name: str, path: str) -> ModelConfig:
    """Build a ModelConfig from a local HF checkpoint's config.json, so any
    HF-layout directory can be served without a registry entry (the engine
    falls back to this when `model` is not a registered name but a
    checkpoint_path is set). Inverse of `hf_config()` for the supported
    families."""
    import json
    import os

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    return _config_from_hf_dict(name, hf, path)


def _deepseek_v2_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """DeepseekV2ForCausalLM's published keys. What this program does not
    serve is refused here, not run wrong: a low-rank query (q_lora_rank),
    group-limited or sigmoid routing, expert layers at a stride."""
    unserved = {
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "scoring_func": hf.get("scoring_func", "softmax") != "softmax",
        "topk_method": hf.get("topk_method", "greedy") != "greedy",
        "n_group": (hf.get("n_group") or 1) != 1,
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        "attention_bias": bool(hf.get("attention_bias")),
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: deepseek_v2 with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (no q_lora, softmax scores, greedy top-k, one group, "
            "experts in every layer past the dense ones, no bias, silu)")
    scaling = None
    rs = hf.get("rope_scaling") or None
    if rs:
        if rs.get("rope_type", rs.get("type")) != "yarn":
            raise ValueError(f"{path}: deepseek_v2 rope_scaling {rs!r}")
        scaling = RopeScaling(
            rope_type="yarn", factor=float(rs["factor"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
        )
    return ModelConfig(
        name=name, family="deepseek_v2",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rope_scaling=scaling,
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings", 163_840),
        num_experts=hf["n_routed_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_shared_experts=hf.get("n_shared_experts") or 0,
        norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        first_k_dense=hf.get("first_k_dense_replace", 0),
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
    )


def _olmo_hybrid_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """OlmoHybrid's published keys. Refused, not run wrong: value heads
    that are not the key heads (a grouped delta rule), a bias, another
    activation, a rotary embedding (rope_theta is null as published: the
    recurrent layers carry position)."""
    rope = (hf.get("rope_parameters") or {}).get("rope_theta")
    unserved = {
        "linear_num_value_heads": hf["linear_num_value_heads"]
        != hf["linear_num_key_heads"],
        "num_key_value_heads": hf.get(
            "num_key_value_heads", hf["num_attention_heads"])
        != hf["num_attention_heads"],
        "attention_bias": bool(hf.get("attention_bias")),
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "rope_theta": rope is not None or hf.get("rope_theta") is not None,
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: olmo_hybrid with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (as many value heads as key heads, a query group of "
            "one, no bias, silu, no rotary embedding)")
    return ModelConfig(
        name=name, family="olmo_hybrid",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        rope_theta=0.0,
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings", 65_536),
        layer_types=tuple(hf["layer_types"]),
        linear_num_heads=hf["linear_num_key_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel=hf["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval")),
    )


def _laguna_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """Laguna's published keys. `layer_types` are ATTENTION kinds here
    (window_layout), not mixers. Refused, not run wrong: what the two
    stacks by kind do not hold (a dense layer past the leading ones, a
    scaled or partial RoPE on the window layers, another scaling rule than
    YaRN with the attention factor its own formula gives, the router's
    weight on the expert's input, a bias)."""
    kinds = list(hf["layer_types"])
    mlps = list(hf.get("mlp_layer_types") or ["sparse"] * len(kinds))
    kd = mlps.index("sparse") if "sparse" in mlps else len(mlps)
    rp = hf.get("rope_parameters") or {}
    full = dict(rp.get("full_attention") or {})
    slide = dict(rp.get("sliding_attention") or {})
    scaling = None
    if full.get("rope_type", "default") == "yarn":
        scaling = RopeScaling(
            rope_type="yarn", factor=float(full["factor"]),
            beta_fast=float(full.get("beta_fast", 32)),
            beta_slow=float(full.get("beta_slow", 1)),
            mscale=1.0, mscale_all_dim=0.0,
            original_max_position_embeddings=full[
                "original_max_position_embeddings"])
    shared = hf.get("shared_expert_intermediate_size") or 0
    width = hf["moe_intermediate_size"]
    unserved = {
        "layer_types": set(kinds) - {"full_attention", "sliding_attention"},
        "mlp_layer_types": mlps != ["dense"] * kd + ["sparse"] * (
            len(mlps) - kd),
        "rope_parameters.full_attention": full.get(
            "rope_type", "default") not in ("default", "yarn") or abs(
            float(full.get("attention_factor") or yarn_factors(scaling)[1])
            - yarn_factors(scaling)[1]) > 1e-6,
        "rope_parameters.sliding_attention": slide.get(
            "rope_type", "default") != "default" or float(
            slide.get("partial_rotary_factor", 1)) != 1.0,
        "moe_apply_router_weight_on_input": bool(
            hf.get("moe_apply_router_weight_on_input")),
        "shared_expert_intermediate_size": bool(shared % width),
        "attention_bias": bool(hf.get("attention_bias")),
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: laguna with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (window and global layers, dense layers first, YaRN or "
            "none on the global layers and a plain whole-head RoPE on the "
            "window ones, the router's weight on the output, shared "
            "experts of the routed width, no bias)")
    heads = tuple(hf.get("num_attention_heads_per_layer")
                  or [hf["num_attention_heads"]] * len(kinds))
    return ModelConfig(
        name=name, family="laguna",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim"),
        rope_theta=float(full.get("rope_theta", 10_000.0)),
        rope_scaling=scaling,
        partial_rotary_factor=float(full.get(
            "partial_rotary_factor", hf.get("partial_rotary_factor", 1.0))),
        window_rope_theta=float(slide.get("rope_theta", 10_000.0)),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings", 262_144),
        num_experts=hf["num_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=width,
        num_shared_experts=shared // width,
        norm_topk_prob=True,
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        router_score="sigmoid",
        first_k_dense=kd,
        attn_gate=bool(hf.get("gating")),
        sliding_window=hf["sliding_window"],
        window_layout=tuple(int(k == "sliding_attention") for k in kinds),
        heads_layout=heads,
    )


def _kimi_linear_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """Kimi-Linear's published keys. The mixers' pattern comes from the two
    1-based lists of `linear_attn_config`. `num_experts` is what THIS chip
    holds of each routed layer: where the file also gives `router_experts`
    (the published count, the router's width) and they differ, the layer is
    a share from `experts_first`. Refused, not run wrong: a low-rank query,
    a scaled or any rotary embedding on the latent layers, group-limited
    routing, expert layers at a stride, multi-token prediction heads,
    another score than the sigmoid, another activation than SiLU."""
    la = hf["linear_attn_config"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    depth = hf["num_hidden_layers"]
    unserved = {
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "rope_scaling": hf.get("rope_scaling") is not None,
        "mla_use_nope": not hf.get("mla_use_nope"),
        "num_expert_group": (hf.get("num_expert_group") or 1) > 1
        or (hf.get("topk_group") or 1) > 1,
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        "num_nextn_predict_layers": (
            hf.get("num_nextn_predict_layers") or 0) > 0,
        "moe_router_activation_func": hf.get(
            "moe_router_activation_func", "sigmoid") != "sigmoid",
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "linear_attn_config": bool(kda & full) or not (
            set(range(1, depth + 1)) <= kda | full),
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: kimi_linear with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (no q_lora, no rotary embedding or scaling of one, one "
            "expert group, experts in every layer past the dense ones, no "
            "multi-token prediction, sigmoid scores, silu, every layer in "
            "exactly one of kda_layers and full_attn_layers)")
    routed = hf.get("router_experts", hf["num_experts"])
    share = hf["num_experts"] != routed
    cfg = ModelConfig(
        name=name, family="kimi_linear",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=depth,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim"),
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings")
        or hf.get("model_max_length", 1_048_576),
        num_experts=routed,
        experts_per_token=hf["num_experts_per_token"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_shared_experts=hf.get("num_shared_experts") or 0,
        norm_topk_prob=bool(hf.get("moe_renormalize", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_score="sigmoid", router_bias=True,
        first_k_dense=hf.get("first_k_dense_replace", 0),
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        layer_types=tuple(
            "linear_attention" if i in kda else "full_attention"
            for i in range(1, max(kda | full) + 1)),
        linear_num_heads=la["num_heads"],
        linear_key_head_dim=la["head_dim"],
        linear_value_head_dim=la["head_dim"],
        linear_conv_kernel=la["short_conv_kernel_size"],
        linear_channel_decay=True,
        experts_held=hf["num_experts"] if share else None,
        experts_first=(hf.get("experts_first") or 0) if share else None,
    )
    cfg.layer_period        # refuses a pattern that is not periods
    return cfg


def _longcat_flash_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """LongCat-Flash's published keys (`num_layers`, `ffn_hidden_size`,
    `expert_ffn_hidden_size`, `moe_topk`, `zero_expert_num`, ...): a block
    is two latent-attention sublayers, two dense SwiGLUs and one expert
    layer on a shortcut. `n_routed_experts` is what THIS chip holds of each
    block's routed experts: where the file also gives `router_experts`
    (the published count) and they differ, the block is a share from
    `experts_first`; the router is `router_experts + zero_expert_num`
    wide either way. `vocab_held`, where the file gives it, is the rows
    of the vocabulary this chip holds (`ModelConfig.vocab_held`: a slice
    of the embedding and the head, over which the traffic, the logits and
    the sampling run). Refused, not run wrong: a full-rank query, another
    attention than MLA, a scaled rotary embedding, zero-compute experts of
    another kind than identity, a bias on the projections."""
    unserved = {
        "q_lora_rank": not hf.get("q_lora_rank"),
        "attention_method": hf.get("attention_method", "MLA") != "MLA",
        "rope_scaling": hf.get("rope_scaling") is not None,
        "zero_expert_type": bool(hf.get("zero_expert_num")) and hf.get(
            "zero_expert_type", "identity") != "identity",
        "attention_bias": bool(hf.get("attention_bias")),
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: longcat_flash with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (a low-rank query, MLA, an unscaled rotary embedding, "
            "identity zero-compute experts, no bias, silu)")
    routed = hf.get("router_experts", hf["n_routed_experts"])
    share = hf["n_routed_experts"] != routed
    return ModelConfig(
        name=name, family="longcat_flash",
        vocab_size=hf["vocab_size"], vocab_held=hf.get("vocab_held"),
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["ffn_hidden_size"],
        num_layers=hf["num_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        rope_theta=float(hf.get("rope_theta", 10_000.0)),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings", 131_072),
        num_experts=routed,
        experts_per_token=hf["moe_topk"],
        moe_intermediate_size=hf["expert_ffn_hidden_size"],
        norm_topk_prob=False,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_bias=True,
        zero_experts=hf.get("zero_expert_num") or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        q_lora_rank=hf["q_lora_rank"],
        mla_scale_q_lora=bool(hf.get("mla_scale_q_lora")),
        mla_scale_kv_lora=bool(hf.get("mla_scale_kv_lora")),
        attn_sublayers=2,
        experts_held=hf["n_routed_experts"] if share else None,
        experts_first=(hf.get("experts_first") or 0) if share else None,
    )


def _granite_hybrid_from_hf(name: str, hf: dict, path: str) -> ModelConfig:
    """GraniteMoeHybrid's published keys: Mamba-2 layers (`mamba_*`) among
    attention layers (`layer_types`: "mamba" / "attention"), the family's
    four multipliers, a shared SwiGLU of `shared_intermediate_size`.
    Refused by name, not run wrong: routed experts beside the shared MLP
    (`num_local_experts` > 0: no configuration proves that part), more
    B / C groups than one, a positional embedding, a bias on a projection,
    another activation or norm, an inner width that is not heads x head, no
    attention multiplier (the family has no head^-0.5 to fall back to)."""
    heads, dh = hf["mamba_n_heads"], hf["mamba_d_head"]
    kinds = {"mamba": "linear_attention", "attention": "full_attention"}
    unserved = {
        "num_local_experts": bool(hf.get("num_local_experts")),
        "mamba_n_groups": hf.get("mamba_n_groups", 1) != 1,
        "position_embedding_type":
            hf.get("position_embedding_type", "nope") != "nope",
        "attention_bias": bool(hf.get("attention_bias")),
        "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
        "mamba_conv_bias": not hf.get("mamba_conv_bias", True),
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "normalization_function":
            hf.get("normalization_function", "rmsnorm") != "rmsnorm",
        "mamba_expand": hf["mamba_expand"] * hf["hidden_size"] != heads * dh,
        "layer_types": not set(hf["layer_types"]) <= set(kinds),
        "attention_multiplier": not hf.get("attention_multiplier"),
    }
    if any(unserved.values()):
        raise ValueError(
            f"{path}: granitemoehybrid with "
            f"{[k for k, v in unserved.items() if v]} as published is not "
            "served (no routed experts, one B/C group, no positional "
            "embedding, no projection bias, a convolution bias, silu, "
            "rmsnorm, expand x hidden = heads x head, an attention "
            "multiplier)")
    return ModelConfig(
        name=name, family="granite_hybrid",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        # read and not used: position_embedding_type is nope, and no
        # other is served (the published file carries 10000 all the same)
        rope_theta=hf.get("rope_theta", 10_000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_seq_len=hf.get("max_position_embeddings", 131_072),
        experts_per_token=0,
        layer_types=tuple(kinds[t] for t in hf["layer_types"]),
        linear_num_heads=heads,
        linear_key_head_dim=hf["mamba_d_state"],
        linear_value_head_dim=dh,
        linear_conv_kernel=hf["mamba_d_conv"],
        linear_groups=hf.get("mamba_n_groups", 1),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
    )


def _config_from_hf_dict(name: str, hf: dict, path: str) -> ModelConfig:
    mt = hf.get("model_type", "llama")
    if mt == "llava":
        vc = hf.get("vision_config") or {}
        text = dict(hf.get("text_config") or {})
        text.setdefault("model_type", "llama")
        # llava text_configs may be sparse (LlamaConfig defaults implied)
        for k, v in (("vocab_size", 32_064), ("hidden_size", 4096),
                     ("intermediate_size", 11_008), ("num_hidden_layers", 32),
                     ("num_attention_heads", 32),
                     ("max_position_embeddings", 4096)):
            text.setdefault(k, v)
        # only keys the HF config actually carries — VisionConfig's field
        # defaults (the single source of truth) fill the rest
        vkeys = {
            "hidden_size": vc.get("hidden_size"),
            "intermediate_size": vc.get("intermediate_size"),
            "num_layers": vc.get("num_hidden_layers"),
            "num_heads": vc.get("num_attention_heads"),
            "image_size": vc.get("image_size"),
            "patch_size": vc.get("patch_size"),
            "layer_norm_eps": vc.get("layer_norm_eps"),
            "feature_layer": hf.get("vision_feature_layer"),
            "image_token": hf.get("image_token_index"),
        }
        return dataclasses.replace(
            _config_from_hf_dict(name, text, path),
            family="llava", vision=True,
            vision_cfg=VisionConfig(
                **{k: v for k, v in vkeys.items() if v is not None}
            ),
        )
    if mt not in _HF_FAMILY:
        raise ValueError(
            f"unsupported HF model_type {mt!r} in {path} "
            f"(supported: {sorted(_HF_FAMILY)} + llava)"
        )
    family = _HF_FAMILY[mt]
    if family == "bert_embed":
        return ModelConfig(
            name=name, family=family,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            rms_eps=hf.get("layer_norm_eps", 1e-12),
            max_seq_len=hf.get("max_position_embeddings", 512),
        )
    if family == "smallthinker":
        if not (hf.get("moe_primary_router_apply_softmax")
                and hf.get("norm_topk_prob", True)):
            raise ValueError(
                f"{path}: smallthinker with a sigmoid or unnormalised "
                "router is not served (softmax over the chosen only)")
        return ModelConfig(
            name=name, family=family,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["moe_ffn_hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf["rope_theta"]),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            max_seq_len=hf.get("max_position_embeddings", 16_384),
            num_experts=hf["moe_num_primary_experts"],
            experts_per_token=hf["moe_num_active_primary_experts"],
            expert_act="relu", router_pre_attn=True,
            sliding_window=hf["sliding_window_size"],
            window_layout=tuple(hf["sliding_window_layout"]),
            rope_layout=tuple(hf["rope_layout"]),
        )
    if family == "deepseek_v2":
        return _deepseek_v2_from_hf(name, hf, path)
    if family == "olmo_hybrid":
        return _olmo_hybrid_from_hf(name, hf, path)
    if family == "laguna":
        return _laguna_from_hf(name, hf, path)
    if family == "kimi_linear":
        return _kimi_linear_from_hf(name, hf, path)
    if family == "longcat_flash":
        return _longcat_flash_from_hf(name, hf, path)
    if family == "granite_hybrid":
        return _granite_hybrid_from_hf(name, hf, path)
    scaling = None
    rs = hf.get("rope_scaling") or None
    if rs and rs.get("rope_type", rs.get("type")) == "llama3":
        scaling = RopeScaling(
            factor=rs["factor"],
            low_freq_factor=rs["low_freq_factor"],
            high_freq_factor=rs["high_freq_factor"],
            original_max_position_embeddings=rs["original_max_position_embeddings"],
        )
    return ModelConfig(
        name=name, family=family,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim"),
        rope_theta=hf.get("rope_theta", 10_000.0),
        rope_scaling=scaling,
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        # gemma2 checkpoints tie embeddings without always saying so
        tie_embeddings=hf.get("tie_word_embeddings", family == "gemma2"),
        max_seq_len=hf.get("max_position_embeddings", 8192),
        num_experts=hf.get("num_local_experts", 0),
        experts_per_token=hf.get("num_experts_per_tok", 2),
        # qwen2-style configs carry sliding_window with
        # use_sliding_window=false — honoring it would break the family's
        # full-attention contract (and trip _check_supported)
        sliding_window=(
            (hf.get("sliding_window") or 0)
            if hf.get("use_sliding_window", True) else 0
        ),
        attn_bias=family == "qwen2" or bool(hf.get("attention_bias")),
        qk_norm=family == "qwen3",
        attn_logit_softcap=hf.get("attn_logit_softcapping") or 0.0,
        final_logit_softcap=hf.get("final_logit_softcapping") or 0.0,
        query_pre_attn_scalar=hf.get("query_pre_attn_scalar"),
        window_layout=(_GEMMA2_LAYOUT * -(-hf["num_hidden_layers"] // 2)
                       if family == "gemma2" else ()),
    )
