"""Model configuration for the standalone transformer LM family.

Reference: apex/transformer/testing/arguments.py (971 LoC of Megatron-style
argparse) collapses here into one frozen dataclass — the only fields the
standalone GPT/BERT models (standalone_transformer_lm.py:1358
``TransformerLanguageModel``) actually consume, plus the TPU-specific knobs
(dtypes, remat, scan-over-layers).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

__all__ = ["TransformerConfig", "gpt_tiny", "gpt_125m", "bert_large",
           "lfm2_moe", "nemotron_h", "joyai_llm_flash"]

LAYER_KINDS = ("attention", "conv", "mamba", "moe", "mla")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static hyperparameters of a ParallelTransformer LM.

    Mirrors the subset of reference ``arguments.py`` used by
    ``standalone_transformer_lm.py`` (hidden_size, num_layers,
    num_attention_heads, ffn_hidden_size, kv_channels,
    max_position_embeddings, padded_vocab_size, hidden_dropout,
    attention_dropout, init_method_std,
    untie_embeddings_and_output_weights…).
    """

    num_layers: int = 2
    hidden_size: int = 128
    num_attention_heads: int = 8
    # grouped-query attention (beyond the reference, whose Megatron-era
    # model is MHA-only): K/V get num_query_groups heads shared by
    # num_attention_heads/groups queries each (GQA, arXiv:2305.13245;
    # groups=1 is MQA).  None = num_attention_heads = classic MHA.  The
    # decode KV cache stores only the group heads — the main win.
    num_query_groups: Optional[int] = None
    ffn_hidden_size: Optional[int] = None         # default 4*h (2/3*4h swiglu)
    kv_channels: Optional[int] = None             # default h // nh
    vocab_size: int = 1024                        # padded to tp divisibility
    max_position_embeddings: int = 512

    # architecture switches
    attn_mask_type: str = "causal"                # 'causal' | 'padding'
    # 'gelu' | 'gelu_tanh' | 'swiglu' | 'relu2' (relu(x)^2, not gated:
    # the ragged expert path and the shared expert of a hybrid stack)
    activation: str = "gelu"
    # 'learned' | 'rope' | 'none' (no positions: a stack whose
    # state-space layers carry the order)
    position_embedding_type: str = "learned"
    normalization: str = "layernorm"              # 'layernorm' | 'rmsnorm'
    untie_embeddings_and_output_weights: bool = False
    layernorm_epsilon: float = 1e-5
    # take the residual from the LN output instead of the block input
    # (reference standalone_transformer_lm.py:620,707,738)
    apply_residual_connection_post_layernorm: bool = False

    # mixture-of-experts (beyond the reference; transformer/moe.py)
    num_experts: "Optional[int]" = None           # None = dense FFN
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_aux_loss_coeff: float = 1e-2
    moe_ep_axis: str = "ep"                       # expert mesh axis name
    # 'capacity' = Switch drop-token einsums (GSPMD-inferred EP);
    # 'ragged' = capacity-free sort-by-expert routing through the
    # grouped matmul with explicit compressed/overlapped EP dispatch
    moe_routing: str = "capacity"
    # EP dispatch/combine wire dtype on the ragged path ('fp32' | 'bf16'
    # | 'int8' — the grad_comm= surface applied to expert all-to-alls)
    moe_comm: str = "fp32"

    # 'softmax' = Switch probabilities; 'sigmoid' = float32 sigmoid
    # scores, a selection-only bias, gates normalised over the chosen
    # (ragged routing, no auxiliary loss)
    moe_router: str = "softmax"
    # (first, count): the expert slabs hold these of the router's
    # num_experts — one chip's share of an expert-parallel layer, run
    # without its exchange.  None = all experts are held
    moe_experts_held: Optional[tuple] = None

    # gates of the sigmoid router: chosen / (sum of the chosen +
    # moe_gate_epsilon) * moe_routed_scaling.  The epsilon is a field
    # only to pin lowerings: 1e-6 keeps LFM2's step the program the
    # benchmark accepted, 1e-20 is Nemotron-H's; the two differ by some
    # 4 float32 ulp of a gate.  One constant, once LFM2 is re-baselined
    moe_routed_scaling: float = 1.0
    moe_gate_epsilon: float = 1e-6
    # width of one expert that every token passes and no gate of the
    # router weighs, added to the routed sum (hybrid stacks); of the
    # experts' own form ('relu2', or gated 'swiglu'); None = none
    moe_shared_expert_size: Optional[int] = None

    # stacks whose layers differ in kind (models/hybrid.py): one of
    # LAYER_KINDS per layer ('conv' = gated short convolution in place
    # of attention); None = attention everywhere, the homogeneous stack
    layer_types: Optional[tuple] = None
    conv_kernel_size: int = 3
    # a 'mamba' or 'moe' kind makes the stack one of single mixers (the
    # mixer_only property).  The Mamba-2 mixer: heads x head size is its
    # inner width, B and C are shared by heads // ssm_groups heads, a
    # state is [head size, ssm_state_size], the scan works chunks of
    # ssm_chunk_size positions (ops/ssd_scan.py); conv_kernel_size is
    # its taps
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    ssm_groups: int = 1
    ssm_chunk_size: int = 128
    # (min, max, floor) of the time step that dt_bias is drawn for
    mamba_time_step: tuple = (1e-3, 1e-1, 1e-4)
    # with num_experts set, the first num_dense_layers layers keep a
    # dense FFN of width dense_ffn_hidden_size
    num_dense_layers: int = 0
    dense_ffn_hidden_size: Optional[int] = None
    rope_theta: float = 10000.0
    # RMSNorm over each head's channels of q and k, before rope
    qk_norm: bool = False
    # an 'mla' layer (multi-head latent attention, models/hybrid.py
    # mla_attention): queries through a latent of mla_q_rank, keys and
    # values through one of mla_kv_rank, both RMS-normed; a head's q and
    # k are [mla_nope_dim without position | mla_rope_dim rotary], its v
    # mla_v_dim wide; the rotary key is one head shared by all (its
    # width, kv_channels, sizes the rope table), turned in pairs
    # (2i, 2i+1)
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    # multi-token prediction (arXiv 2412.19437, section 2.2): mtp_layers
    # (0 or 1) modules after the stack, each one more block over
    # [norm(embedding of the next token) ; norm(hidden)] and the same
    # head, trained on the token two ahead; the loss adds
    # mtp_loss_weight times theirs.  The step takes a third input
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # False: no bias leaf on any projection (hybrid stacks only)
    use_bias: bool = True

    # regularization
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    # stochastic depth on the residual branches (reference drop_path,
    # standalone_transformer_lm.py:712-728 DropPath)
    drop_path_rate: float = 0.0
    init_method_std: float = 0.02

    # numerics / TPU execution
    params_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    softmax_in_fp32: bool = True
    attention_backend: str = "flash"              # 'flash' | 'fused_softmax'
    # jax.checkpoint each layer; all of it is recomputed in the backward
    # pass except flash attention's output and logsumexp, which are kept
    # (17.3 MB a layer at b8 x s1024 x 1024 against a second kernel run)
    remat: bool = False
    scan_layers: bool = True                      # lax.scan over the stack
    # fuse the LM-head matmul into the CE loss, chunked over tokens, so
    # the [tokens, vocab] logits never hit HBM (ops/lm_head_ce.py);
    # applies to the training loss on the non-vocab-parallel path only
    fused_head_ce: bool = False
    head_ce_chunk: int = 2048

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            ffn = (
                int(4 * self.hidden_size * 2 / 3)
                if self.activation == "swiglu"
                else 4 * self.hidden_size
            )
            object.__setattr__(self, "ffn_hidden_size", ffn)
        if self.kv_channels is None:
            if self.hidden_size % self.num_attention_heads:
                raise ValueError(
                    "num_attention_heads must divide hidden_size when "
                    "kv_channels is not given"
                )
            object.__setattr__(
                self, "kv_channels",
                self.hidden_size // self.num_attention_heads,
            )
        if self.moe_routing not in ("capacity", "ragged"):
            raise ValueError(
                f"moe_routing ({self.moe_routing!r}) must be 'capacity' "
                "or 'ragged'")
        if self.moe_comm not in ("fp32", "bf16", "int8"):
            raise ValueError(
                f"moe_comm ({self.moe_comm!r}) must be 'fp32', 'bf16' "
                "or 'int8'")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_router ({self.moe_router!r}) must be 'softmax' or "
                "'sigmoid'")
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)      # a JSON list is no key
            object.__setattr__(self, "layer_types", kinds)
            if len(kinds) != self.num_layers or set(kinds) - set(
                    LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name one of {LAYER_KINDS} for "
                    f"each of the {self.num_layers} layers, got {kinds}")
            if "mamba" in kinds and (
                    self.mamba_num_heads < 1
                    or self.mamba_num_heads % self.ssm_groups):
                raise ValueError(
                    f"mamba_num_heads ({self.mamba_num_heads}) must be a "
                    f"positive multiple of ssm_groups ({self.ssm_groups})")
        if self.mixer_only and (
                "conv" in self.layer_types or self.num_dense_layers
                or ("moe" in self.layer_types) != bool(self.num_experts)):
            raise ValueError(
                "'mamba' and 'moe' layers are single mixers: beside them "
                "only 'attention', num_experts exactly where a 'moe' "
                "layer is, and no num_dense_layers")
        if self.moe_shared_expert_size is not None and (
                self.activation not in ("relu2", "swiglu")
                or not self.num_experts):
            raise ValueError(
                "a shared expert stands beside routed 'relu2' or "
                "'swiglu' experts (num_experts, activation)")
        if "mla" in (self.layer_types or ()) and (
                self.mla_q_rank < 1 or self.mla_kv_rank < 1
                or self.mla_rope_dim % 2
                or self.kv_channels != self.mla_rope_dim
                or self.position_embedding_type != "rope"):
            raise ValueError(
                "an 'mla' layer needs mla_q_rank, mla_kv_rank, an even "
                "mla_rope_dim that kv_channels repeats, and "
                "position_embedding_type='rope'")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and not self.is_hybrid):
            raise ValueError(
                "mtp_layers is 0 or 1, on a hybrid stack (layer_types)")
        if self.moe_experts_held is not None:
            first, count = (int(v) for v in self.moe_experts_held)
            object.__setattr__(self, "moe_experts_held", (first, count))
            if (not self.num_experts or first < 0 or count < 1
                    or first + count > self.num_experts):
                raise ValueError(
                    f"moe_experts_held {(first, count)} is no range of "
                    f"the {self.num_experts} experts")
        if self.num_dense_layers and not (
                self.num_experts and self.dense_ffn_hidden_size):
            raise ValueError(
                "num_dense_layers needs num_experts (the layers after "
                "them) and dense_ffn_hidden_size")
        if self.is_hybrid and (
                self.moe_routing != "ragged" and self.num_experts):
            raise ValueError("a hybrid stack's experts need "
                             "moe_routing='ragged'")
        if not self.is_hybrid and (
                self.moe_router != "softmax" or self.qk_norm
                or self.moe_experts_held is not None
                or not self.use_bias or self.activation == "relu2"
                or self.moe_shared_expert_size is not None
                or self.position_embedding_type == "none"):
            raise ValueError(
                "moe_router='sigmoid', moe_experts_held, qk_norm, "
                "use_bias=False, activation='relu2', a shared expert and "
                "position_embedding_type='none' belong to the hybrid "
                "stack: set layer_types")
        if self.num_query_groups is not None:
            if (self.num_query_groups < 1
                    or self.num_attention_heads % self.num_query_groups):
                raise ValueError(
                    f"num_query_groups ({self.num_query_groups}) must "
                    f"be a positive divisor of num_attention_heads "
                    f"({self.num_attention_heads})")

    @property
    def is_hybrid(self) -> bool:
        """True when the layers differ (kind of operator, or dense FFN
        before expert layers): the stack is built layer by layer
        (models/hybrid.py) instead of one scanned ``[L, ...]`` tree."""
        return self.layer_types is not None or self.num_dense_layers > 0

    @property
    def mixer_only(self) -> bool:
        """True when a layer is of kind 'mamba' or 'moe': every layer is
        then ONE mixer, ``x + mixer(norm(x))``, of its kind (the third is
        'attention') and no FFN follows an operator.  False: operator,
        then dense FFN or experts."""
        return bool({"mamba", "moe"} & set(self.layer_types or ()))

    @property
    def held_experts(self) -> tuple:
        """``(first, count)`` of the experts whose weights are held: all
        of them unless ``moe_experts_held`` says which."""
        return self.moe_experts_held or (0, self.num_experts or 0)

    @property
    def projection_size(self) -> int:
        return self.kv_channels * self.num_attention_heads

    @property
    def kv_groups(self) -> int:
        """Number of K/V heads (== num_attention_heads for MHA)."""
        return (self.num_query_groups
                if self.num_query_groups is not None
                else self.num_attention_heads)

    @property
    def kv_projection_size(self) -> int:
        return self.kv_channels * self.kv_groups

    @property
    def is_gqa(self) -> bool:
        """True when K/V heads differ from query heads (grouped-query).

        Selects the group-major qkv layout — per query group
        ``[q x rep | k | v]`` heads (see ``split_qkv_gqa``, the one
        layout definition) — instead of the legacy per-head-interleaved
        layout, which is kept bit-identical for MHA (golden traces + HF
        import depend on it)."""
        return self.kv_groups != self.num_attention_heads


def gpt_tiny(**kw) -> TransformerConfig:
    """Four-layer toy GPT for tests/dryruns."""
    kw.setdefault("num_layers", 4)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_attention_heads", 8)
    kw.setdefault("vocab_size", 512)
    kw.setdefault("max_position_embeddings", 128)
    return TransformerConfig(**kw)


def gpt_125m(**kw) -> TransformerConfig:
    """GPT-2 125M — the reference's benchmark config
    (BASELINE.json: 'GPT-2 125M: FusedLayerNorm + scaled softmax + RoPE')."""
    kw.setdefault("num_layers", 12)
    kw.setdefault("hidden_size", 768)
    kw.setdefault("num_attention_heads", 12)
    kw.setdefault("vocab_size", 50304)            # 50257 padded to 128
    kw.setdefault("max_position_embeddings", 1024)
    return TransformerConfig(**kw)


def bert_large(**kw) -> TransformerConfig:
    """BERT-large pretrain shape (BASELINE.json FusedLAMB config)."""
    kw.setdefault("num_layers", 24)
    kw.setdefault("hidden_size", 1024)
    kw.setdefault("num_attention_heads", 16)
    kw.setdefault("vocab_size", 30592)            # 30522 padded to 128
    kw.setdefault("max_position_embeddings", 512)
    kw.setdefault("attn_mask_type", "padding")    # bidirectional encoder
    return TransformerConfig(**kw)


def lfm2_moe(*, hidden_size: int, num_hidden_layers: int, layer_types,
             num_attention_heads: int, num_key_value_heads: int,
             intermediate_size: int, moe_intermediate_size: int,
             num_dense_layers: int, num_experts: int,
             num_experts_per_tok: int, vocab_size: int,
             conv_L_cache: int = 3, norm_eps: float = 1e-5,
             rope_theta: float = 1e6, max_position_embeddings: int = 128000,
             experts_held=None, **kw) -> TransformerConfig:
    """The LFM2 mixture-of-experts family (``model_type`` ``lfm2_moe``)
    from its published ``config.json`` keys: gated short-convolution and
    grouped-query attention layers by ``layer_types``, RMSNorm, q/k norm,
    rope, bias-free projections, ``num_dense_layers`` SwiGLU layers and
    then sigmoid-routed experts (selection bias, gates normalised over
    the chosen, no auxiliary loss).  ``num_experts`` is the router's
    width; ``experts_held=(first, count)`` makes the expert layers one
    chip's share of an expert-parallel deployment.  Further keywords go
    to :class:`TransformerConfig` (``remat``, ``fused_head_ce`` …)."""
    kinds = tuple("attention" if k == "full_attention" else k
                  for k in layer_types)
    kw.setdefault("moe_aux_loss_coeff", 0.0)
    return TransformerConfig(
        num_layers=num_hidden_layers, hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        num_query_groups=num_key_value_heads,
        ffn_hidden_size=moe_intermediate_size,
        dense_ffn_hidden_size=intermediate_size,
        num_dense_layers=num_dense_layers, num_experts=num_experts,
        moe_top_k=num_experts_per_tok, moe_routing="ragged",
        moe_router="sigmoid",
        moe_experts_held=(tuple(experts_held) if experts_held is not None
                          else None),
        layer_types=kinds, conv_kernel_size=conv_L_cache,
        vocab_size=vocab_size,
        max_position_embeddings=max_position_embeddings,
        activation="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", rope_theta=float(rope_theta),
        qk_norm=True, use_bias=False, layernorm_epsilon=norm_eps,
        scan_layers=False, **kw)


def nemotron_h(*, hidden_size: int, num_hidden_layers: int,
               hybrid_override_pattern: str, num_attention_heads: int,
               num_key_value_heads: int, head_dim: int,
               mamba_num_heads: int, mamba_head_dim: int,
               ssm_state_size: int, n_groups: int, conv_kernel: int,
               chunk_size: int, moe_intermediate_size: int,
               moe_shared_expert_intermediate_size: int,
               n_routed_experts: int, num_experts_per_tok: int,
               routed_scaling_factor: float, vocab_size: int,
               norm_eps: float = 1e-5, time_step_min: float = 1e-3,
               time_step_max: float = 1e-1, time_step_floor: float = 1e-4,
               max_position_embeddings: int = 262144, experts_held=None,
               **kw) -> TransformerConfig:
    """The Nemotron-H family (``model_type`` ``nemotron_h``) from its
    published ``config.json`` keys: one mixer a layer by
    ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer, ``E`` an expert
    layer, ``*`` grouped-query attention without positions), RMSNorm,
    bias-free projections, sigmoid-routed ``relu2`` experts (selection
    bias, gates normalised over the chosen and scaled by
    ``routed_scaling_factor``) beside one shared expert, an untied head.
    ``n_routed_experts`` is the router's width; ``experts_held=(first,
    count)`` makes the expert layers one chip's share of an
    expert-parallel deployment.  Further keywords go to
    :class:`TransformerConfig` (``remat``, ``fused_head_ce`` ...)."""
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    if (len(hybrid_override_pattern) != num_hidden_layers
            or set(hybrid_override_pattern) - set(kinds)
            or not set(hybrid_override_pattern) & {"M", "E"}):
        raise ValueError(
            f"hybrid_override_pattern {hybrid_override_pattern!r} must "
            f"name M, E or * for each of the {num_hidden_layers} layers, "
            "an M or an E among them (they make the layers single "
            "mixers: cfg.mixer_only)")
    kw.setdefault("moe_aux_loss_coeff", 0.0)
    experts = {}
    if "E" in hybrid_override_pattern:
        experts = dict(
            num_experts=n_routed_experts,
            moe_shared_expert_size=moe_shared_expert_intermediate_size,
            moe_experts_held=(tuple(experts_held)
                              if experts_held is not None else None))
    return TransformerConfig(
        num_layers=num_hidden_layers, hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        num_query_groups=num_key_value_heads, kv_channels=head_dim,
        ffn_hidden_size=moe_intermediate_size,
        moe_top_k=num_experts_per_tok, moe_routing="ragged",
        moe_router="sigmoid",
        moe_routed_scaling=float(routed_scaling_factor),
        moe_gate_epsilon=1e-20,
        layer_types=tuple(kinds[c] for c in hybrid_override_pattern),
        mamba_num_heads=mamba_num_heads,
        mamba_head_dim=mamba_head_dim, ssm_state_size=ssm_state_size,
        ssm_groups=n_groups, ssm_chunk_size=chunk_size,
        conv_kernel_size=conv_kernel,
        mamba_time_step=(time_step_min, time_step_max, time_step_floor),
        vocab_size=vocab_size,
        max_position_embeddings=max_position_embeddings,
        activation="relu2", normalization="rmsnorm",
        position_embedding_type="none", use_bias=False,
        untie_embeddings_and_output_weights=True,
        layernorm_epsilon=norm_eps, scan_layers=False, **experts, **kw)


def joyai_llm_flash(*, hidden_size: int, num_hidden_layers: int,
                    num_attention_heads: int, q_lora_rank: int,
                    kv_lora_rank: int, qk_nope_head_dim: int,
                    qk_rope_head_dim: int, v_head_dim: int,
                    intermediate_size: int, moe_intermediate_size: int,
                    first_k_dense_replace: int, n_routed_experts: int,
                    n_shared_experts: int, num_experts_per_tok: int,
                    routed_scaling_factor: float, vocab_size: int,
                    num_nextn_predict_layers: int = 0,
                    mtp_loss_weight: float = 0.3,
                    rms_norm_eps: float = 1e-6, rope_theta: float = 3.2e7,
                    max_position_embeddings: int = 131072,
                    experts_held=None, **kw) -> TransformerConfig:
    """The DeepSeek-V3 layer as JoyAI-LLM-Flash publishes it
    (``model_type`` ``joyai_llm_flash``) from its ``config.json`` keys:
    multi-head latent attention in every layer (rope in interleaved pairs
    on the rotary channels, no rope scaling), RMSNorm, bias-free
    projections, ``first_k_dense_replace`` SwiGLU layers and then
    sigmoid-routed gated experts (``noaux_tc`` with one group: selection
    bias, gates normalised over the chosen and scaled by
    ``routed_scaling_factor``) beside ``n_shared_experts`` gated shared
    experts (one expert of their joint width), an untied head, and
    ``num_nextn_predict_layers`` multi-token-prediction modules in the
    loss (``mtp_loss_weight`` is not a published key).
    ``n_routed_experts`` is the router's width; ``experts_held=(first,
    count)`` makes the expert layers one chip's share of an
    expert-parallel deployment.  Further keywords go to
    :class:`TransformerConfig` (``remat``, ``fused_head_ce`` ...)."""
    kw.setdefault("moe_aux_loss_coeff", 0.0)
    return TransformerConfig(
        num_layers=num_hidden_layers, hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        kv_channels=qk_rope_head_dim,
        mla_q_rank=q_lora_rank, mla_kv_rank=kv_lora_rank,
        mla_nope_dim=qk_nope_head_dim, mla_rope_dim=qk_rope_head_dim,
        mla_v_dim=v_head_dim,
        ffn_hidden_size=moe_intermediate_size,
        dense_ffn_hidden_size=intermediate_size,
        num_dense_layers=first_k_dense_replace,
        num_experts=n_routed_experts,
        moe_shared_expert_size=(n_shared_experts * moe_intermediate_size
                                or None),
        moe_top_k=num_experts_per_tok, moe_routing="ragged",
        moe_router="sigmoid",
        moe_routed_scaling=float(routed_scaling_factor),
        moe_gate_epsilon=1e-20,
        moe_experts_held=(tuple(experts_held) if experts_held is not None
                          else None),
        layer_types=("mla",) * num_hidden_layers,
        mtp_layers=num_nextn_predict_layers,
        mtp_loss_weight=float(mtp_loss_weight), vocab_size=vocab_size,
        max_position_embeddings=max_position_embeddings,
        activation="swiglu", normalization="rmsnorm",
        position_embedding_type="rope", rope_theta=float(rope_theta),
        use_bias=False, untie_embeddings_and_output_weights=True,
        layernorm_epsilon=rms_norm_eps, scan_layers=False, **kw)
