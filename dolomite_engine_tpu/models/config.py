"""Model configuration classes.

Parity: reference `dolomite_engine/hf_models/config.py:6-111` (`CommonConfig`, a GPT-2-style HF
PretrainedConfig). Same field names and validation semantics, but a plain JSON-serializable
dataclass — the JAX model code is functional and does not inherit from HF machinery. The
`attribute_map` aliases (hidden_size -> n_embd etc.) are exposed as properties for interop code.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field

from .enums import AttentionHeadType, InitMethod, PositionEmbeddingType


@dataclass
class CommonConfig:
    model_type: str = "gpt_dolomite"

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    num_key_value_heads: int | None = None
    n_inner: int | None = None
    activation_function: str = "gelu_pytorch_tanh"
    attention_head_type: str = "mqa"
    resid_pdrop: float = 0.1
    embd_pdrop: float = 0.1
    attn_pdrop: float = 0.1
    normalization_function: str = "layernorm"
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    scale_attn_weights: bool = True
    attention_multiplier: float | None = None
    use_cache: bool = True
    bos_token_id: int = 50256
    eos_token_id: int = 50256
    pad_token_id: int = 50256
    attention_softmax_in_fp32: bool = True
    add_bias: bool = True
    position_embedding_type: str = "learned_absolute"
    rope_theta: float = 10000
    rope_scaling: dict | None = None
    m_emb: float | None = None
    m_width: float | None = None
    m_residual: float | None = None
    init_method: str = "normal"
    upcast_logits_for_loss: bool = False
    tie_word_embeddings: bool = True
    # TPU-only addition (no reference counterpart): compute the LM-head loss chunked along the
    # sequence axis without materializing [B, S, V] logits (ops/loss.py
    # fused_linear_cross_entropy). Training-only; requires tie_word_embeddings. The full-logits
    # tensor is the largest single allocation in a train step at 50k vocab.
    fused_lm_head_loss: bool = False
    loss_chunk_size: int = 256
    # PaLM-style z-loss: coef * mean(logsumexp(logits)^2) added to the LM loss, keeping
    # the softmax normalizer near 1 (stabilizes bf16 pretraining). 0 disables. Computed
    # identically by the plain and the chunked fused loss paths (ops/loss.py).
    z_loss_coef: float = 0.0
    # per-head width when it differs from n_embd // n_head (HF T5's d_kv: flan-t5-small is
    # 512 wide with 6 heads of 64); None derives it from n_embd
    attention_head_dim: int | None = None
    # an RMSNorm of every query and key head over its columns (one weight of head_dim each,
    # eps `layer_norm_epsilon`) between the QKV split and the rotation
    # (`ops/rope.split_qkv_apply_rope`)
    qk_norm: bool = False
    # ``sigmoid(W_g h)``, as wide as the heads' output, multiplied into it before the
    # out-projection (`modeling_utils.Attention`, scope ``attention_gate``)
    attention_output_gate: bool = False

    def __post_init__(self) -> None:
        if self.n_inner is None:
            self.n_inner = 4 * self.n_embd

        if (
            self.fused_lm_head_loss
            and not self.tie_word_embeddings
            and not self.fused_loss_reads_untied_head()
        ):
            raise ValueError(
                "fused_lm_head_loss requires tie_word_embeddings (the chunked loss reads the "
                "tied embedding table; an untied lm_head would silently fall back to "
                "materializing full logits)"
            )

        if self.attention_multiplier is not None:
            assert self.scale_attn_weights

        # validate enums
        InitMethod(self.init_method)
        pe_type = PositionEmbeddingType(self.position_embedding_type)
        if pe_type not in self.supported_position_embeddings():
            # an unsupported type would build a model with NO position information and
            # train silently position-blind — fail at config time instead
            raise ValueError(
                f"{type(self).__name__} does not support position_embedding_type="
                f"'{self.position_embedding_type}' (supported: "
                f"{sorted(t.value for t in self.supported_position_embeddings())})"
            )
        head_type = AttentionHeadType(self.attention_head_type)

        if head_type == AttentionHeadType.mha:
            if self.num_key_value_heads is None:
                self.num_key_value_heads = self.n_head
            assert self.n_head == self.num_key_value_heads, (
                "MultiHeadAttention should have same number of heads for query, keys and values"
            )
        elif head_type == AttentionHeadType.mqa:
            if self.num_key_value_heads is None:
                self.num_key_value_heads = 1
            assert self.num_key_value_heads == 1, (
                "MultiQueryAttention should have 1 head for keys and values"
            )
        elif head_type == AttentionHeadType.gqa:
            assert self.num_key_value_heads is not None, (
                "`num_key_value_heads` needs to be specified with GroupedQueryAttention"
            )
            assert self.n_head % self.num_key_value_heads == 0, (
                "GroupedQueryAttention needs n_head divisible by num_key_value_heads"
            )

    @classmethod
    def fused_loss_reads_untied_head(cls) -> bool:
        """Whether the family's model hands the chunked loss an untied head's table
        (`nemotron_h` does); the others read the tied embedding table only."""
        return False

    @classmethod
    def supported_position_embeddings(cls) -> frozenset[PositionEmbeddingType]:
        """Types this family actually builds; relative_bucketed is enc_dec-only."""
        return frozenset(
            {
                PositionEmbeddingType.learned_absolute,
                PositionEmbeddingType.alibi,
                PositionEmbeddingType.rope,
                PositionEmbeddingType.nope,
            }
        )

    # HF attribute_map aliases
    @property
    def hidden_size(self) -> int:
        return self.n_embd

    @property
    def max_position_embeddings(self) -> int:
        return self.n_positions

    @property
    def num_attention_heads(self) -> int:
        return self.n_head

    @property
    def num_hidden_layers(self) -> int:
        return self.n_layer

    @property
    def head_dim(self) -> int:
        if self.attention_head_dim is not None:
            return self.attention_head_dim
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    # ------------------------------------------------------------------ serialization
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CommonConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def save_pretrained(self, save_directory: str) -> None:
        os.makedirs(save_directory, exist_ok=True)
        with open(os.path.join(save_directory, "config.json"), "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)

    @classmethod
    def from_pretrained(cls, path: str) -> "CommonConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_dict(json.load(f))


@dataclass
class MoEConfig(CommonConfig):
    """Parity: reference `hf_models/models/moe_dolomite/config.py:40-44` adds MoE knobs."""

    model_type: str = "moe_dolomite"
    num_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.01


@dataclass
class GPTCrossLayerConfig(CommonConfig):
    """Parity: reference `hf_models/models/gpt_crosslayer/config.py`: cross-layer KV sharing
    pattern; `sharing_pattern[i]` = index of the layer whose KV cache layer i attends with
    (consecutive equal entries = one KV group). Attention head type is forced to gqa
    (reference config.py:48). `joint_residual_stream` adds the group input to every
    sub-layer residual."""

    model_type: str = "gpt_crosslayer"
    sharing_pattern: list[int] | None = None
    joint_residual_stream: bool = False

    def __post_init__(self) -> None:
        self.attention_head_type = "gqa"
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.n_head
        super().__post_init__()
        if self.sharing_pattern is None:
            self.sharing_pattern = list(range(self.n_layer))
        # reference validation (config.py:67-78): parents self-reference, non-decreasing,
        # in range
        assert len(self.sharing_pattern) == self.n_layer
        assert all(
            self.sharing_pattern[i] == i for i in set(self.sharing_pattern)
        ), "a filled sharing pattern doesn't have a parent layer"
        assert all(
            self.sharing_pattern[i] <= self.sharing_pattern[i + 1]
            for i in range(len(self.sharing_pattern) - 1)
        )
        assert all(0 <= p < self.n_layer for p in self.sharing_pattern)


@dataclass
class DenseMoEConfig(CommonConfig):
    """Parity: reference `hf_models/models/dense_moe/config.py` ("Dense Training, Sparse
    Inference"): wide MLP with per-expert soft routing; mixture-of-attention with one KV
    head per expert (moa.py:25-27 sets num_key_value_heads = num_experts)."""

    model_type: str = "dense_moe"
    num_experts: int = 8

    def __post_init__(self) -> None:
        assert self.n_head % self.num_experts == 0, (
            "number of attention heads must be divisible by the number of experts"
        )
        self.num_key_value_heads = self.num_experts
        self.attention_head_type = "mha" if self.num_experts == self.n_head else "gqa"
        super().__post_init__()


@dataclass
class EncDecDolomiteConfig(CommonConfig):
    """Encoder-decoder family backing `model_class: AutoModelForSeq2SeqLM` (reference
    `arguments.py:72-76` accepts HF encoder-decoders; the registry here is from-scratch, so
    seq2seq gets its own small family instead — same pre-norm blocks as GPTDolomite plus a
    bidirectional encoder and per-decoder-block cross-attention). `n_layer` counts decoder
    blocks; `n_encoder_layer` defaults to the same. `decoder_start_token_id` seeds the
    shifted-right decoder input (HF seq2seq convention)."""

    model_type: str = "enc_dec_dolomite"
    position_embedding_type: str = "rope"
    n_encoder_layer: int | None = None
    decoder_start_token_id: int | None = None
    # residual-branch count for depth-scaled init (modeling_utils.depth_scaled_init_std);
    # set internally per stack — encoder blocks have 2 branches, decoder blocks 3
    init_residual_branches: int | None = None
    # T5-style bucketed relative bias (position_embedding_type="relative_bucketed"):
    # bucket count and the distance beyond which buckets saturate (HF T5 config names)
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128

    @classmethod
    def supported_position_embeddings(cls) -> frozenset[PositionEmbeddingType]:
        # the stacks build neither wpe nor alibi slopes; relative_bucketed exists for
        # weight-exact T5/flan-t5 import (hf_interop/conversion.py)
        return frozenset(
            {PositionEmbeddingType.rope, PositionEmbeddingType.relative_bucketed}
        )

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.n_encoder_layer is None:
            self.n_encoder_layer = self.n_layer
        if self.decoder_start_token_id is None:
            self.decoder_start_token_id = next(
                (t for t in (self.bos_token_id, self.pad_token_id) if t is not None), 0
            )


@dataclass
class RNNDolomiteConfig(CommonConfig):
    """Parity: reference `hf_models/models/rnn_dolomite/config.py`: hybrid DeltaNet/attention;
    `attention_pattern` is a string over {'d' (DeltaNet), 'a' (attention)} of length n_layer."""

    model_type: str = "rnn_dolomite"
    attention_pattern: str | None = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.attention_pattern is None:
            self.attention_pattern = "d" * self.n_layer
        assert len(self.attention_pattern) == self.n_layer
        assert set(self.attention_pattern) <= {"a", "d"}


class ExpertShareConfig(CommonConfig):
    """What the configs of the families routed by sigmoid scores over a chip's share of the experts
    (`models/unrolled_stack.py`, `models/shared_expert_moe.py`) say alike. It has no field of its
    own — `experts_held`, `deployment` and `num_experts` stand in each family's class, in the order
    its `to_dict()` always had — and reads theirs:

    `experts_held` = (first, count): the chip's share of a layer's experts under expert
    parallelism. The router keeps `num_experts` outputs and `num_experts_per_tok` choices;
    the banks hold `count` experts and the layer adds what those give (`ops/moe.py`
    `experts_held_ragged`). None holds all. `deployment` is free text and numbers about the
    cut (published depth and vocabulary, chips sharing a layer) for the run's `model_layout`
    event; the program reads nothing from it."""

    # parameter leaves the optimizer must leave as they are (buffers of the public models)
    buffer_names = ("e_score_correction_bias",)

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.experts_held is not None:
            first, count = self.experts_held
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(f"experts_held {self.experts_held} lies outside 0..{self.num_experts}")
            self.experts_held = [int(first), int(count)]

    @classmethod
    def fused_loss_reads_untied_head(cls) -> bool:
        return True  # (`HeadTableForCausalLM`: the head is a table, tied or not)

    @classmethod
    def supported_position_embeddings(cls) -> frozenset[PositionEmbeddingType]:
        return frozenset({PositionEmbeddingType.rope})

    def held_experts(self) -> tuple[int, int]:
        return tuple(self.experts_held) if self.experts_held else (0, self.num_experts)

    def share_record(self) -> dict:
        """The end of every family's `layout_record`: the share held, of what was published."""
        first, count = self.held_experts()
        return dict(
            experts_held=count,
            first_expert_held=first,
            experts_published=self.num_experts,
            vocabulary_rows_held=self.vocab_size,
            **(self.deployment or {}),
        )


@dataclass
class NemotronHConfig(ExpertShareConfig):
    """The `nemotron_h` tower (Nemotron-H / Nemotron-Labs-TwoTower's first tower): every
    layer is ONE mixer behind a pre-norm and a residual, chosen by `hybrid_override_pattern`
    over ``M`` (Mamba-2), ``E`` (routed experts + a shared expert) and ``*`` (attention
    without positions). The repo's names carry the widths they always carried (`n_embd`,
    `n_head`, `num_key_value_heads`, `attention_head_dim`); the rest are the public
    `config.json`'s keys; `experts_held` and `deployment` are `ExpertShareConfig`'s."""

    model_type: str = "nemotron_h"
    attention_head_type: str = "gqa"
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    activation_function: str = "relu2"
    add_bias: bool = False
    tie_word_embeddings: bool = False
    hybrid_override_pattern: str | None = None
    # Mamba-2 mixer
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    num_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: list[int] | None = None
    deployment: dict | None = None

    # the literal of the family's public code in the renormalisation's denominator (a third family's differs)
    norm_topk_prob_epsilon = 1e-20

    def __post_init__(self) -> None:
        if self.hybrid_override_pattern is None:
            self.hybrid_override_pattern = "M" * self.n_layer
        super().__post_init__()
        if len(self.hybrid_override_pattern) != self.n_layer:
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} names "
                f"{len(self.hybrid_override_pattern)} layers, n_layer is {self.n_layer}"
            )
        unknown = set(self.hybrid_override_pattern) - set("ME*")
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern knows M (Mamba-2), E (experts) and * (attention), "
                f"not {sorted(unknown)}"
            )
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError("mamba_num_heads must be a multiple of mamba_n_groups")

    @classmethod
    def supported_position_embeddings(cls) -> frozenset[PositionEmbeddingType]:
        return frozenset({PositionEmbeddingType.nope})

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.ssm_state_size

    def forward_block_flops(self, b: int, s: int) -> float:
        """Forward matmul FLOPs of all the blocks for `b` rows of `s` tokens, under
        `train_utils.get_model_tflops`' conventions (2 per multiply-add; attention's score
        and value products at the full square; the routed experts by the even share of
        token-slots the experts held here get; the scan's and the convolution's own work
        left out). A family whose block is not attention + MLP has this method, and
        `get_model_tflops` takes its blocks from it."""
        h = self.n_embd
        heads, kv, d = self.n_head, self.num_key_value_heads, self.head_dim
        held = self.held_experts()[1]
        per_kind = {
            "M": 2 * b * s * (h * (self.mamba_inner + self.mamba_conv_dim + self.mamba_num_heads) + self.mamba_inner * h),
            "*": 2 * b * s * (h * (heads + 2 * kv) * d + heads * d * h),
            "E": 2 * b * s * (
                h * self.num_experts
                + 2 * h * self.moe_shared_expert_intermediate_size
                + self.num_experts_per_tok * held / self.num_experts * 2 * h * self.moe_intermediate_size
            ),
        }
        return float(sum(per_kind[kind] for kind in self.hybrid_override_pattern) + self.attention_product_flops(b, s))

    @property
    def attention_blocks(self) -> int:
        """The blocks that attend (`estimate_remat_activation_bytes` counts the attention
        kernel's kept residuals by it; a family without it attends in every block)."""
        return self.hybrid_override_pattern.count("*")

    def attention_product_flops(self, b: int, s: int) -> float:
        """The score and value products of `forward_block_flops`, all attending blocks at once:
        what a replay does not run again where the attention kernel's residuals are kept."""
        return float(self.attention_blocks * 4 * b * s * s * self.n_head * self.head_dim)

    def layout_record(self) -> dict:
        """What the run's one `model_layout` telemetry event says."""
        return dict(
            pattern=self.hybrid_override_pattern,
            **self.share_record(),
        )


@dataclass
class JoyAIFlashConfig(ExpertShareConfig):
    """`joyai_llm_flash` (JoyAI-LLM-Flash; its keys are the DeepSeek-V3 family's): every block
    is latent attention (`modeling_utils.LatentAttention`) and then a feed-forward sublayer —
    a dense SwiGLU MLP of `n_inner` in the first `first_k_dense_replace` blocks, routed
    experts plus a shared expert (`shared_expert_moe.SharedExpertMoE`) in the others. With
    `num_nextn_predict_layers` 1 a multi-token-prediction module — one more expert block over
    ``W [norm(embedding of the next token) ; norm(last block's output)]`` — sends a second
    pass through the head, and the loss is ``main + mtp_loss_coef x mtp``.

    The repo's names carry the widths they always carried (`n_embd`, `n_head`, `n_inner`);
    the rest are the public `config.json`'s keys. `experts_held` and `deployment` are
    `ExpertShareConfig`'s."""

    model_type: str = "joyai_llm_flash"
    attention_head_type: str = "mha"
    position_embedding_type: str = "rope"
    normalization_function: str = "rmsnorm"
    activation_function: str = "swiglu"
    layer_norm_epsilon: float = 1e-6
    add_bias: bool = False
    tie_word_embeddings: bool = False
    # latent attention
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = True
    # feed-forward sublayers
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: list[int] | None = None
    # multi-token prediction
    num_nextn_predict_layers: int = 0
    mtp_loss_coef: float = 0.3
    deployment: dict | None = None

    norm_topk_prob_epsilon = 1e-20  # (as `NemotronHConfig`'s)

    def __post_init__(self) -> None:
        if self.attention_head_dim is None:
            self.attention_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        super().__post_init__()
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers > 1: multi-token prediction is built at depth 1")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if not 0 <= self.first_k_dense_replace <= self.n_layer:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} lies outside 0..{self.n_layer}")

    @property
    def moe_shared_expert_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def expert_layers(self) -> int:
        """Layers of experts whose counters a step returns: the blocks after the dense ones,
        and the multi-token-prediction module's."""
        return self.n_layer - self.first_k_dense_replace + self.num_nextn_predict_layers

    def forward_block_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.forward_block_flops` for this family: the blocks and the
        multi-token-prediction module (its projection and its block; the head's second
        pass is the caller's)."""
        h, heads = self.n_embd, self.n_head
        qk, v = self.qk_nope_head_dim + self.qk_rope_head_dim, self.v_head_dim
        held = self.held_experts()[1]
        attention = 2 * b * s * (
            h * self.q_lora_rank + self.q_lora_rank * heads * qk
            + h * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * heads * (self.qk_nope_head_dim + v)
            + heads * v * h
        )
        dense = 2 * b * s * 3 * h * self.n_inner
        experts = 2 * b * s * (
            h * self.num_experts
            + 3 * h * self.moe_shared_expert_intermediate_size
            + self.num_experts_per_tok * held / self.num_experts * 3 * h * self.moe_intermediate_size
        )
        dense_blocks = self.first_k_dense_replace
        expert_blocks = self.expert_layers
        mtp = self.num_nextn_predict_layers * 2 * b * s * 2 * h * h
        blocks = (dense_blocks + expert_blocks) * attention + dense_blocks * dense + expert_blocks * experts + mtp
        return float(blocks + self.attention_product_flops(b, s))

    @property
    def attention_blocks(self) -> int:
        """`NemotronHConfig.attention_blocks`: every block and the multi-token-prediction module's."""
        return self.n_layer + self.num_nextn_predict_layers

    def attention_product_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.attention_product_flops` (scores over the wider head, values of `v_head_dim`)."""
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return float(self.attention_blocks * 2 * b * s * s * self.n_head * (qk + self.v_head_dim))

    def layout_record(self) -> dict:
        """What the run's one `model_layout` telemetry event says."""
        return dict(
            blocks_dense=self.first_k_dense_replace,
            blocks_experts=self.n_layer - self.first_k_dense_replace,
            blocks_mtp=self.num_nextn_predict_layers,
            **self.share_record(),
        )


@dataclass
class Lfm2MoeConfig(ExpertShareConfig):
    """`lfm2_moe` (LFM2-24B-A2B's family): every block is ONE operator behind a pre-norm and a
    residual — by `layer_types[i]` a gated short convolution (``conv``: `models/lfm2_moe.ShortConv`)
    or grouped-query attention with per-head QK norms and rope (``full_attention``) — and then
    a feed-forward sublayer behind another: a dense SwiGLU MLP of `n_inner` in the first
    `num_dense_layers` blocks, sigmoid-routed SwiGLU experts WITHOUT a shared expert
    (`shared_expert_moe.SharedExpertMoE`) in the others. The embedding is the head's table.

    The repo's names carry the widths they always carried (`n_embd`, `n_head`,
    `num_key_value_heads`, `n_inner`); the rest are the public `config.json`'s keys
    (`norm_topk_prob_epsilon` is the public modeling code's literal). `experts_held` and
    `deployment` are `ExpertShareConfig`'s."""

    model_type: str = "lfm2_moe"
    attention_head_type: str = "gqa"
    position_embedding_type: str = "rope"
    normalization_function: str = "rmsnorm"
    activation_function: str = "swiglu"
    layer_norm_epsilon: float = 1e-5
    rope_theta: float = 1000000.0
    add_bias: bool = False
    tie_word_embeddings: bool = True
    qk_norm: bool = True
    # the operator of every block, and its short convolution
    layer_types: list[str] | None = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    # feed-forward sublayers
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_topk_prob_epsilon: float = 1e-6
    experts_held: list[int] | None = None
    deployment: dict | None = None

    # the family has no shared expert (`SharedExpertMoE` then builds none)
    moe_shared_expert_intermediate_size = 0

    def __post_init__(self) -> None:
        if self.layer_types is None:
            self.layer_types = ["full_attention"] * self.n_layer
        super().__post_init__()
        if len(self.layer_types) != self.n_layer:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, n_layer is {self.n_layer}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types knows conv and full_attention, not {sorted(unknown)}")
        if self.conv_bias:
            raise ValueError("conv_bias true: the short convolution is built without biases (the published models')")
        if not self.use_expert_bias:
            raise ValueError("use_expert_bias false: the router is built with its bias (the published models')")
        if not 0 <= self.num_dense_layers <= self.n_layer:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} lies outside 0..{self.n_layer}")

    @property
    def expert_layers(self) -> int:
        """Layers of experts whose counters a step returns: the blocks after the dense ones."""
        return self.n_layer - self.num_dense_layers

    def forward_block_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.forward_block_flops` for this family (the convolution's taps and
        gates, no matmuls, left out as the tower's are)."""
        h, heads, kv, d = self.n_embd, self.n_head, self.num_key_value_heads, self.head_dim
        held = self.held_experts()[1]
        per_operator = {
            "conv": 2 * b * s * (3 * h * h + h * h),
            "full_attention": 2 * b * s * (h * (heads + 2 * kv) * d + heads * d * h),
        }
        dense = 2 * b * s * 3 * h * self.n_inner
        experts = 2 * b * s * (
            h * self.num_experts
            + self.num_experts_per_tok * held / self.num_experts * 3 * h * self.moe_intermediate_size
        )
        operators = sum(per_operator[kind] for kind in self.layer_types) + self.attention_product_flops(b, s)
        return float(operators + self.num_dense_layers * dense + self.expert_layers * experts)

    @property
    def attention_blocks(self) -> int:
        """`NemotronHConfig.attention_blocks`."""
        return self.layer_types.count("full_attention")

    def attention_product_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.attention_product_flops`."""
        return float(self.attention_blocks * 4 * b * s * s * self.n_head * self.head_dim)

    def layout_record(self) -> dict:
        """What the run's one `model_layout` telemetry event says."""
        return dict(
            layer_types=",".join(self.layer_types),
            blocks_conv=self.layer_types.count("conv"),
            blocks_attention=self.layer_types.count("full_attention"),
            blocks_dense=self.num_dense_layers,
            blocks_experts=self.expert_layers,
            **self.share_record(),
        )


@dataclass
class AfmoeConfig(ExpertShareConfig):
    """`afmoe` (Trinity-Mini's family): every block is attention of one of two kinds by
    `layer_types[i]` — ``sliding_attention`` (rope by halves; a query sees its own key and the
    `sliding_window` - 1 before it, inside its document) or ``full_attention`` (NO positions;
    every earlier key of its document) — and then one of two feed-forwards by depth: a dense
    SwiGLU MLP of `n_inner` in the first `num_dense_layers` blocks, sigmoid-routed SwiGLU
    experts with a shared expert (`shared_expert_moe.SharedExpertMoE`) in the others. Every
    query and key head is RMS-normed before the rotation (`qk_norm`), the heads' output is
    multiplied by ``sigmoid(W_g h)`` before the out-projection (`attention_output_gate`), and
    each sub-layer stands between a norm of its input and a norm of its output
    (`modeling_utils.sandwich_normed_block`: Ouro's four norms). `mup_enabled` multiplies the
    embedding's output by ``sqrt(n_embd)`` (the repo's `m_emb`) and does nothing else. The head
    is untied.

    The repo's names carry the widths they always carried (`n_embd`, `n_head`,
    `num_key_value_heads`, `attention_head_dim`, `n_inner`); the rest are the public
    `config.json`'s keys (`route_norm_epsilon` is the public modeling code's literal).
    `experts_held` and `deployment` are `ExpertShareConfig`'s."""

    model_type: str = "afmoe"
    attention_head_type: str = "gqa"
    position_embedding_type: str = "rope"
    normalization_function: str = "rmsnorm"
    activation_function: str = "swiglu"
    layer_norm_epsilon: float = 1e-5
    rope_theta: float = 10000
    add_bias: bool = False
    tie_word_embeddings: bool = False
    qk_norm: bool = True
    attention_output_gate: bool = True
    # the kind of every block's attention
    layer_types: list[str] | None = None
    sliding_window: int = 2048
    # feed-forward sublayers
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    route_norm_epsilon: float = 1e-20
    mup_enabled: bool = True
    experts_held: list[int] | None = None
    deployment: dict | None = None

    def __post_init__(self) -> None:
        if self.layer_types is None:
            self.layer_types = ["full_attention"] * self.n_layer
        if self.mup_enabled and self.m_emb is None:
            self.m_emb = float(self.n_embd) ** 0.5
        super().__post_init__()
        if len(self.layer_types) != self.n_layer:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, n_layer is {self.n_layer}")
        unknown = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types knows sliding_attention and full_attention, not {sorted(unknown)}")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}: a query sees at least its own key")
        if self.score_func != "sigmoid":
            raise ValueError(f"score_func {self.score_func!r}: the router is built with sigmoid scores (the published models')")
        if not self.qk_norm or not self.attention_output_gate:
            raise ValueError("qk_norm / attention_output_gate false: the family's attention has both (the published models')")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings true: the family's head is a table of its own (the published models')")
        if not 0 <= self.num_dense_layers <= self.n_layer:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} lies outside 0..{self.n_layer}")

    # what `SharedExpertMoE` reads, under the names the other expert families gave them
    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def norm_topk_prob_epsilon(self) -> float:
        return self.route_norm_epsilon

    @property
    def moe_shared_expert_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    @property
    def expert_layers(self) -> int:
        """Layers of experts whose counters a step returns: the blocks after the dense ones."""
        return self.n_layer - self.num_dense_layers

    def layer_window(self, index: int) -> int | None:
        """The window of block `index`'s attention (None: a full layer)."""
        return self.sliding_window if self.layer_types[index] == "sliding_attention" else None

    def forward_block_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.forward_block_flops` for this family: attention's projections with
        the gate's, the score and value products over ``s`` keys a token in a full layer and
        ``min(s, sliding_window)`` in a window layer (the function's convention counts the full
        square; a window layer's square is a band)."""
        h, heads, kv, d = self.n_embd, self.n_head, self.num_key_value_heads, self.head_dim
        held = self.held_experts()[1]
        projections = 2 * b * s * (h * (heads + 2 * kv) * d + 2 * heads * d * h)  # q, k, v; the gate and the out-projection
        attention = len(self.layer_types) * projections + self.attention_product_flops(b, s)
        dense = 2 * b * s * 3 * h * self.n_inner
        experts = 2 * b * s * (
            h * self.num_experts
            + 3 * h * self.moe_shared_expert_intermediate_size
            + self.num_experts_per_tok * held / self.num_experts * 3 * h * self.moe_intermediate_size
        )
        return float(attention + self.num_dense_layers * dense + self.expert_layers * experts)

    def attention_product_flops(self, b: int, s: int) -> float:
        """`NemotronHConfig.attention_product_flops`: a full layer's square, a window layer's band."""
        keys = {"full_attention": s, "sliding_attention": min(s, self.sliding_window)}
        return float(sum(4 * b * s * keys[kind] * self.n_head * self.head_dim for kind in self.layer_types))

    def layout_record(self) -> dict:
        """What the run's one `model_layout` telemetry event says."""
        return dict(
            layer_types=",".join(self.layer_types),
            blocks_window=self.layer_types.count("sliding_attention"),
            blocks_full=self.layer_types.count("full_attention"),
            sliding_window=self.sliding_window,
            blocks_dense=self.num_dense_layers,
            blocks_experts=self.expert_layers,
            **self.share_record(),
        )


@dataclass
class OuroConfig(CommonConfig):
    """`ouro` (Ouro-2.6B's family, a looped language model): ONE stack of `n_layer` blocks
    applied `total_ut_steps` times over the same weights, the final norm closing every pass,
    and an exit gate (a linear layer to one number, with a bias) read after every pass
    (`models/ouro.py` has the equations). A block norms each sub-layer's input AND its output
    (four RMSNorms). The head is untied.

    The repo's names carry the widths they always carried; `total_ut_steps` and
    `early_exit_threshold` are the public `config.json`'s keys. `exit_entropy_coef` is the
    loss's weight on the entropy of the gate's distribution over passes (the family's report:
    0.05). `early_exit_threshold` is generation's (the cumulated exit probability at which a
    token stops; at 1 every token runs every pass) and the training path does not read it."""

    model_type: str = "ouro"
    attention_head_type: str = "mha"
    position_embedding_type: str = "rope"
    normalization_function: str = "rmsnorm"
    activation_function: str = "swiglu"
    layer_norm_epsilon: float = 1e-6
    rope_theta: float = 1000000.0
    add_bias: bool = False
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    exit_entropy_coef: float = 0.05

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps}: the stack runs at least once")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings true: the family's head is a table of its own (the published models')")

    @classmethod
    def fused_loss_reads_untied_head(cls) -> bool:
        return True

    @classmethod
    def supported_position_embeddings(cls) -> frozenset[PositionEmbeddingType]:
        return frozenset({PositionEmbeddingType.rope})

    # what `train_utils.get_model_tflops`, `estimate_remat_activation_bytes` and the
    # ``remat_plan`` / ``loop_plan`` events count a step's work by: a family without these two
    # applies each block and reads the head once
    @property
    def block_applications(self) -> int:
        return self.total_ut_steps * self.n_layer

    @property
    def head_readings(self) -> int:
        return self.total_ut_steps
