"""`joyai_llm_flash`: JoyAI-LLM-Flash (the DeepSeek-V3 family's layers), training path.

Every block is ``a = x + MLA(RMSNorm(x))``, ``y = a + F(RMSNorm(a))``: latent attention
(`modeling_utils.LatentAttention`) and then a dense SwiGLU MLP in the first
`first_k_dense_replace` blocks, the routed experts with a shared expert
(`shared_expert_moe.SharedExpertMoE`: sigmoid scores chosen with a correction bias, the chip's
share of the experts) in the others. After the last block a norm and an untied head.

Multi-token prediction (`num_nextn_predict_layers` 1), when a loss is asked for: with ``h_i``
the last block's output (before the final norm) and ``t`` the tokens,
``h'_i = W [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``, one more expert block over ``h'`` under
the masks and positions of the main blocks, a norm, and the main model's head — the same
``[V, H]`` table, read by the chunked loss a second time — against ``t_{i+2}``, over the
positions whose ``t_{i+1}`` and ``t_{i+2}`` lie in ``t_i``'s document. The loss is
``main + mtp_loss_coef x mtp``; both come back beside the experts' counters
(`STEP_COUNTERS`), with the count of MTP targets.

Training path only: a KV cache raises (the latent page and the absorbed decode form are not
built: ROADMAP M5), `scan_layers` raises (the first block differs from the others), tp > 1
and ep > 1 raise rather than replicate the heads or the experts silently.

Scopes inside the jitted step (docs/OBSERVABILITY.md "Phases of the train step"):
``latent_attention`` (``mla_q_down``, ``mla_q_up``, ``mla_kv_down``, ``mla_kv_up``,
``mla_rope``, the splash kernels' own, ``mla_out_proj``), ``dense_mlp``, ``moe`` (the five
sub-scopes of `SharedExpertMoE`), and ``mtp`` (``mtp_combine``, the block's scopes,
``mtp_final_norm``, ``mtp_head_loss``). The module's block runs as ``blocks/mtp/...`` and its
pass through the head as ``head_loss/mtp/mtp_head_loss/...``, so a reader of the step's
phases counts them with the blocks and with the head.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..enums import AttentionImplementation
from ..ops.loss import IGNORE_INDEX, causal_lm_loss, derive_causal_labels
from ..parallel.sharding import logical_constraint
from .config import JoyAIFlashConfig
from .gpt_dolomite import CausalLMOutput, say_remat_plan
from .modeling_utils import ATTENTION_OUT_CHECKPOINT_NAME, MLP, LatentAttention, ParameterizedLinear, get_norm
from .shared_expert_moe import STEP_COUNTERS, SharedExpertMoE, stack_step_counters
from .unrolled_stack import UnrolledStack, UnrolledStackForCausalLM

# beside the experts' counters: both parts of the loss and the positions the second had
LOSS_PARTS = ("main_loss", "mtp_loss", "mtp_targets")


class JoyAIFlashBlock(nn.Module):
    """Latent attention, then the dense MLP (`dense`) or the experts."""

    config: JoyAIFlashConfig
    dense: bool
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, hidden_states: jax.Array, attention_mask=None, segment_ids=None, rope_cos_sin=None, deterministic: bool = True
    ) -> tuple[jax.Array, dict | None]:
        config = self.config
        residual = hidden_states
        h = get_norm(config, self.dtype, "ln_1")(hidden_states)
        with jax.named_scope("latent_attention"):
            attn_out = LatentAttention(
                config=config, attention_implementation=self.attention_implementation, dtype=self.dtype, name="attn"
            )(h, attention_mask, segment_ids, rope_cos_sin, deterministic)
        attn_out = checkpoint_name(attn_out, ATTENTION_OUT_CHECKPOINT_NAME)
        h, hidden_states = get_norm(config, self.dtype, "ln_2")(attn_out, residual=residual)
        counters = None
        if self.dense:
            with jax.named_scope("dense_mlp"):
                out = MLP(config=config, dtype=self.dtype, name="mlp")(h, deterministic=deterministic)
        else:
            with jax.named_scope("moe"):
                out, counters = SharedExpertMoE(config=config, dtype=self.dtype, name="moe")(h)
        hidden_states = hidden_states + out.astype(hidden_states.dtype)
        hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
        return hidden_states, counters


class MultiTokenPrediction(nn.Module):
    """``block(W [norm(next token's embedding) ; norm(hidden)])`` and a norm: what the head
    reads for the token after the next."""

    config: JoyAIFlashConfig
    block_cls: type
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, hidden_states, next_embeds, attention_mask=None, segment_ids=None, rope_cos_sin=None, deterministic: bool = True
    ) -> tuple[jax.Array, dict]:
        config = self.config
        with jax.named_scope("mtp_combine"):
            both = jnp.concatenate(
                [get_norm(config, self.dtype, "enorm")(next_embeds), get_norm(config, self.dtype, "hnorm")(hidden_states)],
                axis=-1,
            )
            hidden_states = ParameterizedLinear(
                features=config.n_embd,
                use_bias=False,
                std=config.initializer_range,
                kernel_axes=("embed", None),
                dtype=self.dtype,
                name="eh_proj",
            )(both)
        hidden_states, counters = self.block_cls(
            config=config, dense=False, attention_implementation=self.attention_implementation, dtype=self.dtype, name="block"
        )(hidden_states, attention_mask, segment_ids, rope_cos_sin, deterministic)
        with jax.named_scope("mtp_final_norm"):
            return get_norm(config, self.dtype, "norm")(hidden_states), counters


class JoyAIFlashModel(UnrolledStack):
    family = "joyai_llm_flash"
    why_no_scan = "the dense first block differs from the expert blocks and a scan over the like ones is not built"
    replicated_under = {"tp": "the latent attention's heads", "ep": "the experts held"}
    no_cache = "a latent page and the absorbed decode form are not built"
    roadmap_item = "ROADMAP M5"
    block_cls = JoyAIFlashBlock

    @nn.nowrap
    def block_arguments(self, i: int) -> dict:
        return dict(dense=i < self.config.first_k_dense_replace)

    @nn.nowrap
    def rope_width(self) -> int:
        return self.config.qk_rope_head_dim

    @nn.nowrap
    def block_count(self) -> int:
        return self.config.n_layer + self.config.num_nextn_predict_layers

    def setup(self) -> None:
        super().setup()
        if self.config.num_nextn_predict_layers:
            self.mtp = MultiTokenPrediction(
                config=self.config,
                block_cls=self.block_class(self.config.n_layer),
                attention_implementation=self.attention_implementation,
                dtype=self.dtype,
            )

    def __call__(
        self,
        input_ids: jax.Array,
        position_ids: jax.Array | None = None,
        attention_mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
        kv_caches: list | None = None,
        cache_index: jax.Array | None = None,
        deterministic: bool = True,
        inputs_embeds: jax.Array | None = None,
        predict_second: bool = False,
    ) -> tuple[jax.Array, None, list, jax.Array | None]:
        """The stack's pass and, after the blocks, the multi-token-prediction module's:
        (normed hidden states, None, the expert layers' counters, and — `predict_second`,
        with such a module — its normed hidden states, else None)."""
        hidden_states, block_inputs = self.embed(input_ids, position_ids, attention_mask, segment_ids, kv_caches, inputs_embeds)
        second = None
        with self.watch_blocks() as run:
            for block in self.h:
                hidden_states = run(block, hidden_states, *block_inputs, deterministic)
            # (at initialization too, whatever the call asks for: the module's parameters exist)
            if self.config.num_nextn_predict_layers and (predict_second or self.is_initializing()):
                with jax.named_scope("mtp"):
                    # the last position's next token is not in `input_ids`; it has no target either
                    next_embeds = self.wte(jnp.roll(input_ids, -1, axis=1))
                    second = run(self.mtp, hidden_states, next_embeds, *block_inputs, deterministic)
        if len(run.kernel_residual_bytes) == len(self.rematerialized):
            say_remat_plan(self, run.kernel_residual_bytes)
        with jax.named_scope("final_norm"):
            hidden_states = self.ln_f(hidden_states)
        return hidden_states, None, run.extras, second


def second_token_labels(labels: jax.Array, segment_ids: jax.Array | None) -> jax.Array:
    """Targets of the multi-token-prediction pass from the main pass's: position i's is
    ``t_{i+2}``, the main label of position i + 1 (which already is no label where ``t_{i+2}``
    leaves ``t_{i+1}``'s document), and no label where ``t_{i+1}`` leaves ``t_i``'s document
    or the row ends."""
    shifted = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], IGNORE_INDEX)], axis=1)
    if segment_ids is None:
        return shifted
    next_segment = jnp.concatenate([segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1)
    return jnp.where(next_segment == segment_ids, shifted, IGNORE_INDEX)


class JoyAIFlashForCausalLM(UnrolledStackForCausalLM):
    """The blocks under the repo's untied head table and chunked loss, the loss read twice
    where the model predicts a second token."""

    base_model_cls: type = JoyAIFlashModel
    family_counter_names = STEP_COUNTERS + LOSS_PARTS

    @nn.nowrap  # (as `fused_head_loss`: the scopes are the caller's)
    def head_loss(self, hidden_states: jax.Array, labels: jax.Array) -> jax.Array:
        """Mean cross-entropy (+ z-loss) of the head over `hidden_states` against `labels`."""
        if self.config.fused_lm_head_loss:
            return self.fused_head_loss(hidden_states, labels)
        return causal_lm_loss(
            self.compute_logits(hidden_states),
            labels,  # (unread: the labels are given)
            upcast=self.config.upcast_logits_for_loss,
            labels=labels,
            z_loss_coef=self.config.z_loss_coef,
        )

    def __call__(
        self,
        input_ids: jax.Array,
        position_ids: jax.Array | None = None,
        attention_mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
        labels: jax.Array | None = None,
        kv_caches: list | None = None,
        cache_index: jax.Array | None = None,
        deterministic: bool = True,
        compute_loss: bool = False,
        inputs_embeds: jax.Array | None = None,
    ) -> CausalLMOutput:
        want_loss = compute_loss or labels is not None
        hidden_states, _, extras, second = self.transformer(
            input_ids,
            position_ids=position_ids,
            attention_mask=attention_mask,
            segment_ids=segment_ids,
            kv_caches=kv_caches,
            cache_index=cache_index,
            deterministic=deterministic,
            inputs_embeds=inputs_embeds,
            predict_second=want_loss,
        )
        if not want_loss:
            with jax.named_scope("head_loss"):
                return CausalLMOutput(logits=self.compute_logits(hidden_states))

        with jax.named_scope("head_loss"):
            if labels is None:
                labels = derive_causal_labels(input_ids, attention_mask, segment_ids)
            main_loss = self.head_loss(hidden_states, labels)
        mtp_loss = jnp.zeros((), jnp.float32)
        mtp_targets = jnp.zeros((), jnp.int32)
        loss = main_loss
        if second is not None:
            with jax.named_scope("head_loss"), jax.named_scope("mtp"), jax.named_scope("mtp_head_loss"):
                second_labels = second_token_labels(labels, segment_ids)
                mtp_loss = self.head_loss(second, second_labels)
                mtp_targets = jnp.sum(second_labels != IGNORE_INDEX).astype(jnp.int32)
            loss = main_loss + self.config.mtp_loss_coef * mtp_loss
        counters = stack_step_counters(extras) or {}
        counters.update(main_loss=main_loss, mtp_loss=mtp_loss, mtp_targets=mtp_targets)
        counters.update(self.splash_step_counters(hidden_states, segment_ids, attention_mask))
        return CausalLMOutput(loss=loss, counters=counters)
