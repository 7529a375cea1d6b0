"""`afmoe`: Trinity-Mini's family, training path.

``h_0 = sqrt(n_embd) E[token]`` (`mup_enabled`: the repo's `m_emb`). Every block is the
four-norm block Ouro's is (`modeling_utils.sandwich_normed_block`):

    a = h + N2(Attn_l(N1(h))),   h' = a + N4(F_l(N3(a)))

``Attn_l`` is the repo's `Attention` (grouped-query; every query and key head RMS-normed before
any rotation: `qk_norm`; the heads' output times ``sigmoid(W_g x)`` before the out-projection:
`attention_output_gate`) of one of two kinds, by the config's `layer_types[l]`:

  - ``sliding_attention``  rope by halves over the whole head, and a query sees its own key
    and the `sliding_window` - 1 before it, inside its document (`Attention.window`);
  - ``full_attention``  NO positions — the layer is called with ``rope_cos_sin=None`` through
    the one rope+QKV seam (`ops/rope.split_qkv_apply_rope`; the QK norm is still applied) — and
    a query sees every earlier key of its document.

``F_l`` is a dense SwiGLU MLP in the first `num_dense_layers` blocks and the routed experts
with a shared expert in the others (`shared_expert_moe.SharedExpertMoE`: sigmoid scores chosen
with a bias that is a buffer, the chosen scores over their sum + 1e-20, times `route_scale`; the
chip's share of the experts). After the last block a norm and an untied head.

Packed rows (``segment_ids``): attention, the window and positions reset at document
boundaries. On a TPU both kinds run jax's splash kernel on this repo's block tables
(`ops/attention.document_block_pairs`): a window layer's tables drop the key blocks its window
does not reach and are only as wide as that reach, so its launches walk ``reach + 1`` key slots
a query block where a full layer's walk a row's; the two kinds of one step visit different
block counts, and the step returns them apart (`ops/attention.SPLASH_COUNTERS_BY_KIND`).

Training path only, and what that refuses is said where the expert families share it
(`shared_expert_moe.refuse_what_is_not_built`, `refuse_generation_cache`): a generation cache
(window layers would free pages full layers keep: per-layer page budgets and a window in the
paged decode and chunked prefill walks are not built, ROADMAP M6), `scan_layers`, tp > 1 and
ep > 1.

Scopes inside the jitted step (docs/OBSERVABILITY.md "Phases of the train step"):
``attention`` and inside it ``attention_window`` or ``attention_full`` by the layer's kind
(``qk_norm``, ``attention_gate`` and the splash kernels' own inside those), ``dense_mlp``,
``moe`` (the five sub-scopes of `SharedExpertMoE`), ``block_norms`` (a block's four norms).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..enums import AttentionImplementation
from ..ops.attention import SPLASH_COUNTERS_BY_KIND, splash_block_counters_by_kind, watch_kernel_residuals
from ..ops.rope import RoPEParams, get_cos_sin
from ..parallel.sharding import logical_constraint
from .config import AfmoeConfig
from .gpt_dolomite import HeadTableForCausalLM, resolve_remat_policy, say_remat_plan
from .modeling_utils import MLP, Attention, ParameterizedEmbedding, get_norm, sandwich_normed_block
from .shared_expert_moe import (
    STEP_COUNTERS,
    SharedExpertMoE,
    refuse_generation_cache,
    refuse_what_is_not_built,
    say_dispatch_plan,
    stack_step_counters,
)

NO_CACHE = (
    "window layers would free pages full layers keep: per-layer page budgets and a window in the "
    "paged decode and chunked prefill walks are not built: ROADMAP M6"
)


class AfmoeBlock(nn.Module):
    """Attention under `window` (None: a full layer, which also takes no positions), then the
    dense MLP (`dense`) or the experts, each between a norm of its input and one of its output."""

    config: AfmoeConfig
    window: int | None
    dense: bool
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, hidden_states: jax.Array, attention_mask=None, segment_ids=None, rope_cos_sin=None, deterministic: bool = True
    ) -> tuple[jax.Array, dict | None]:
        config = self.config
        attn = Attention(
            config=config, attention_implementation=self.attention_implementation, dtype=self.dtype, window=self.window, name="attn"
        )

        def attention(h: jax.Array) -> jax.Array:
            with jax.named_scope("attention"), jax.named_scope("attention_full" if self.window is None else "attention_window"):
                return attn(
                    h,
                    attention_mask=attention_mask,
                    segment_ids=segment_ids,
                    rope_cos_sin=None if self.window is None else rope_cos_sin,  # rope on the window layers only
                    deterministic=deterministic,
                )[0]

        def feed_forward(h: jax.Array):
            if self.dense:
                with jax.named_scope("dense_mlp"):
                    return MLP(config=config, dtype=self.dtype, name="mlp")(h, deterministic=deterministic)
            with jax.named_scope("moe"):
                return SharedExpertMoE(config=config, dtype=self.dtype, name="moe")(h)

        return sandwich_normed_block(config, self.dtype, hidden_states, attention, feed_forward)


class AfmoeModel(nn.Module):
    config: AfmoeConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32
    checkpoint_every: int = 0
    checkpoint_policy: str | None = None
    scan_layers: bool = False

    def setup(self) -> None:
        config = self.config
        refuse_what_is_not_built(
            "afmoe",
            self.scan_layers,
            "the blocks differ by attention's kind and by feed-forward and a scan over whole periods is not built",
            {"tp": "the attention heads and their gate", "ep": "the experts held"},
        )
        self.wte = ParameterizedEmbedding(
            num_embeddings=config.vocab_size, features=config.n_embd, std=config.initializer_range, dtype=self.dtype
        )
        self.rope_params = RoPEParams.from_config(config.head_dim, config.rope_theta, config.rope_scaling, config.n_positions)
        remat_policy = resolve_remat_policy(self.checkpoint_policy)
        self.rematerialized = tuple(
            self.checkpoint_every > 0 and i % self.checkpoint_every == 0 for i in range(config.n_layer)
        )
        blocks = []
        for i in range(config.n_layer):
            cls = AfmoeBlock
            if self.rematerialized[i]:
                # flax counts the module instance as argument 0; deterministic is arg 5.
                # prevent_cse stays on, as for the other unrolled families
                cls = nn.remat(cls, static_argnums=(5,), policy=remat_policy)
            blocks.append(
                cls(
                    config=config,
                    window=config.layer_window(i),
                    dense=i < config.num_dense_layers,
                    attention_implementation=self.attention_implementation,
                    dtype=self.dtype,
                )
            )
        self.h = blocks
        self.ln_f = get_norm(config, self.dtype)

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
    ) -> tuple[jax.Array, None, list]:
        if kv_caches is not None:
            refuse_generation_cache("afmoe", NO_CACHE)
        config = self.config
        batch, seq = input_ids.shape
        with jax.named_scope("embed"):
            hidden_states = self.wte(input_ids) if inputs_embeds is None else inputs_embeds
            if config.m_emb is not None:
                hidden_states = hidden_states * config.m_emb
            hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
            if position_ids is None:
                position_ids = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
            rope_cos_sin = get_cos_sin(self.rope_params, position_ids, dtype=self.dtype)
        if segment_ids is None and attention_mask is not None:
            segment_ids = attention_mask.astype(jnp.int32)  # the pad tokens are a document of their own
        extras, kernel_residual_bytes = [], []
        with jax.named_scope("blocks"), watch_kernel_residuals() as seen, say_dispatch_plan():
            for block in self.h:
                calls_before = len(seen)
                hidden_states, counters = block(hidden_states, attention_mask, segment_ids, rope_cos_sin, deterministic)
                kernel_residual_bytes.append(sum(seen[calls_before:]))
                if counters is not None:
                    extras.append(counters)
        say_remat_plan(self, kernel_residual_bytes)
        with jax.named_scope("final_norm"):
            hidden_states = self.ln_f(hidden_states)
        return hidden_states, None, extras


class AfmoeForCausalLM(HeadTableForCausalLM):
    """The blocks under the repo's untied head table and chunked loss."""

    base_model_cls: type = AfmoeModel
    family_counter_names = STEP_COUNTERS
    splash_counter_names = SPLASH_COUNTERS_BY_KIND

    def step_counters(self, extras: list) -> dict | None:
        """``{name: int32[layers of experts, ...]}`` from the blocks' counters."""
        return stack_step_counters(extras)

    def count_splash_blocks(self, batch: int, seq: int, segment_ids: jax.Array | None) -> dict:
        """`SPLASH_COUNTERS_BY_KIND` of these rows: the layers do NOT share the ids' tables — a
        window layer's drop the key blocks its window does not reach — so each kind's tables
        are counted and summed over the layers of that kind."""
        config = self.config
        window_layers = config.layer_types.count("sliding_attention")
        return splash_block_counters_by_kind(
            batch, seq, segment_ids, config.sliding_window, window_layers, config.n_layer - window_layers
        )

    def init_kv_caches(self, batch_size: int, max_length: int, dtype=None) -> list:
        refuse_generation_cache("afmoe", "ROADMAP M6")
