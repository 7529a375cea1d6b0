"""`lfm2_moe`: LFM2-24B-A2B's family, training path.

Every block is ``a = x + Op(RMSNorm(x))``, ``y = a + F(RMSNorm(a))``. ``Op`` is ONE operator,
chosen by the config's `layer_types`:

  - ``conv``  `ShortConv`, the gated short convolution: ``[B | C | u] = W_in h``,
    ``z = B * u``, a causal depthwise convolution of `conv_L_cache` taps over ``z``
    (`ops/causal_conv.causal_conv1d`: no bias, a tap before the row's or the document's start
    reads zero), ``W_out (C * conv(z))``. No activation anywhere in it: both gates are plain
    products;
  - ``full_attention``  the repo's `Attention` (grouped-query, rope by halves) with an RMSNorm
    of every query and key head before the rotation (`qk_norm`: the one rope+QKV seam,
    `ops/rope.split_qkv_apply_rope`).

``F`` is a dense SwiGLU MLP in the first `num_dense_layers` blocks and the routed experts in
the others (`shared_expert_moe.SharedExpertMoE` without a shared expert: sigmoid scores chosen
with a bias that is a buffer, the chip's share of the experts). After the last block a norm and
the head, which is the embedding's table (`tie_word_embeddings`).

Packed rows (``segment_ids``): attention, positions and the convolution's taps reset at
document boundaries. Training path only, and what that refuses is said where the expert
families share it (`shared_expert_moe.refuse_what_is_not_built`): a generation cache (the
convolution's taps are not a state of the serving engine's cache: ROADMAP M2), `scan_layers`,
tp > 1 and ep > 1.

Scopes inside the jitted step (docs/OBSERVABILITY.md "Phases of the train step"):
``short_conv`` (``short_conv_in_proj``, ``short_conv_gates_taps`` — the two gates and the taps,
the memory-bound part —, ``short_conv_out_proj``), ``attention`` (``qk_norm`` inside it, the
splash kernels' own), ``dense_mlp``, ``moe`` (``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``; no ``moe_shared_expert``).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..enums import AttentionImplementation
from ..ops.causal_conv import causal_conv1d
from ..parallel.sharding import logical_constraint
from .config import Lfm2MoeConfig
from .modeling_utils import (
    ATTENTION_OUT_CHECKPOINT_NAME,
    MLP,
    Attention,
    ParameterizedLinear,
    depth_scaled_init_std,
    get_norm,
)
from .shared_expert_moe import SharedExpertMoE
from .unrolled_stack import UnrolledStack, UnrolledStackForCausalLM


class ShortConv(nn.Module):
    """The gated short convolution: a gate before the taps and a gate after, no activation."""

    config: Lfm2MoeConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hidden_states: jax.Array, segment_ids: jax.Array | None = None) -> jax.Array:
        config = self.config
        hidden, taps = config.n_embd, config.conv_L_cache

        with jax.named_scope("short_conv_in_proj"):
            projected = ParameterizedLinear(
                features=3 * hidden,
                use_bias=False,
                std=config.initializer_range,
                kernel_axes=("embed", "mlp"),
                dtype=self.dtype,
                name="in_proj",
            )(hidden_states)

        def conv_init(key, shape, dtype=jnp.float32):  # torch's Conv1d default
            bound = 1.0 / math.sqrt(taps)
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        with jax.named_scope("short_conv_gates_taps"):
            conv_weight = self.param(
                "conv_weight", nn.with_logical_partitioning(conv_init, (None, None)), (hidden, taps), jnp.float32
            )
            gate_in, gate_out, x = jnp.split(projected, 3, axis=-1)
            y = gate_out * causal_conv1d(gate_in * x, conv_weight.astype(self.dtype), None, segment_ids)

        with jax.named_scope("short_conv_out_proj"):
            return ParameterizedLinear(
                features=hidden,
                use_bias=False,
                std=depth_scaled_init_std(config),
                kernel_axes=("mlp", "embed"),
                dtype=self.dtype,
                name="out_proj",
            )(y)


class Lfm2MoeBlock(nn.Module):
    """One operator (`operator`: a name of `layer_types`), then the dense MLP (`dense`) or the
    experts."""

    config: Lfm2MoeConfig
    operator: str
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
        if self.operator == "conv":
            with jax.named_scope("short_conv"):
                out = ShortConv(config=config, dtype=self.dtype, name="conv")(h, segment_ids)
        else:
            with jax.named_scope("attention"):
                out, _ = Attention(
                    config=config, attention_implementation=self.attention_implementation, dtype=self.dtype, name="attn"
                )(h, attention_mask=attention_mask, segment_ids=segment_ids, rope_cos_sin=rope_cos_sin, deterministic=deterministic)
            out = checkpoint_name(out, ATTENTION_OUT_CHECKPOINT_NAME)
        h, hidden_states = get_norm(config, self.dtype, "ln_2")(out, residual=residual)
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


class Lfm2MoeModel(UnrolledStack):
    family = "lfm2_moe"
    why_no_scan = "the blocks differ by operator and by feed-forward and a scan over whole periods is not built"
    replicated_under = {"tp": "the convolution's channels and the attention heads", "ep": "the experts held"}
    no_cache = "the short convolution's taps are not a state of the serving engine's cache"
    roadmap_item = "ROADMAP M2"
    block_cls = Lfm2MoeBlock

    @nn.nowrap
    def block_arguments(self, i: int) -> dict:
        return dict(operator=self.config.layer_types[i], dense=i < self.config.num_dense_layers)


class Lfm2MoeForCausalLM(UnrolledStackForCausalLM):
    """The blocks under the embedding's table as the head (tied) and the repo's chunked loss."""

    base_model_cls: type = Lfm2MoeModel
