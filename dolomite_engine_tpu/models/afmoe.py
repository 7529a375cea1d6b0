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
from ..ops.attention import SPLASH_COUNTERS_BY_KIND, splash_block_counters_by_kind
from .config import AfmoeConfig
from .modeling_utils import MLP, Attention, sandwich_normed_block
from .shared_expert_moe import SharedExpertMoE
from .unrolled_stack import UnrolledStack, UnrolledStackForCausalLM


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


class AfmoeModel(UnrolledStack):
    family = "afmoe"
    why_no_scan = "the blocks differ by attention's kind and by feed-forward and a scan over whole periods is not built"
    replicated_under = {"tp": "the attention heads and their gate", "ep": "the experts held"}
    no_cache = (
        "window layers would free pages full layers keep: per-layer page budgets and a window in the "
        "paged decode and chunked prefill walks are not built"
    )
    roadmap_item = "ROADMAP M6"
    block_cls = AfmoeBlock

    @nn.nowrap
    def block_arguments(self, i: int) -> dict:
        return dict(window=self.config.layer_window(i), dense=i < self.config.num_dense_layers)


class AfmoeForCausalLM(UnrolledStackForCausalLM):
    """The blocks under the repo's untied head table and chunked loss."""

    base_model_cls: type = AfmoeModel
    splash_counter_names = SPLASH_COUNTERS_BY_KIND

    def count_splash_blocks(self, batch: int, seq: int, segment_ids: jax.Array | None) -> dict:
        """`SPLASH_COUNTERS_BY_KIND` of these rows: the layers do NOT share the ids' tables — a
        window layer's drop the key blocks its window does not reach — so each kind's tables
        are counted and summed over the layers of that kind."""
        config = self.config
        window_layers = config.layer_types.count("sliding_attention")
        return splash_block_counters_by_kind(
            batch, seq, segment_ids, config.sliding_window, window_layers, config.n_layer - window_layers
        )
