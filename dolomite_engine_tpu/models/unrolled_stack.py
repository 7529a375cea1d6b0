"""The stack of the families whose blocks differ from layer to layer (`nemotron_h`,
`joyai_llm_flash`, `lfm2_moe`, `afmoe`), written once: an embedding, positions where the family
rotates, `n_layer` blocks unrolled — each under `jax.checkpoint` where the remat arguments say
so —, a final norm; and over it the head table and the chunked loss (`UnrolledStackForCausalLM`).

A family is a block and a description. The block is a module called as
``block(hidden_states, attention_mask, segment_ids, rope_cos_sin, deterministic)`` that returns
its output and its layer of experts' counters (None: it holds none). The description is what a
subclass of `UnrolledStack` says of itself:

  - `family`, `why_no_scan`, `replicated_under`, `no_cache`, `roadmap_item`: its name and the
    texts of what it refuses (`shared_expert_moe.refuse_what_is_not_built`,
    `refuse_generation_cache`);
  - `block_cls` and `block_arguments(i)`: block ``i``'s class and what it takes beside the
    config, the attention implementation and the dtype;
  - `rope_width()`: the head width that rotates (None: the family takes no positions);
  - `watch_blocks()`: what is watched while the blocks trace, where a family watches more than
    the attention kernels' residuals and the expert layers' plans.

The embedding's output is scaled by the config's `m_emb` where it has one. A family with more to
run than the blocks (`joyai_llm_flash`'s multi-token prediction) extends `setup` and `__call__`
from these pieces. The parameter paths (``wte``, ``h_<i>``, ``ln_f``) and the scopes inside the
jitted step (``embed``, ``blocks``, ``final_norm``: docs/OBSERVABILITY.md "Phases of the train
step") are the stack's; the weights' makers (`benchmark/weights_*.py`) and the trace readers go
by them.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..enums import AttentionImplementation
from ..ops.attention import watch_kernel_residuals
from ..ops.rope import RoPEParams, get_cos_sin
from ..parallel.sharding import logical_constraint
from .config import CommonConfig
from .gpt_dolomite import HeadTableForCausalLM, resolve_remat_policy, say_remat_plan
from .modeling_utils import ParameterizedEmbedding, get_norm
from .shared_expert_moe import (
    STEP_COUNTERS,
    refuse_generation_cache,
    refuse_what_is_not_built,
    say_dispatch_plan,
    stack_step_counters,
)


class BlockRun:
    """What a stack's blocks leave while they trace: the expert layers' counters (`extras`) and,
    a block, the bytes a batch row of the residuals its attention kernels tagged
    (`kernel_residual_bytes`; `seen` is `ops.attention.watch_kernel_residuals`' list)."""

    def __init__(self, seen: list) -> None:
        self.seen, self.extras, self.kernel_residual_bytes = seen, [], []

    def __call__(self, block: nn.Module, *args) -> jax.Array:
        calls_before = len(self.seen)
        out, counters = block(*args)
        self.kernel_residual_bytes.append(sum(self.seen[calls_before:]))
        if counters is not None:
            self.extras.append(counters)
        return out


class UnrolledStack(nn.Module):
    config: CommonConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32
    checkpoint_every: int = 0
    checkpoint_policy: str | None = None
    scan_layers: bool = False

    # the family's description (the module's docstring)
    family = why_no_scan = no_cache = roadmap_item = block_cls = None
    replicated_under = {}

    @nn.nowrap
    def block_arguments(self, i: int) -> dict:
        raise NotImplementedError

    @nn.nowrap
    def rope_width(self) -> int | None:
        return self.config.head_dim

    @nn.nowrap
    def block_count(self) -> int:
        """The blocks the remat arguments count over (a family may run one beside the stack's)."""
        return self.config.n_layer

    @nn.nowrap
    def block_class(self, i: int) -> type:
        """`block_cls`, under `jax.checkpoint` where block `i` is rematerialized."""
        if not self.rematerialized[i]:
            return self.block_cls
        # flax counts the module instance as argument 0; deterministic is arg 5.
        # prevent_cse stays on: the layers are unrolled, and XLA would merge a
        # layer's replay with its forward pass and keep every layer's activations
        return nn.remat(self.block_cls, static_argnums=(5,), policy=self.remat_policy)

    def setup(self) -> None:
        config = self.config
        refuse_what_is_not_built(self.family, self.scan_layers, self.why_no_scan, self.replicated_under)
        self.wte = ParameterizedEmbedding(
            num_embeddings=config.vocab_size, features=config.n_embd, std=config.initializer_range, dtype=self.dtype
        )
        width = self.rope_width()
        self.rope_params = RoPEParams.from_config(width, config.rope_theta, config.rope_scaling, config.n_positions) if width else None
        self.remat_policy = resolve_remat_policy(self.checkpoint_policy)
        self.rematerialized = tuple(
            self.checkpoint_every > 0 and i % self.checkpoint_every == 0 for i in range(self.block_count())
        )
        self.h = [
            self.block_class(i)(
                config=config, attention_implementation=self.attention_implementation, dtype=self.dtype, **self.block_arguments(i)
            )
            for i in range(config.n_layer)
        ]
        self.ln_f = get_norm(config, self.dtype)

    @nn.nowrap
    def embed(self, input_ids, position_ids, attention_mask, segment_ids, kv_caches, inputs_embeds) -> tuple[jax.Array, tuple]:
        """The blocks' input, and what every block takes after it but `deterministic`: the
        mask, the rows' segment ids, the rotation's cos and sin (None: no positions)."""
        if kv_caches is not None:
            refuse_generation_cache(self.family, f"{self.no_cache}: {self.roadmap_item}")
        config = self.config
        batch, seq = input_ids.shape
        with jax.named_scope("embed"):
            hidden_states = self.wte(input_ids) if inputs_embeds is None else inputs_embeds
            if config.m_emb is not None:
                hidden_states = hidden_states * config.m_emb
            hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
            rope_cos_sin = None
            if self.rope_params is not None:
                if position_ids is None:
                    position_ids = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
                rope_cos_sin = get_cos_sin(self.rope_params, position_ids, dtype=self.dtype)
        if segment_ids is None and attention_mask is not None:
            segment_ids = attention_mask.astype(jnp.int32)  # the pad tokens are a document of their own
        return hidden_states, (attention_mask, segment_ids, rope_cos_sin)

    @nn.nowrap
    @contextmanager
    def watch_blocks(self):
        """Round the blocks, inside the ``blocks`` scope: yields the `BlockRun` to call them
        through, and writes the ``moe_dispatch_plan`` event of the layers of experts it saw."""
        with jax.named_scope("blocks"), watch_kernel_residuals() as seen, say_dispatch_plan():
            yield BlockRun(seen)

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
        hidden_states, block_inputs = self.embed(input_ids, position_ids, attention_mask, segment_ids, kv_caches, inputs_embeds)
        with self.watch_blocks() as run:
            for block in self.h:
                hidden_states = run(block, hidden_states, *block_inputs, deterministic)
        say_remat_plan(self, run.kernel_residual_bytes)
        with jax.named_scope("final_norm"):
            hidden_states = self.ln_f(hidden_states)
        return hidden_states, None, run.extras


class UnrolledStackForCausalLM(HeadTableForCausalLM):
    """A family's stack (`base_model_cls`: an `UnrolledStack`) under the head table — untied, or
    the embedding's where the config ties — and the repo's chunked loss."""

    family_counter_names = STEP_COUNTERS

    def step_counters(self, extras: list) -> dict | None:
        """``{name: int32[layers of experts, ...]}`` from the blocks' counters."""
        return stack_step_counters(extras)

    def init_kv_caches(self, batch_size: int, max_length: int, dtype=None) -> list:
        refuse_generation_cache(self.base_model_cls.family, self.base_model_cls.roadmap_item)
