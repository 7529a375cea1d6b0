"""`nemotron_h`: the hybrid tower of Nemotron-H / Nemotron-Labs-TwoTower (first tower only).

Every layer is ``x + mixer(RMSNorm(x))`` with ONE mixer, chosen by the config's pattern:

  - ``M``  `Mamba2Mixer`: in-projection to ``[z | xBC | dt]``, a causal depthwise
    convolution with silu over ``xBC``, the selective scan in chunks (`ops/mamba2.py`), a
    gated grouped RMSNorm, out-projection;
  - ``E``  `shared_expert_moe.SharedExpertMoE`: a router that scores ALL experts (sigmoid, chosen with a
    correction bias, weighed without it, renormalised, scaled), the chip's share of the
    routed experts (`ops/moe.experts_held_ragged`) and a shared expert every token passes;
  - ``*``  the repo's `Attention` without position embedding.

After the last layer a norm and an untied head; the loss is the repo's chunked one, which
reads the head's ``[V, H]`` table. No second tower, no adaLN conditioning, no in-block
bidirectional attention, no diffusion objective: the public ``config.json`` has no key for
them (PERF.md section 7).

Packed rows (``segment_ids``): attention, the convolution's taps and the Mamba state all
reset at document boundaries. Training path only: a KV/state cache raises. `scan_layers`
raises — the layers differ, and a scan over whole periods is not built. tp > 1 and ep > 1
raise rather than replicate the Mamba heads or the experts silently.

Scopes inside the jitted step (docs/OBSERVABILITY.md "Phases of the train step"):
``mamba_mixer`` (``mamba_in_proj``, ``mamba_conv``, ``mamba2_scan``, ``mamba_gated_norm``,
``mamba_out_proj``), ``moe`` (``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_shared_expert``, ``moe_combine``), ``attention``. The backward pass carries them
under ``transpose(...)``: nearly everything is differentiated by JAX, and the backward rules
of the kernels (splash, the scan's where `ops/mamba2.mamba2_scan` takes it) inherit the
scopes of their calls.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..enums import AttentionImplementation
from ..ops.attention import watch_kernel_residuals
from ..ops.causal_conv import causal_conv1d
from ..ops.mamba2 import gated_group_rmsnorm, mamba2_scan, watch_scan_lowerings
from ..parallel.sharding import logical_constraint
from .config import NemotronHConfig
from .gpt_dolomite import HeadTableForCausalLM, resolve_remat_policy, say_remat_plan
from .modeling_utils import (
    Attention,
    ParameterizedEmbedding,
    ParameterizedLinear,
    depth_scaled_init_std,
    get_norm,
)
from .shared_expert_moe import (
    STEP_COUNTERS,
    SharedExpertMoE,
    refuse_generation_cache,
    refuse_what_is_not_built,
    say_dispatch_plan,
    stack_step_counters,
)


def _inverse_softplus(x: jax.Array) -> jax.Array:
    return x + jnp.log(-jnp.expm1(-x))


def scan_plan(scans: list[dict]) -> dict:
    """What the telemetry event ``mamba2_scan_plan`` says, once a traced model: which lowering
    of the chunked scan each ``M`` layer took (`ops/mamba2.scan_lowering`'s record of each call,
    in the order of the layers), and of the `jnp` ones why."""
    kernel = [plan for plan in scans if plan["form"] == "kernel"]
    return {
        "layers": len(scans),
        "kernel_layers": tuple(i for i, plan in enumerate(scans) if plan["form"] == "kernel"),
        "jnp_layers": tuple(i for i, plan in enumerate(scans) if plan["form"] == "jnp"),
        "jnp_reasons": tuple(sorted({plan["reason"] for plan in scans if plan["form"] == "jnp"})),
        "chunk": max(plan["chunk"] for plan in scans),
        "kernel_launches_per_layer_and_pass": max((plan["launches_per_pass"] for plan in kernel), default=0),
        "kept_bytes_per_layer": max((plan["kept_bytes"] for plan in kernel), default=0),
        "kept_state_bytes_per_layer": max((plan["kept_state_bytes"] for plan in kernel), default=0),
    }


class Mamba2Mixer(nn.Module):
    config: NemotronHConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hidden_states: jax.Array, segment_ids: jax.Array | None = None) -> jax.Array:
        config = self.config
        heads, width = config.mamba_num_heads, config.mamba_head_dim
        groups, state = config.mamba_n_groups, config.ssm_state_size
        inner, conv_dim = config.mamba_inner, config.mamba_conv_dim
        batch, seq = hidden_states.shape[:2]

        with jax.named_scope("mamba_in_proj"):
            projected = ParameterizedLinear(
                features=inner + conv_dim + heads,
                use_bias=False,
                std=config.initializer_range,
                kernel_axes=("embed", "mamba_inner"),
                dtype=self.dtype,
                name="in_proj",
            )(hidden_states)
            gate, xbc, dt = jnp.split(projected, [inner, inner + conv_dim], axis=-1)

        def conv_init(key, shape, dtype=jnp.float32):  # torch's Conv1d default
            bound = 1.0 / math.sqrt(config.conv_kernel)
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        with jax.named_scope("mamba_conv"):
            conv_weight = self.param(
                "conv_weight",
                nn.with_logical_partitioning(conv_init, (None, None)),
                (conv_dim, config.conv_kernel),
                jnp.float32,
            )
            conv_bias = None
            if config.use_conv_bias:
                conv_bias = self.param(
                    "conv_bias",
                    nn.with_logical_partitioning(conv_init, (None,)),
                    (conv_dim,),
                    jnp.float32,
                ).astype(self.dtype)
            xbc = jax.nn.silu(
                causal_conv1d(xbc, conv_weight.astype(self.dtype), conv_bias, segment_ids)
            )
            x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)

        def init_dt_bias(key, shape, dtype=jnp.float32):
            low, high = math.log(config.time_step_min), math.log(config.time_step_max)
            dt0 = jnp.exp(jax.random.uniform(key, shape, dtype) * (high - low) + low)
            return _inverse_softplus(jnp.maximum(dt0, config.time_step_floor))

        def init_a_log(key, shape, dtype=jnp.float32):
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))

        per_head = lambda init: nn.with_logical_partitioning(init, (None,))  # noqa: E731
        dt_bias = self.param("dt_bias", per_head(init_dt_bias), (heads,), jnp.float32)
        a_log = self.param("A_log", per_head(init_a_log), (heads,), jnp.float32)
        d_skip = self.param("D", per_head(nn.initializers.ones_init()), (heads,), jnp.float32)

        with jax.named_scope("mamba2_scan"):
            # one Pallas kernel a pass or the `jnp` form, by what the trace observes
            y = mamba2_scan(
                x.reshape(batch, seq, heads, width),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                b.reshape(batch, seq, groups, state),
                c.reshape(batch, seq, groups, state),
                d_skip,
                segment_ids,
                config.chunk_size,
            ).reshape(batch, seq, inner)

        with jax.named_scope("mamba_gated_norm"):
            norm_weight = self.param(
                "norm_weight",
                nn.with_logical_partitioning(nn.initializers.ones_init(), (None,)),
                (inner,),
                jnp.float32,
            )
            y = gated_group_rmsnorm(y, gate, norm_weight, groups, config.layer_norm_epsilon)

        with jax.named_scope("mamba_out_proj"):
            return ParameterizedLinear(
                features=config.n_embd,
                use_bias=False,
                std=depth_scaled_init_std(config),
                kernel_axes=("mamba_inner", "embed"),
                dtype=self.dtype,
                name="out_proj",
            )(y)


class NemotronHBlock(nn.Module):
    """``x + mixer(norm(x))``; `mixer` is one letter of the pattern."""

    config: NemotronHConfig
    mixer: str
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, hidden_states: jax.Array, attention_mask=None, segment_ids=None, deterministic: bool = True
    ) -> tuple[jax.Array, dict | None]:
        config = self.config
        h = get_norm(config, self.dtype, "ln_1")(hidden_states)
        counters = None
        if self.mixer == "M":
            with jax.named_scope("mamba_mixer"):
                out = Mamba2Mixer(config=config, dtype=self.dtype, name="mixer")(h, segment_ids)
        elif self.mixer == "E":
            with jax.named_scope("moe"):
                out, counters = SharedExpertMoE(config=config, dtype=self.dtype, name="moe")(h)
        else:
            with jax.named_scope("attention"):
                out, _ = Attention(
                    config=config,
                    attention_implementation=self.attention_implementation,
                    dtype=self.dtype,
                    name="attn",
                )(h, attention_mask=attention_mask, segment_ids=segment_ids, deterministic=deterministic)
        hidden_states = hidden_states + out.astype(hidden_states.dtype)
        hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
        return hidden_states, counters


class NemotronHModel(nn.Module):
    config: NemotronHConfig
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32
    checkpoint_every: int = 0
    checkpoint_policy: str | None = None
    scan_layers: bool = False

    def setup(self) -> None:
        config = self.config
        refuse_what_is_not_built(
            "nemotron_h",
            self.scan_layers,
            "the layers of a pattern differ and a scan over whole periods is not built",
            {"tp": "the Mamba-2 heads", "ep": "the experts held"},
        )
        self.wte = ParameterizedEmbedding(
            num_embeddings=config.vocab_size,
            features=config.n_embd,
            std=config.initializer_range,
            dtype=self.dtype,
        )
        remat_policy = resolve_remat_policy(self.checkpoint_policy)
        self.rematerialized = tuple(
            self.checkpoint_every > 0 and i % self.checkpoint_every == 0
            for i in range(len(config.hybrid_override_pattern))
        )
        blocks = []
        for i, mixer in enumerate(config.hybrid_override_pattern):
            cls = NemotronHBlock
            if self.rematerialized[i]:
                # flax counts the module instance as argument 0; deterministic is arg 4.
                # prevent_cse stays on: the layers are unrolled, and XLA would merge a
                # layer's replay with its forward pass and keep every layer's activations
                cls = nn.remat(cls, static_argnums=(4,), policy=remat_policy)
            blocks.append(
                cls(
                    config=config,
                    mixer=mixer,
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
            refuse_generation_cache(
                "nemotron_h", "Mamba state and convolution taps are not in the serving engine's cache: ROADMAP M2"
            )
        with jax.named_scope("embed"):
            hidden_states = self.wte(input_ids) if inputs_embeds is None else inputs_embeds
            hidden_states = logical_constraint(hidden_states, ("act_batch", "act_seq", "act_embed"))
        if segment_ids is None and attention_mask is not None:
            # padded rows: the pad tokens are a document of their own
            segment_ids = attention_mask.astype(jnp.int32)
        extras = []
        kernel_residual_bytes = []
        with (
            jax.named_scope("blocks"),
            watch_kernel_residuals() as seen,
            watch_scan_lowerings() as scans,
            say_dispatch_plan(),
        ):
            for block in self.h:
                calls_before = len(seen)
                hidden_states, counters = block(hidden_states, attention_mask, segment_ids, deterministic)
                kernel_residual_bytes.append(sum(seen[calls_before:]))
                if counters is not None:
                    extras.append(counters)
        say_remat_plan(self, kernel_residual_bytes)
        if scans:
            from ..utils.telemetry import get_telemetry

            get_telemetry().event_once("mamba2_scan_plan", **scan_plan(scans))
        with jax.named_scope("final_norm"):
            hidden_states = self.ln_f(hidden_states)
        return hidden_states, None, extras


class NemotronHForCausalLM(HeadTableForCausalLM):
    """The tower under the repo's untied head table and chunked loss."""

    base_model_cls: type = NemotronHModel
    family_counter_names = STEP_COUNTERS

    def step_counters(self, extras: list) -> dict | None:
        """``{name: int32[layers of experts, ...]}`` from the blocks' counters."""
        return stack_step_counters(extras)

    def init_kv_caches(self, batch_size: int, max_length: int, dtype=None) -> list:
        refuse_generation_cache("nemotron_h", "ROADMAP M2")
