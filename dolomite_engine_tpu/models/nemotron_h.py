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
from contextlib import contextmanager
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..enums import AttentionImplementation
from ..ops.causal_conv import causal_conv1d
from ..ops.mamba2 import gated_group_rmsnorm, mamba2_scan, watch_scan_lowerings
from ..parallel.sharding import logical_constraint
from .config import NemotronHConfig
from .modeling_utils import Attention, ParameterizedLinear, depth_scaled_init_std, get_norm
from .shared_expert_moe import SharedExpertMoE
from .unrolled_stack import UnrolledStack, UnrolledStackForCausalLM


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
    """``x + mixer(norm(x))``; `mixer` is one letter of the pattern. (Called as every block of
    `unrolled_stack.UnrolledStack` is: `rope_cos_sin` is None here, the tower takes no positions.)"""

    config: NemotronHConfig
    mixer: str
    attention_implementation: AttentionImplementation = AttentionImplementation.sdpa
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, hidden_states: jax.Array, attention_mask=None, segment_ids=None, rope_cos_sin=None, deterministic: bool = True
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


class NemotronHModel(UnrolledStack):
    family = "nemotron_h"
    why_no_scan = "the layers of a pattern differ and a scan over whole periods is not built"
    replicated_under = {"tp": "the Mamba-2 heads", "ep": "the experts held"}
    no_cache = "Mamba state and convolution taps are not in the serving engine's cache"
    roadmap_item = "ROADMAP M2"
    block_cls = NemotronHBlock

    @nn.nowrap
    def block_arguments(self, i: int) -> dict:
        return dict(mixer=self.config.hybrid_override_pattern[i])

    @nn.nowrap
    def rope_width(self) -> None:
        return None  # no positions: the Mamba layers before an attention layer carry the order

    @nn.nowrap
    @contextmanager
    def watch_blocks(self):
        """... and the ``mamba2_scan_plan`` event of the scans the `M` layers lowered."""
        with watch_scan_lowerings() as scans, super().watch_blocks() as run:
            yield run
        if scans:
            from ..utils.telemetry import get_telemetry

            get_telemetry().event_once("mamba2_scan_plan", **scan_plan(scans))


class NemotronHForCausalLM(UnrolledStackForCausalLM):
    """The tower under the repo's untied head table and chunked loss."""

    base_model_cls: type = NemotronHModel
