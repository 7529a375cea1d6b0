"""The expert layer of the families routed by sigmoid scores with a correction bias
(`nemotron_h`, `joyai_llm_flash`, `lfm2_moe`, `afmoe`): a router that scores ALL experts, the chip's
share of the routed experts (`ops/moe.experts_held_ragged`) and — where the family has one — a
shared expert every token passes. Also what those families refuse, said once
(`refuse_what_is_not_built`, `refuse_generation_cache`)."""

from __future__ import annotations

from collections import Counter
from contextlib import contextmanager
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.activations import get_activation_function, is_glu
from ..ops.moe import experts_held_ragged, route_sigmoid_bias, watch_dispatch_plans
from .config import CommonConfig
from .modeling_utils import ParameterizedLinear, _normal_init, depth_scaled_init_std
from .moe_dolomite import ParameterizedExperts

# what the step returns beside the loss, one number a layer of experts
STEP_COUNTERS = ("routed_slots", "absent_slots", "fullest_expert_rows", "held_expert_rows")


def refuse_what_is_not_built(family: str, scan_layers: bool, why_no_scan: str, replicated_under: dict) -> None:
    """What the training-path-only expert families raise when a model is set up: `scan_layers`
    (`why_no_scan`: how the family's layers differ and which scan is not built) and a mesh axis
    of `replicated_under` (``{"tp": what would be replicated, "ep": ...}``) above 1 — rather
    than replicate heads or experts silently."""
    if scan_layers:
        raise ValueError(f"scan_layers with {family}: {why_no_scan}; run it unrolled (scan_layers: false)")
    from ..parallel.mesh import MeshManager

    if MeshManager.is_initialized():
        for axis, what in replicated_under.items():
            if MeshManager.axis_size(axis) > 1:
                raise ValueError(
                    f"{family} on a mesh with {axis} > 1: {what} would be replicated, "
                    f"not sharded; {axis} for this family is not built"
                )


def refuse_generation_cache(family: str, why: str | None = None) -> None:
    """The same families have no generation cache (`why`: what is missing, and the ROADMAP
    item that says so)."""
    detail = f" ({why})" if why else ""
    raise NotImplementedError(f"{family} has no generation cache{detail}; the training path only")


def stack_step_counters(extras: list) -> dict | None:
    """``{name: int32[layers of experts, ...]}`` from the blocks' counters (None: no layer of
    experts ran)."""
    if not extras:
        return None
    return {name: jnp.stack([layer[name] for layer in extras]) for name in STEP_COUNTERS}


@contextmanager
def say_dispatch_plan():
    """Round a model's blocks: write the ``moe_dispatch_plan`` event, once a traced model, for
    the layers of experts traced inside — how many `layers`, and what
    `ops/moe.experts_held_ragged` planned for them from their shapes (the buffers' `capacity`,
    the `block_rows` a loop step of its row movements takes, `blocks_per_capacity`, the
    `form`; the `activation_block_rows` and `activation_form` of the walk between the grouped
    products; `group_sizes`: read off the sorted keys). With `routed_slots` of the
    ``step_counters`` event a layer ran ``ceil(routed_slots / block_rows)`` of
    `blocks_per_capacity` blocks that step, and its activation ``ceil(routed_slots /
    activation_block_rows)`` a pass."""
    from ..utils.telemetry import get_telemetry

    with watch_dispatch_plans() as plans:
        yield
    for plan, layers in Counter(tuple(plan.items()) for plan in plans).items():
        get_telemetry().event_once("moe_dispatch_plan", layers=layers, **dict(plan))


class SharedExpertMoE(nn.Module):
    """Routed experts (the share held here) plus a shared expert, where the family has one.
    Returns the layer's output and its counters (`STEP_COUNTERS`: int32 scalars, and the rows
    of each held expert).

    Every width comes from the family's config (`NemotronHConfig`, `JoyAIFlashConfig`,
    `Lfm2MoeConfig`, `AfmoeConfig`): `num_experts`, `num_experts_per_tok`, `moe_intermediate_size`,
    `moe_shared_expert_intermediate_size`, `routed_scaling_factor`, `norm_topk_prob`,
    `held_experts()` and `activation_function` — with a gated one (``swiglu``) the up
    banks and the shared expert's up projection are twice as wide, ``[up | gate]`` as
    everywhere in the repo, and the activation folds them. A shared width of 0 is no shared
    expert: no parameters, no ``moe_shared_expert`` scope, and the routed experts' weighted
    scatter-add is the layer's output. `norm_topk_prob_epsilon` stands in the renormalisation's
    denominator."""

    config: CommonConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hidden_states: jax.Array) -> tuple[jax.Array, dict]:
        config = self.config
        hidden = config.n_embd
        act = get_activation_function(config.activation_function)
        up_factor = 2 if is_glu(config.activation_function) else 1
        first, held = config.held_experts()
        batch, seq, _ = hidden_states.shape
        x = hidden_states.reshape(-1, hidden)

        with jax.named_scope("moe_router"):
            gate = self.param(
                "gate",
                nn.with_logical_partitioning(_normal_init(config.initializer_range), (None, None)),
                (hidden, config.num_experts),
                jnp.float32,
            )
            # the router's scores are float32 whatever the model's dtype (the public model's)
            logits = jnp.dot(
                x.astype(jnp.float32), gate, precision=jax.lax.Precision.HIGHEST
            )
            # the family's routing rule (`ops/moe.route_sigmoid_bias`); the bias is a buffer
            correction_bias = self.param(
                "e_score_correction_bias",
                nn.with_logical_partitioning(nn.initializers.zeros_init(), (None,)),
                (config.num_experts,),
                jnp.float32,
            )
            weights, selected = route_sigmoid_bias(
                logits,
                config.num_experts_per_tok,
                correction_bias,
                config.routed_scaling_factor,
                config.norm_topk_prob,
                config.norm_topk_prob_epsilon,
            )

        c_fc, _ = ParameterizedExperts(
            num_experts=held,
            features=up_factor * config.moe_intermediate_size,
            use_bias=False,
            std=config.initializer_range,
            kernel_axes=("experts", "embed", "expert_mlp"),
            dtype=self.dtype,
            name="c_fc",
        )(hidden)
        c_proj, _ = ParameterizedExperts(
            num_experts=held,
            features=hidden,
            use_bias=False,
            std=depth_scaled_init_std(config),
            kernel_axes=("experts", "expert_mlp", "embed"),
            dtype=self.dtype,
            name="c_proj",
        )(config.moe_intermediate_size)

        # `moe_dispatch` (the sort, the gather), `moe_experts` (the grouped products and the
        # activation between them) and `moe_combine` (the weighted scatter-add) are opened
        # inside: one function, so that the overflow path is the same code at more rows
        routed, counters = experts_held_ragged(
            x.astype(self.dtype),
            weights,
            selected,
            c_fc.astype(self.dtype),
            c_proj.astype(self.dtype),
            act,
            config.num_experts,
            first,
        )

        if not config.moe_shared_expert_intermediate_size:
            return routed.astype(self.dtype).reshape(batch, seq, hidden), counters

        with jax.named_scope("moe_shared_expert"):
            h = ParameterizedLinear(
                features=up_factor * config.moe_shared_expert_intermediate_size,
                use_bias=False,
                std=config.initializer_range,
                kernel_axes=("embed", "mlp"),
                dtype=self.dtype,
                name="shared_c_fc",
            )(x)
            shared = ParameterizedLinear(
                features=hidden,
                use_bias=False,
                std=depth_scaled_init_std(config),
                kernel_axes=("mlp", "embed"),
                dtype=self.dtype,
                name="shared_c_proj",
            )(act(h))

        with jax.named_scope("moe_combine"):
            out = (routed.astype(self.dtype) + shared).reshape(batch, seq, hidden)
        return out, counters
