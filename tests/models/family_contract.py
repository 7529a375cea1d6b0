"""What every family trained against a plain reference (`benchmark/reference/`) is held to, written
once: the table of the families (`FAMILIES`: a tiny config, the weights' maker, the reference, the
packed rows, the step's counters, what it refuses, its pinned lowered step) and the tests over it
(`contract_tests`). A family's test file takes its tests with

    globals().update(contract_tests("<model_type>"))

and holds beside them only what is the family's own. Adding a family is one row here.

The tests, each generated where the table's row has what it reads:

  - registered under its model type, and the seeded weights fit the program's parameter tree;
  - logits of a packed row follow the reference, and a document alone gives its part of the row;
  - the loss (its parts) and every leaf's gradient follow the reference;
  - three steps of the trainer's own step follow the reference's, the routers' buffers held;
  - the shares of an expert layer add up to the reference's uncut layer;
  - what the family refuses, from the one place the expert families share;
  - the lowered train step is, letter for letter, what it was (`PINNED_STEPS`).

A family's model, seeded weights, logits, loss and gradients — the program's and the reference's —
are computed once a process (`functools.cache`, by family and case), whichever tests read them, and
each as ONE jitted program: run op by op the same comparison takes four times as long.

Tolerances: everything here is float32 under ``highest`` matmul precision on both sides, so values
agree to rounding in another order of summation: 2e-4 on logits of size ~1, 2e-5 relative on a
loss and its parts, 2e-3 on a leaf's gradient norm and on its elements against the leaf's largest
(a near-tie of a router's may fall either way for a token-slot, which moves a routed bank's row),
1e-4 on an expert layer's output."""

import dataclasses
import functools
import hashlib
import json
from types import ModuleType
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmark import compare, weights_afmoe, weights_joyai_flash, weights_lfm2_moe, weights_nemotron_h, weights_ouro
from benchmark.reference import afmoe, joyai_flash, lfm2_moe, nemotron_h_tower, ouro
from dolomite_engine_tpu.distributed import TrainState
from dolomite_engine_tpu.enums import LRDecaySchedule, Mode
from dolomite_engine_tpu.model_wrapper.pretraining import ModelWrapperForPretraining
from dolomite_engine_tpu.models import config_from_dict, get_config_class, get_model_class
from dolomite_engine_tpu.models.joyai_flash import LOSS_PARTS
from dolomite_engine_tpu.models.ouro import pass_step_counter_names
from dolomite_engine_tpu.models.shared_expert_moe import STEP_COUNTERS, SharedExpertMoE
from dolomite_engine_tpu.optimization import get_optimizer, get_scheduler
from dolomite_engine_tpu.train_utils import make_train_step

COMMON = dict(
    vocab_size=256, n_positions=64, n_embd=32, n_head=4,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0, bos_token_id=0, eos_token_id=0, pad_token_id=0,
    fused_lm_head_loss=True, loss_chunk_size=16, z_loss_coef=1e-4, initializer_range=0.1,
)
EXPERTS = dict(num_experts=32, num_experts_per_tok=4, experts_held=[8, 8], moe_intermediate_size=12)
OPTIMIZER = dict(lr=1e-3, weight_decay=0.1, betas=[0.9, 0.95], eps=1e-10, gradient_clipping=1.0)
AFMOE_KINDS = ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"]
BIAS = "e_score_correction_bias"


@dataclasses.dataclass(frozen=True)
class Case:
    """Packed rows (each: its documents' lengths and the seed of its tokens) under the family's
    config with `overrides`."""

    rows: tuple
    overrides: dict = dataclasses.field(default_factory=dict)


def one_row(*docs: int, **overrides) -> Case:
    return Case(((docs, 1),), overrides)


TWO_AND_THREE = {"two_documents": one_row(23, 41), "three_documents": one_row(10, 37, 17)}


@dataclasses.dataclass(frozen=True)
class Family:
    cfg: dict
    W: ModuleType  # benchmark.weights_<family>
    reference: ModuleType  # benchmark.reference.<family>
    classes: tuple  # the names of the config's and the model's class
    counters: tuple  # what the step returns beside the loss (no splash kernel on the CPU)
    init_kwargs: dict = dataclasses.field(default_factory=dict)  # of the `model.init` that makes the program's tree
    norms: tuple = ()  # the layers' norm weights the tests move away from one
    move_outer: Callable | None = None  # ... and what else of the seeded weights they move
    logit_rows: dict = dataclasses.field(default_factory=dict)
    gradient_rows: dict = dataclasses.field(default_factory=dict)
    reference_loss: Callable | None = None  # (family, cfg, weights, text) -> (loss, parts)
    check_parts: Callable | None = None  # (counters, parts, cfg, text): the step's counters against the reference's parts
    check_steps: Callable | None = None  # (counters of three steps, the reference's steps, losses)
    bias_layers: tuple = ()  # the layers whose router holds a buffer
    shares: dict | None = None  # the expert layer cut into shares: its index, and the config it is cut under
    shared_alone: Callable | None = None  # (reference, dims, layer, u) -> the shared expert's output
    refuses: dict | None = None  # {"scan": regex, "cache": regex, "config": [(regex, overrides)]}
    pinned_wrapper: dict = dataclasses.field(default_factory=lambda: {"reset_position_ids": True})


# ---- the families' own readings of their references

def summed_row_loss(f: Family, cfg: dict, weights: dict, text: jax.Array, **faults):
    m = f.W.model_dims(cfg)
    loss_sum, z_sum, count, _ = f.reference.sequence_loss_terms(m, weights, text[0], **faults)
    return (loss_sum + m["z_loss_coef"] * z_sum) / jnp.maximum(count, 1.0), None


def joyai_loss(f: Family, cfg: dict, weights: dict, text: jax.Array):
    m = f.W.model_dims(cfg)
    counts = [jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0) for mask in f.reference.label_masks(m, text[0])]
    main, second, _ = f.reference.sequence_loss_terms(m, weights, text[0])
    main_loss = (main[0] + m["z_loss_coef"] * main[1]) / counts[0]
    mtp_loss = (second[0] + m["z_loss_coef"] * second[1]) / counts[1]
    return main_loss + m["mtp_coef"] * mtp_loss, (main_loss, mtp_loss)


def joyai_parts(counters, parts, cfg, text):
    np.testing.assert_allclose([counters["main_loss"], counters["mtp_loss"]], parts, rtol=2e-5)
    assert int(counters["mtp_targets"]) == int(jnp.sum(joyai_flash.label_masks(weights_joyai_flash.model_dims(cfg), text[0])[1]))


def joyai_steps(counters, ref, losses):
    """... each loss's two parts, and the loss being their weighted sum."""
    parts = [(float(c["main_loss"]), float(c["mtp_loss"])) for c in counters]
    np.testing.assert_allclose(parts, list(zip(ref["main_losses"], ref["mtp_losses"])), rtol=2e-5)
    np.testing.assert_allclose(losses, [a + 0.3 * b for a, b in parts], rtol=1e-6)


def ouro_loss(f: Family, cfg: dict, weights: dict, text: jax.Array):
    return f.reference.batch_loss(cfg, weights, text)


def ouro_parts(counters, parts, cfg, text):
    for t in range(4):
        np.testing.assert_allclose(counters[f"pass_loss_{t + 1}"], parts["pass_ce"][t], rtol=2e-5)
        np.testing.assert_allclose(counters[f"exit_mass_{t + 1}"], parts["exit_mass"][t], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(counters["weighted_loss"], parts["weighted"], rtol=2e-5)
    np.testing.assert_allclose(counters["exit_entropy"], parts["entropy"], rtol=2e-5)
    np.testing.assert_allclose(counters["last_pass_loss"], parts["pass_ce"][3], rtol=2e-5)
    assert abs(sum(float(counters[f"exit_mass_{t + 1}"]) for t in range(4)) - 1.0) < 1e-5


def ouro_steps(counters, ref, losses):
    """... each pass's loss and exit mass from the step's counters."""
    for mine, pass_losses, mass in zip(counters, ref["pass_losses"], ref["exit_mass"]):
        np.testing.assert_allclose([mine[f"pass_loss_{t + 1}"] for t in range(4)], pass_losses, rtol=2e-5)
        np.testing.assert_allclose([mine[f"exit_mass_{t + 1}"] for t in range(4)], mass, rtol=1e-4)


def ouro_outer(weights: dict, cfg: dict) -> None:
    """The final norm away from one, and a gate away from one half, so that the passes weigh unevenly."""
    weights["outer"]["ln_f"] = 1.0 + 0.2 * jnp.sin(jnp.arange(cfg["n_embd"], dtype=jnp.float32))
    weights["outer"]["gate_w"] = 5.0 * weights["outer"]["gate_w"]
    weights["outer"]["gate_b"] = jnp.asarray([0.3], jnp.float32)


def experts_of(reference: ModuleType) -> Callable:
    """The reference's expert layer (the tower's is one mixer among three)."""
    return getattr(reference, "experts", None) or reference.experts_mixer


def without_routed_experts(reference, dims, layer, u):
    return experts_of(reference)(dict(dims, held=0), layer, u)


EXPERT_FAMILY_REFUSES = [("experts_held", dict(experts_held=[30, 8])), ("position_embedding_type", dict(position_embedding_type="alibi"))]

FAMILIES = {
    "nemotron_h": Family(
        cfg=dict(
            COMMON, model_type="nemotron_h", n_layer=5, hybrid_override_pattern="MEM*E", num_key_value_heads=2, attention_head_dim=16,
            mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=16,
            **dict(EXPERTS, num_experts_per_tok=6, moe_intermediate_size=24), moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
        ),
        W=weights_nemotron_h, reference=nemotron_h_tower, classes=("NemotronHConfig", "NemotronHForCausalLM"), counters=STEP_COUNTERS,
        gradient_rows={"three_documents": TWO_AND_THREE["three_documents"]}, reference_loss=summed_row_loss,
        bias_layers=(1, 4), shares=dict(layer=1), shared_alone=without_routed_experts,
        refuses=dict(
            scan="scan_layers with nemotron_h", cache="nemotron_h has no generation cache.*Mamba state.*ROADMAP M2",
            config=EXPERT_FAMILY_REFUSES + [("pattern", dict(hybrid_override_pattern="MEM-E")), ("names 4 layers", dict(hybrid_override_pattern="MEM*"))],
        ),
        pinned_wrapper={},
    ),
    "joyai_llm_flash": Family(
        cfg=dict(
            COMMON, model_type="joyai_llm_flash", n_layer=3, n_inner=48, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, rope_theta=32e6, first_k_dense_replace=1, **EXPERTS, n_shared_experts=1, routed_scaling_factor=2.5,
            num_nextn_predict_layers=1, mtp_loss_coef=0.3,
        ),
        W=weights_joyai_flash, reference=joyai_flash, classes=("JoyAIFlashConfig", "JoyAIFlashForCausalLM"), counters=STEP_COUNTERS + LOSS_PARTS,
        init_kwargs={"compute_loss": True}, logit_rows=TWO_AND_THREE, gradient_rows=TWO_AND_THREE,
        reference_loss=joyai_loss, check_parts=joyai_parts, check_steps=joyai_steps,
        bias_layers=(1, 2, 3), shares=dict(layer=1), shared_alone=without_routed_experts,
        refuses=dict(
            scan="scan_layers with joyai_llm_flash", cache="joyai_llm_flash has no generation cache.*latent page.*ROADMAP M5",
            config=EXPERT_FAMILY_REFUSES + [("depth 1", dict(num_nextn_predict_layers=2))],
        ),
    ),
    "lfm2_moe": Family(
        cfg=dict(
            COMMON, model_type="lfm2_moe", n_layer=5, n_inner=48, num_key_value_heads=2, layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            num_dense_layers=1, conv_L_cache=3, rope_theta=1e6, **EXPERTS, routed_scaling_factor=1.0,
        ),
        W=weights_lfm2_moe, reference=lfm2_moe, classes=("Lfm2MoeConfig", "Lfm2MoeForCausalLM"), counters=STEP_COUNTERS,
        init_kwargs={"compute_loss": True}, norms=("q_norm_weight", "k_norm_weight", "ln_1", "ln_2"),
        logit_rows=TWO_AND_THREE, gradient_rows=TWO_AND_THREE, reference_loss=summed_row_loss,
        bias_layers=(1, 2, 3, 4), shares=dict(layer=2),
        refuses=dict(
            scan="scan_layers with lfm2_moe", cache="lfm2_moe has no generation cache.*convolution's taps.*ROADMAP M2",
            config=EXPERT_FAMILY_REFUSES + [
                ("names 4 layers", dict(layer_types=["conv"] * 4)),
                ("conv and full_attention", dict(layer_types=["conv", "sliding_attention", "conv", "conv", "conv"])),
                ("conv_bias", dict(conv_bias=True)), ("use_expert_bias", dict(use_expert_bias=False)),
            ],
        ),
    ),
    "afmoe": Family(
        cfg=dict(
            COMMON, model_type="afmoe", n_layer=5, n_inner=48, num_key_value_heads=2, attention_head_dim=8, layer_types=AFMOE_KINDS, sliding_window=12,
            num_dense_layers=1, rope_theta=10000, **EXPERTS, num_shared_experts=1, route_scale=2.826,
        ),
        W=weights_afmoe, reference=afmoe, classes=("AfmoeConfig", "AfmoeForCausalLM"), counters=STEP_COUNTERS,
        init_kwargs={"compute_loss": True}, norms=("q_norm_weight", "k_norm_weight") + weights_afmoe.NORMS,
        # documents longer than the window (12) and shorter than it; in `odd_window_7` a window that no block of the kernel divides
        logit_rows={
            "longer_than_the_window": one_row(23, 41), "shorter_and_longer": one_row(7, 40, 17),
            "every_document_inside_the_window": one_row(11, 12, 9, 12, 10, 10), "odd_window_7": one_row(7, 40, 17, sliding_window=7),
        },
        gradient_rows={"longer_than_the_window": one_row(23, 41), "shorter_and_longer": one_row(7, 40, 17)},
        reference_loss=summed_row_loss, bias_layers=(1, 2, 3, 4),
        # sixteen shares of 8 of 128 experts: the deployment's split, at a small width
        shares=dict(layer=2, num_experts=128, num_experts_per_tok=8),
        shared_alone=lambda reference, dims, layer, u: reference.swiglu(u, layer["shared_c_fc"], layer["shared_c_proj"]),
        refuses=dict(
            scan="scan_layers with afmoe.*attention's kind", cache="afmoe has no generation cache.*per-layer page budgets.*ROADMAP M6",
            config=EXPERT_FAMILY_REFUSES + [
                ("names 4 layers", dict(layer_types=["full_attention"] * 4)),
                ("sliding_attention and full_attention", dict(layer_types=["conv"] + AFMOE_KINDS[1:])),
                ("sliding_window 0", dict(sliding_window=0)), ("score_func", dict(score_func="softmax")),
                ("qk_norm / attention_output_gate", dict(attention_output_gate=False)), ("tie_word_embeddings", dict(tie_word_embeddings=True)),
            ],
        ),
    ),
    "ouro": Family(
        cfg=dict(COMMON, model_type="ouro", n_layer=2, n_inner=48, total_ut_steps=4, rope_theta=1e6),
        W=weights_ouro, reference=ouro, classes=("OuroConfig", "OuroForCausalLM"), counters=pass_step_counter_names(4),
        init_kwargs={"compute_loss": True},
        norms=("ln_1", "ln_1_out", "ln_2", "ln_2_out"), move_outer=ouro_outer,
        gradient_rows={
            name: Case((((23, 41), 1), ((10, 37, 17), 2)), {"fused_lm_head_loss": fused})
            for name, fused in (("chunked_head", True), ("whole_logits", False))
        },
        reference_loss=ouro_loss, check_parts=ouro_parts, check_steps=ouro_steps,
    ),
}

# sha256 of (the parameter tree, the lowered train step) and the step's lines: bfloat16, `full` remat
# every block, `skip_nonfinite`, the counters beside the loss, at the families' configs above. Each was
# taken on the commit BEFORE the change it was to hold still, on this installation (jax 0.9.0): the
# tower's tree at PR 30 (its expert layer moved to `shared_expert_moe.py`), joyai's at PR 33 (a third
# family shared that layer), ouro's, lfm2's and the dense model's at PR 40 (`Attention` took a window,
# a gate and positions by layer; the four-norm block moved), afmoe's at PR 44 (the four unrolled stacks
# became `models/unrolled_stack.py`; the other five passed it untouched). A change of one of these
# programs ON PURPOSE takes its text anew and says so — the tower's and joyai's did at PR 34 (the expert
# layer's row movements became loops: 6527 / 8214 lines before), PR 37 (the activation walks blocks of
# rows: 6970 / 8862 before) and PR 39 (the head's logits once: 7270 / 9264 before).
PINNED_STEPS = {
    "nemotron_h": ("36990d468b39e5c040180d1e45a25dac74eeeb1d513dc6bc37c03b12fe9fca5b", 7104, "dfec910f7fc86d2ca344ad7ad9e65ee52dde2439295c3b1aa2d148931e9bc880"),
    "joyai_llm_flash": ("4f3c47802e75a87cbdc4288b995f51a06c50485f568ee733f27eb4296b8d833b", 8979, "c86fd38c4d2ebde4f9169ec711ea30c7a3bb05972644b0b649ba51eef1995b9a"),
    "lfm2_moe": ("fc7c87885542e98aa56749b3e6a7539f93807f25092603fd1e49b840f3beefaf", 7800, "a553fd571935354fb599047c27b4c2023d2e572db75f3c69fc26d813decce770"),
    "ouro": ("b8853daa465c2428ee01123becaae40f4e606c618102b5fd88a57f8bb7e94080", 3077, "39d711fe836f9d2642fcd83e8e7f1be3d097b9178088e406547a3b8694cd41f5"),
    "afmoe": ("51a24313a6bef304cb8b95535646354eacfa638aeae0fd20eea3a73f404613cc", 11093, "26833a5b1f9d3110a3927ea20650ce2b1777092bff6d515f8b51dcde72d47644"),
    "gpt_dolomite": ("de94680e0fc8ef7a359ba9d96a7939ae553ec83eb80ea7fa490f7492b52ee8a4", 1903, "6a96f4765c3c02a4bed9513711bcab89867e0bdfb42ba4a84cd99ac8437cd478"),
}
GPT_DOLOMITE_CFG = dict(
    COMMON, model_type="gpt_dolomite", n_layer=2, n_inner=48, num_key_value_heads=2, attention_head_type="gqa", position_embedding_type="rope",
    activation_function="swiglu", normalization_function="rmsnorm", add_bias=False,
)


# ---- rows, models and weights

def packed_row(docs, seed=1, length=COMMON["n_positions"], vocab=COMMON["vocab_size"]) -> np.ndarray:
    """[length + 1] tokens: documents of the given lengths, each ending in eos (0), the rest one more."""
    rng = np.random.default_rng(seed)
    text = rng.integers(1, vocab, size=length + 1).astype(np.int32)
    text[np.cumsum(docs) - 1] = 0
    return text


def text_of(case: Case) -> jax.Array:
    return jnp.asarray(np.stack([packed_row(docs, seed) for docs, seed in case.rows]))


def batches(steps=3, rows=2, seed=0) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        text = rng.integers(1, COMMON["vocab_size"], size=(rows, COMMON["n_positions"] + 1)).astype(np.int32)
        for row in text:
            row[rng.integers(5, 60, size=2)] = 0  # document boundaries (eos)
        out.append(text)
    return out


def wrapper_for(cfg: dict, zero_stage=0, **kwargs) -> ModelWrapperForPretraining:
    return ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="fp32", sequence_length=cfg["n_positions"],
        reset_attention_mask=True, reset_position_ids=True, zero_stage=zero_stage, **kwargs,
    )


def model_of(name: str, cfg: dict | None = None, **kwargs) -> nn.Module:
    return get_model_class(name)(config=config_from_dict(cfg or FAMILIES[name].cfg), **kwargs)


def seeded_weights(name: str, cfg: dict, seed=3) -> dict:
    """`make_all`'s, with the norm weights away from one, so that a norm in the wrong place or with
    the wrong weight shows."""
    f = FAMILIES[name]
    weights = f.W.make_all(cfg, seed)
    for i, layer in enumerate(weights["layers"]):
        for norm in f.norms:
            if norm in layer:
                layer[norm] = 1.0 + 0.3 * jnp.cos(jnp.arange(layer[norm].shape[0], dtype=jnp.float32) + i + len(norm))
    if f.move_outer:
        f.move_outer(weights, cfg)
    return weights


def built(name: str, **overrides) -> tuple:
    """(the model, the seeded weights, the same in the program's tree, the config) of a family
    under `overrides` of its config: made once a process."""
    return _built(name, json.dumps(overrides, sort_keys=True))


@functools.cache
def _built(name: str, overrides: str) -> tuple:
    cfg = dict(FAMILIES[name].cfg, **json.loads(overrides))
    weights = seeded_weights(name, cfg)
    return model_of(name, cfg), weights, FAMILIES[name].W.unrolled_program_tree(weights, cfg), cfg


@functools.cache
def program_tree(name: str) -> dict:
    """The shapes of the parameter tree the program makes for itself."""
    model = model_of(name)
    return nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), **FAMILIES[name].init_kwargs))["params"])


# ---- the program's and the reference's results, once a process

@functools.cache
def program_logits(name: str, case: str) -> jax.Array:
    """[T, V] logits of the case's one row."""
    model, _, params, cfg = built(name, **FAMILIES[name].logit_rows[case].overrides)
    batch = wrapper_for(cfg).prepare_inputs_and_labels(text_of(FAMILIES[name].logit_rows[case]))
    run = jax.jit(lambda p, ids, positions, segments: model.apply({"params": p}, ids, position_ids=positions, segment_ids=segments).logits)
    with jax.default_matmul_precision("highest"):
        return run(params, batch["input_ids"], batch["position_ids"], batch["segment_ids"])[0]


@functools.cache
def program_loss_and_grads(name: str, case: str) -> tuple:
    """((loss, counters), gradients) through the trainer's wrapper, every block rematerialized."""
    rows = FAMILIES[name].gradient_rows[case]
    _, _, params, cfg = built(name, **rows.overrides)
    wrapper = wrapper_for(cfg, gradient_checkpointing_args={"checkpoint_every": 1})
    text = text_of(rows)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: wrapper.loss(p, text, train=True), has_aux=True))(params)


def reference_loss_and_grads(name: str, case: str, **faults) -> tuple:
    """((loss, parts), gradients in the weights' own tree) of the plain reference; `faults` are
    arguments of the reference alone."""
    return _reference_loss_and_grads(name, case, json.dumps(faults, sort_keys=True))


@functools.cache
def _reference_loss_and_grads(name: str, case: str, faults: str) -> tuple:
    f = FAMILIES[name]
    _, weights, _, cfg = built(name, **f.gradient_rows[case].overrides)
    text = text_of(f.gradient_rows[case])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: f.reference_loss(f, cfg, p, text, **json.loads(faults)), has_aux=True))(weights)


def trainer_optimizer(config):
    schedule = get_scheduler(0, 0, None, 10, LRDecaySchedule.constant, 0.1, base_lr=OPTIMIZER["lr"])
    return get_optimizer("TorchAdamW", {k: OPTIMIZER[k] for k in ("weight_decay", "betas", "eps")}, schedule, model_config=config)


def lowered_step_hashes(cfg: dict, wrapper_kwargs: dict, init_kwargs: dict) -> tuple:
    """`PINNED_STEPS`' three readings of a config's train step."""
    wrapper = ModelWrapperForPretraining(
        mode=Mode.training, pretrained_config=cfg, dtype="bf16", sequence_length=cfg["n_positions"], reset_attention_mask=True,
        zero_stage=0, gradient_checkpointing_args={"checkpoint_every": 1}, **wrapper_kwargs,
    )
    optimizer = trainer_optimizer(wrapper.config)

    def init():
        params = nn.unbox(wrapper.model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), **init_kwargs)["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=optimizer.init(params), fp8=None)

    state = jax.eval_shape(init)
    step = jax.jit(make_train_step(
        lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True), optimizer,
        gradient_clipping=1.0, skip_nonfinite=True, has_aux=bool(wrapper.step_counter_names),
    ))
    text = step.lower(
        state, {"text": jax.ShapeDtypeStruct((1, 2, cfg["n_positions"] + 1), jnp.int32)}, jax.ShapeDtypeStruct((2,), jnp.uint32)
    ).as_text()
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)), state.params))
    return hashlib.sha256(tree.encode()).hexdigest(), len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()


def check_the_lowered_step(name: str) -> None:
    """The parameter tree and the lowered train step of `name` are, letter for letter, what
    `PINNED_STEPS` holds (the failing assert prints the new values)."""
    if name in FAMILIES:
        got = lowered_step_hashes(FAMILIES[name].cfg, FAMILIES[name].pinned_wrapper, FAMILIES[name].init_kwargs)
    else:
        got = lowered_step_hashes(GPT_DOLOMITE_CFG, {"reset_position_ids": True}, {"compute_loss": True})
    assert got == PINNED_STEPS[name]


def leaf_norms(f: Family, tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in f.W.leaves_by_name(tree).items()}


# ---- the tests

def contract_tests(name: str) -> dict:
    """The contract's tests of family `name`, by the names its test file collects them under."""
    f = FAMILIES[name]
    cfg, W, reference = f.cfg, f.W, f.reference

    def test_registered_under_its_model_type_and_the_seeded_weights_fit_the_program_tree():
        assert (get_config_class(name).__name__, get_model_class(name).__name__) == f.classes
        model, _, params, _ = built(name)
        assert model.step_counter_names == f.counters
        own = program_tree(name)
        assert jax.tree.structure(own) == jax.tree.structure(params)
        assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, own)) == jax.tree.leaves(jax.tree.map(lambda a: a.shape, params))
        assert len(W.leaves_by_name(params)) == len(jax.tree.leaves(params))
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == W.count_parameters(cfg)["total"]

    @pytest.mark.parametrize("case", f.logit_rows)
    def test_logits_of_a_packed_row_follow_the_reference(case):
        (docs, _), = f.logit_rows[case].rows
        model, weights, params, case_cfg = built(name, **f.logit_rows[case].overrides)
        text = text_of(f.logit_rows[case])[0]
        mine = program_logits(name, case)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda w, tokens: reference.forward_logits(case_cfg, w, tokens))(weights, text[:-1])
            np.testing.assert_allclose(mine, ref, rtol=2e-4, atol=2e-4)
            # and the documents do not see each other: a document alone gives its part of the row
            first = jax.jit(lambda p, ids: model.apply({"params": p}, ids).logits)(params, text[None, : docs[0]])
            np.testing.assert_allclose(mine[: docs[0]], first[0], rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("case", f.gradient_rows)
    def test_the_loss_and_every_leaf_s_gradient_follow_the_reference(case):
        (loss, counters), grads = program_loss_and_grads(name, case)
        (ref_loss, parts), ref_grads = reference_loss_and_grads(name, case)
        case_cfg = built(name, **f.gradient_rows[case].overrides)[3]
        np.testing.assert_allclose(loss, ref_loss, rtol=2e-5)
        if f.bias_layers:
            assert counters["held_expert_rows"].shape == (len(f.bias_layers), 8)
        if f.check_parts:
            f.check_parts(counters, parts, case_cfg, text_of(f.gradient_rows[case]))
        mine, ref = W.leaves_by_name(grads), W.leaves_by_name(W.unrolled_program_tree(ref_grads, case_cfg))
        assert set(mine) == set(ref)
        for leaf_name, leaf in ref.items():
            if leaf_name.endswith(BIAS):
                assert float(jnp.abs(mine[leaf_name]).max()) == 0.0 == float(jnp.abs(leaf).max())  # a buffer: no gradient reaches it
                continue
            assert float(jnp.abs(leaf).max()) > 0, leaf_name
            np.testing.assert_allclose(mine[leaf_name], leaf, rtol=2e-3, atol=2e-3 * float(jnp.abs(leaf).max()), err_msg=leaf_name)

    def test_the_trainer_s_step_follows_the_reference_and_holds_the_buffers():
        """Three steps of `make_train_step` (the loss through `ModelWrapperForPretraining`, AdamW
        from `get_optimizer` with the routers' buffers held) against the reference's three steps:
        each loss, the first gradient's per-leaf norms, the parameters' change (a router's bias:
        none, weight decay or not), the counters of the layers of experts, and what else the
        family's step counts."""
        seed = 11
        wrapper = wrapper_for(cfg, gradient_checkpointing_args={"checkpoint_every": 1})
        assert wrapper.step_counter_names == f.counters
        optimizer = trainer_optimizer(wrapper.config)
        start = W.unrolled_program_tree(W.make_all(cfg, seed), cfg)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=start, opt_state=optimizer.init(start), fp8=None)
        step = jax.jit(make_train_step(
            lambda p, micro, rng: wrapper.loss(p, micro["text"], rngs=None, train=True), optimizer,
            gradient_clipping=OPTIMIZER["gradient_clipping"], has_aux=True,
        ))
        data = batches()
        losses, counters, first_nu = [], [], None
        with jax.default_matmul_precision("highest"):
            for text in data:
                state, metrics = step(state, {"text": jnp.asarray(text)[None]}, jax.random.PRNGKey(0))
                losses.append(float(metrics["loss"]))
                counters.append(jax.device_get(metrics["counters"]))
                if first_nu is None:
                    adam = [s for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "nu")) if hasattr(s, "nu")][0]
                    first_nu = adam.nu
        ref = reference.train_steps(cfg, seed, data, OPTIMIZER)

        np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
        b2 = OPTIMIZER["betas"][1]
        grad_norms = {k: float(np.sqrt(np.sum(v) / (1 - b2))) for k, v in W.leaves_by_name(first_nu).items()}
        gap, where = compare.worst_leaf_gap(grad_norms, ref["grad_norms"])
        assert gap < 2e-3, (gap, where)
        delta_norms = leaf_norms(f, jax.tree.map(lambda a, b: a - b, state.params, start))
        gap, where = compare.worst_leaf_gap(delta_norms, ref["delta_norms"])
        assert gap < 2e-3, (gap, where)
        assert sorted(k for k in delta_norms if k.endswith(BIAS)) == [f"layer{layer}.{BIAS}" for layer in f.bias_layers]
        for layer in f.bias_layers:  # the buffers stayed where the seed put them, weight decay or not; everything else moved
            assert delta_norms[f"layer{layer}.{BIAS}"] == 0.0 == ref["delta_norms"][f"layer{layer}.{BIAS}"]
        assert min(v for k, v in delta_norms.items() if not k.endswith(BIAS)) > 0
        for mine, facts in zip(counters, ref["routing"] if f.bias_layers else ()):
            assert mine["held_expert_rows"].shape == (len(f.bias_layers), 8)
            np.testing.assert_allclose(mine["held_expert_rows"], np.asarray(facts["held_expert_rows"]), atol=2)  # a near-tie may fall either way
        if f.check_steps:
            f.check_steps(counters, ref, losses)

    def test_the_shares_add_up_to_the_reference_s_uncut_layer():
        """The expert layer cut into shares of 8 experts: every share is a slice of the uncut
        layer's banks and gives what the reference's share gives; the shares' routed parts, added,
        and the shared expert ONCE (where the family has one) are the reference's layer with all
        the experts; every token-slot was some share's."""
        layer, base = f.shares["layer"], dict(cfg, **{k: v for k, v in f.shares.items() if k != "layer"})
        experts = experts_of(reference)
        cfg_all = dict(base, experts_held=None)
        u = jnp.asarray(np.random.default_rng(4).normal(size=(1, 48, cfg["n_embd"])).astype(np.float32))
        with jax.default_matmul_precision("highest"):
            p_all = W.make_layer(cfg_all, 9, layer)
            whole = experts(W.model_dims(cfg_all), p_all, u[0])
            shared = f.shared_alone(reference, W.model_dims(cfg_all), p_all, u[0]) if f.shared_alone else jnp.zeros_like(whole)
            total, rows = jnp.zeros_like(whole), 0
            for first in range(0, base["num_experts"], 8):
                share = dict(base, experts_held=[first, 8])
                p = W.make_layer(share, 9, layer)
                banks = [k for k in ("c_fc", "c_proj", "shared_c_fc", "shared_c_proj") if k in p]
                assert p["c_fc"].shape[0] == 8 and ("shared_c_fc" in banks) == bool(f.shared_alone)
                np.testing.assert_array_equal(p["c_fc"], p_all["c_fc"][first : first + 8])  # the share IS a slice
                params = {"gate": p["gate"], BIAS: p[BIAS], **{k: {"kernel": p[k]} for k in banks}}
                out, counted = SharedExpertMoE(config=config_from_dict(share)).apply({"params": params}, u)
                np.testing.assert_allclose(out[0], experts(W.model_dims(share), p, u[0]), rtol=1e-4, atol=1e-5)
                assert int(counted["routed_slots"]) + int(counted["absent_slots"]) == 48 * base["num_experts_per_tok"]
                total, rows = total + (out[0] - shared), rows + int(counted["routed_slots"])
        np.testing.assert_allclose(total + shared, whole, rtol=1e-4, atol=1e-5)
        assert rows == 48 * base["num_experts_per_tok"]  # every token-slot was some share's

    def test_what_the_family_refuses(eight_devices):
        from dolomite_engine_tpu.parallel.mesh import MeshManager

        ids = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(ValueError, match=f.refuses["scan"]):
            model_of(name, scan_layers=True).init(jax.random.PRNGKey(0), ids)
        model, _, params, _ = built(name)
        with pytest.raises(NotImplementedError, match=f.refuses["cache"]):
            model.apply({"params": params}, ids, kv_caches=[None] * cfg["n_layer"], cache_index=0)
        with pytest.raises(NotImplementedError, match=f"{name} has no generation cache"):
            model.init_kv_caches(1, 16)
        for match, overrides in f.refuses["config"]:
            with pytest.raises(ValueError, match=match):
                config_from_dict(dict(cfg, **overrides))
        for axis, kwargs in (("tp", dict(tensor_parallel_size=2)), ("ep", dict(expert_parallel_size=2))):
            MeshManager(**kwargs)
            try:
                with pytest.raises(ValueError, match=f"{name} on a mesh with {axis} > 1"):
                    model.init(jax.random.PRNGKey(0), ids)
            finally:
                MeshManager.destroy()

    def test_the_lowered_step_is_what_it_was():
        check_the_lowered_step(name)

    tests = dict(locals())
    reads = {
        "test_logits_of_a_packed_row_follow_the_reference": f.logit_rows,
        "test_the_loss_and_every_leaf_s_gradient_follow_the_reference": f.gradient_rows,
        "test_the_shares_add_up_to_the_reference_s_uncut_layer": f.shares,
        "test_what_the_family_refuses": f.refuses,
    }
    return {k: v for k, v in tests.items() if k.startswith("test_") and reads.get(k, True)}
